"""graft-gauge tests (ISSUE 19, marker ``serve``; docs/serving.md §14).

Covers the online-recall estimator end to end: the Wilson interval
math, the batcher's bounded best-effort shadow lane (drop-oldest, no
live backpressure), the oracle-rung selection that keeps a crippled
swap from scoring itself perfect, the :class:`QualityMonitor` closed
loop — estimates, bounded tighten/relax retunes with hysteresis, swap
probation with expiry and rollback — driven through a stub serving
unit, the fleet-level quality view (``Fabric.recall_estimates`` /
helm quality alarms), and the live server integration: the off-path
contracts (rate=0 → no monitor; obs off → the shadow lane stays dark
and retains nothing), shadow sampling through a real server with
zero steady-state retraces, and the ``slow``-marked swap-probation
rollback acceptance."""

import os
import threading
import time
import tracemalloc
import types
from concurrent.futures import Future

import numpy as np
import pytest

from raft_tpu import obs, serve, tuning
from raft_tpu.analysis import lockwatch
from raft_tpu.neighbors import ivf_flat
from raft_tpu.resilience import faultinject
from raft_tpu.serve import engine as serve_engine
from raft_tpu.serve import quality
from raft_tpu.serve.adaptive import AdaptivePolicy
from raft_tpu.serve.batcher import Batch, MicroBatcher, Request
from raft_tpu.serve.controller import HelmController
from raft_tpu.serve.fabric import Fabric
from raft_tpu.serve.quality import QualityMonitor, ShadowSample, \
    wilson_interval
from raft_tpu.serve.registry import Registry

pytestmark = [pytest.mark.serve, pytest.mark.threadsan]

N, DIM = 320, 16


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    monkeypatch.setenv(lockwatch.ENV_VAR, "1")
    monkeypatch.delenv("RAFT_TPU_OBS", raising=False)
    obs.set_mode(None)
    obs.reset()
    faultinject.clear()
    yield
    obs.reset()
    obs.set_mode(None)
    faultinject.clear()
    tuning.reload()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(42)
    x = rng.standard_normal((N, DIM)).astype(np.float32)
    q = rng.standard_normal((24, DIM)).astype(np.float32)
    return x, q


def _params(**kw):
    kw.setdefault("max_batch_rows", 16)
    kw.setdefault("max_wait_ms", 1.0)
    kw.setdefault("max_k", 8)
    return serve.ServeParams(**kw)


def _value(snap, name, /, **labels):
    want = {str(k): str(v) for k, v in labels.items()}
    for p in snap["metrics"].get(name, {}).get("points", []):
        if all(p["labels"].get(k) == v for k, v in want.items()):
            return p.get("value", p)
    return None


# ---------------------------------------------------------------------------
# wilson interval
# ---------------------------------------------------------------------------


def test_wilson_interval_math():
    # no data -> the vacuous interval, not a crash
    assert wilson_interval(0, 0) == (0.0, 1.0)
    lo, hi = wilson_interval(9, 10)
    assert 0.0 <= lo < 0.9 < hi <= 1.0
    # perfect small-n success keeps an honest lower bound under 1
    lo, hi = wilson_interval(8, 8)
    assert hi == 1.0 and lo < 1.0
    # the interval narrows as n grows at fixed p
    lo_s, hi_s = wilson_interval(8, 16)
    lo_l, hi_l = wilson_interval(128, 256)
    assert (hi_l - lo_l) < (hi_s - lo_s)
    assert lo_l < 0.5 < hi_l
    # degenerate inputs clamp instead of escaping [0, 1]
    lo, hi = wilson_interval(20, 10)
    assert 0.0 <= lo <= hi <= 1.0


# ---------------------------------------------------------------------------
# the batcher's shadow lane
# ---------------------------------------------------------------------------


def _shadow_req(rows=1):
    return Request(queries=np.zeros((rows, 4), np.float32), k=1,
                   prefilter=None, future=Future())


def test_shadow_lane_bounded_drop_oldest():
    started = threading.Event()
    release = threading.Event()

    def dispatch(b):
        if not b.shadow:
            started.set()
            release.wait(timeout=10)

    mb = MicroBatcher(dispatch, max_batch_rows=8, max_wait_ms=0.0,
                      shadow_queue_rows=4, name="q")
    try:
        # park the dispatcher in a live batch so the shadow lane can
        # actually accumulate (it only drains when the thread is idle)
        mb.submit(np.zeros((1, 4), np.float32), 1)
        assert started.wait(timeout=10)
        reqs = [_shadow_req() for _ in range(6)]
        dropped = []
        for r in reqs:
            dropped += mb.submit_shadow(r)
        # past the 4-row cap the OLDEST queued samples fall out, in
        # order, and are handed BACK (the caller owns their pins)
        assert len(dropped) == 2
        assert dropped[0] is reqs[0] and dropped[1] is reqs[1]
        # a sample alone exceeding the cap bounces immediately
        big = _shadow_req(rows=5)
        assert mb.submit_shadow(big) == [big]
        left = mb.drain_shadow()
        assert len(left) == 4
        assert all(a is b for a, b in zip(left, reqs[2:]))
        assert mb.drain_shadow() == []        # rows accounting reset
        one = _shadow_req()
        assert mb.submit_shadow(one) == []    # space again after drain
        assert mb.drain_shadow() == [one]
        # live admission never saw shadow rows: the queue accepted a
        # full live load while the shadow lane churned above
        assert mb.depth_rows() == 0
    finally:
        release.set()
        mb.close()
    # closed batcher hands every sample straight back
    post = _shadow_req()
    assert mb.submit_shadow(post) == [post]


# ---------------------------------------------------------------------------
# oracle rung selection
# ---------------------------------------------------------------------------


def _orung(algo, n_lists=16, n_probes=4):
    stub = types.SimpleNamespace(
        algo=algo,
        index=types.SimpleNamespace(n_lists=n_lists),
        search_params=types.SimpleNamespace(n_probes=n_probes))
    return serve_engine._Handle.oracle_rung(stub)


def test_oracle_rung_outranks_any_serving_ceiling():
    # the under-trained-swap trap: a generation crippled to n_probes=1
    # must NOT be its own oracle — the full probe count is the truth
    assert _orung("ivf_flat", n_lists=16, n_probes=1) == 16
    assert _orung("ivf_pq", n_lists=32, n_probes=4) == 32
    # ceiling already at the top tier: the resolved exhaustive program
    # IS the oracle, no extra trace needed
    assert _orung("ivf_flat", n_lists=16, n_probes=16) is None
    # no probe axis to escalate
    assert _orung("brute_force") is None
    assert _orung("cagra") is None


def test_rung_params_override_on_non_adaptive_ivf():
    sp = ivf_flat.SearchParams(n_probes=2)
    stub = types.SimpleNamespace(algo="ivf_flat", adaptive=None,
                                 search_params=sp,
                                 pipeline_rr=lambda: 1)
    over, rr = serve_engine._Handle.rung_params(stub, 16)
    assert over.n_probes == 16 and rr == 1
    # rung=None hands back the resolved params verbatim
    verbatim, _ = serve_engine._Handle.rung_params(stub, None)
    assert verbatim is sp


def test_exact_tier_oracle_debiases_quantized_overscore():
    """ROADMAP 9(a): a quantized oracle scores its own quantization
    error as ground truth — candidates IT mis-ranks look "matched"
    whenever serving mis-ranks them the same way, so the recall
    estimate reads high exactly where it matters.  When the generation
    carries an exact tier (``dataset=`` / a RerankSource), the oracle
    rung becomes the exact-rerank PLAN (``"exact"``): exhaustive
    probing + exact re-rank, whose answers track true recall."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import brute_force, ivf_pq

    rng = np.random.default_rng(11)
    n, dim, k = 2048, 32, 8
    x = rng.standard_normal((n, dim)).astype(np.float32)
    q = rng.standard_normal((48, dim)).astype(np.float32)
    _, ti = brute_force.knn(q, x, k, metric="sqeuclidean")
    truth = [set(map(int, row)) for row in np.asarray(ti)]

    bp = ivf_pq.IndexParams(n_lists=16, pq_dim=4, metric="sqeuclidean")
    sp = ivf_pq.SearchParams(n_probes=4, local_recall_target=1.0)

    def overlap(ids, oracle_sets):
        ids = np.asarray(ids)
        return float(np.mean([
            len(set(map(int, ids[r])) & oracle_sets[r]) / k
            for r in range(ids.shape[0])]))

    with serve.Server(_params(warmup=False)) as srv:
        # generation WITH the exact tier (dataset kept)
        srv.create_index("a", x, algo="ivf_pq", build_params=bp,
                         search_params=sp, refine_ratio=32, warmup=False)
        ha = srv.registry.get("a").handle
        # same index WITHOUT an exact tier: the quantizer is all it has
        srv.add_index("b", ha.index, algo="ivf_pq", search_params=sp,
                      warmup=False)
        hb = srv.registry.get("b").handle

        # rung selection: the tier flips the oracle to the exact plan
        assert ha.oracle_rung() == "exact"
        assert hb.oracle_rung() == 16

        qd = jnp.asarray(q)
        _, served = hb.compiled(k, None)(qd)
        _, quant_oracle = hb.compiled(k, hb.oracle_rung())(qd)
        _, exact_oracle = ha.compiled(k, ha.oracle_rung())(qd)

        exact_sets = [set(map(int, row)) for row in np.asarray(exact_oracle)]
        quant_sets = [set(map(int, row)) for row in np.asarray(quant_oracle)]

        # the exact-tier oracle IS (near) ground truth; the quantized
        # oracle is not even close on a pq_dim=4 quantizer
        assert overlap(np.asarray(exact_oracle), truth) > 0.95
        assert overlap(np.asarray(quant_oracle), truth) < 0.8

        true_recall = overlap(served, truth)
        quant_scored = overlap(served, quant_sets)
        exact_scored = overlap(served, exact_sets)
        # quantized oracle OVER-scores the served answers...
        assert quant_scored > true_recall + 0.1
        # ...the exact-tier oracle does not (tracks true recall)
        assert abs(exact_scored - true_recall) < 0.05


# ---------------------------------------------------------------------------
# QualityMonitor closed loop (stub serving unit)
# ---------------------------------------------------------------------------


def _stub_serving(registry=None, warmup_enabled=False, **pkw):
    pkw.setdefault("quality_sample_rate", 1.0)
    pkw.setdefault("quality_band", 0.9)
    pkw.setdefault("quality_window", 8)
    pkw.setdefault("quality_min_samples", 4)
    warmed = []
    s = types.SimpleNamespace(
        params=serve.ServeParams(**pkw),
        registry=registry if registry is not None else Registry(),
        batcher=None,
        warmup_enabled=warmup_enabled,
        warmup_handle=warmed.append)
    s.warmed = warmed
    return s


def _feed(mon, gen, recalls, k=4, rung=None):
    """Score one synthetic shadow batch: sample i matches
    ``round(recalls[i] * k)`` of the oracle's k slots."""
    reqs, truth_rows = [], []
    for rc in recalls:
        m = int(round(rc * k))
        served = np.arange(k, dtype=np.int64)[None, :]
        truth = np.concatenate([np.arange(m),
                                np.arange(100, 100 + k - m)])
        gen.pin()
        reqs.append(Request(
            queries=np.zeros((1, DIM), np.float32), k=k,
            prefilter=None, future=Future(),
            shadow=ShadowSample(gen, rung, served, k)))
        truth_rows.append(truth.astype(np.int64))
    batch = Batch(requests=reqs, rows=len(reqs), bucket=len(reqs),
                  prefilter=None, rung=rung, shadow=True)
    try:
        mon.score_batch(batch, np.stack(truth_rows))
    finally:
        for r in reqs:
            r.shadow.gen.release()


def test_monitor_estimates_per_rung_and_masks_invalid_slots():
    serving = _stub_serving()
    gen = serving.registry.publish(
        "t", types.SimpleNamespace(adaptive=None))
    mon = QualityMonitor(serving, "t")
    _feed(mon, gen, [1.0] * 4, rung=2)
    _feed(mon, gen, [0.5] * 4, rung=8)
    st = mon.stats()
    assert st["samples"] == 8 and st["band"] == 0.9
    assert st["estimate"] == 0.75          # pooled 24/32
    assert st["ci_low"] < 0.75 < st["ci_high"] < 0.9
    assert st["slots"] == 32
    # masked -1 slots count for neither side: truth has 2 live slots,
    # served matches one of them -> 1/2, not 1/4
    gen2 = serving.registry.publish(
        "m", types.SimpleNamespace(adaptive=None))
    mon2 = QualityMonitor(serving, "m")
    gen2.pin()
    req = Request(queries=np.zeros((1, DIM), np.float32), k=4,
                  prefilter=None, future=Future(),
                  shadow=ShadowSample(
                      gen2, None,
                      np.array([[5, 7, -1, -1]], np.int64), 4))
    batch = Batch(requests=[req], rows=1, bucket=1, prefilter=None,
                  shadow=True)
    mon2.score_batch(batch, np.array([[5, 6, -1, -1]], np.int64))
    gen2.release()
    assert mon2.stats()["estimate"] == 0.5


def test_monitor_tighten_is_bounded_and_relax_is_exact():
    serving = _stub_serving(quality_max_retunes=2)
    pol = AdaptivePolicy.build(ceiling=8, list_cap=64)
    h = types.SimpleNamespace(adaptive=pol)
    gen = serving.registry.publish("t", h)
    mon = QualityMonitor(serving, "t")
    base_easy = pol.easy_margin

    _feed(mon, gen, [0.5] * 8)
    assert mon.stats()["retune_steps"] == 1
    assert h.adaptive.easy_margin == pytest.approx(
        min(base_easy * 2, 0.95))
    # the retune reset the window: verdicts come from post-retune
    # samples only
    assert mon.stats()["samples"] == 0 and mon.stats()["estimate"] is None
    _feed(mon, gen, [0.5] * 8)
    assert mon.stats()["retune_steps"] == 2
    # bounded: quality_max_retunes caps the ratchet
    _feed(mon, gen, [0.5] * 8)
    assert mon.stats()["retune_steps"] == 2
    # recovery: ci_low must clear band + hysteresis (k=8 gives the
    # window enough slots) before one exact relax step fires
    _feed(mon, gen, [1.0] * 8, k=8)
    st = mon.stats()
    assert st["retune_steps"] == 1
    assert [a[0] for a in st["actions"]] == \
        ["tighten", "tighten", "relax"]
    # relax is base.tightened()^1, not a drifting inverse
    assert h.adaptive.easy_margin == pytest.approx(
        min(base_easy * 2, 0.95))


def test_monitor_defers_refine_rewarm_out_of_the_lock():
    # refine_ratio=2 -> tightened() doubles the over-fetch, the refine
    # ladder grows, and the re-warm must run AFTER the monitor lock is
    # released (the GL013 quality->mutation edge), via the serving unit
    serving = _stub_serving(warmup_enabled=True)
    pol = AdaptivePolicy.build(ceiling=8, list_cap=64, refine_ratio=2)
    h = types.SimpleNamespace(adaptive=pol)
    gen = serving.registry.publish("t", h)
    mon = QualityMonitor(serving, "t")
    _feed(mon, gen, [0.5] * 8)
    assert serving.warmed == [h]
    assert mon._deferred_rewarm is None
    assert h.adaptive.refine_ladder() != pol.refine_ladder()


def test_monitor_probation_rollback_restores_predecessor():
    serving = _stub_serving(quality_min_samples=4, quality_retune=False)
    handle_a = types.SimpleNamespace(adaptive=None)
    gen1 = serving.registry.publish("t", handle_a)
    mon = QualityMonitor(serving, "t")
    _feed(mon, gen1, [1.0] * 8)                # healthy baseline
    assert mon.stats()["estimate"] == 1.0

    mon.before_publish()                        # Server._publish_guarded
    gen2 = serving.registry.publish(
        "t", types.SimpleNamespace(adaptive=None))
    mon.after_publish(gen2)
    assert mon.stats()["probation_open"]
    assert mon.stats()["estimate"] is None      # successor starts fresh

    _feed(mon, gen2, [0.5] * 8)                 # the swap degraded
    st = mon.stats()
    assert [a[0] for a in st["actions"]] == ["rollback"]
    detail = st["actions"][0][1]
    assert detail["to_version"] == 1 and detail["prev_estimate"] == 1.0
    cur = serving.registry.get("t")
    assert cur.version == 3 and cur.handle is handle_a
    assert not st["probation_open"]
    # fresh verdicts for the restored generation
    assert st["samples"] == 0 and st["estimate"] is None


def test_monitor_probation_expires_and_releases_the_pin():
    serving = _stub_serving()
    gen1 = serving.registry.publish(
        "t", types.SimpleNamespace(adaptive=None))
    mon = QualityMonitor(serving, "t")
    _feed(mon, gen1, [1.0] * 8)
    mon.before_publish()
    gen2 = serving.registry.publish(
        "t", types.SimpleNamespace(adaptive=None))
    mon.after_publish(gen2)
    assert not gen1.drained.is_set()    # probation pin holds it alive
    # the successor holds the band for a full window of its own samples
    _feed(mon, gen2, [1.0] * 8)
    st = mon.stats()
    assert not st["probation_open"] and not st["actions"]
    assert serving.registry.get("t").version == 2
    # probation's was the last pin: expiry lets the predecessor drain
    assert gen1.drained.is_set()


def test_monitor_rollback_disabled_leaves_the_swap():
    serving = _stub_serving(quality_rollback=False,
                            quality_retune=False)
    gen1 = serving.registry.publish(
        "t", types.SimpleNamespace(adaptive=None))
    mon = QualityMonitor(serving, "t")
    _feed(mon, gen1, [1.0] * 8)
    mon.before_publish()
    gen2 = serving.registry.publish(
        "t", types.SimpleNamespace(adaptive=None))
    mon.after_publish(gen2)
    _feed(mon, gen2, [0.5] * 8)
    assert not mon.stats()["actions"]
    assert serving.registry.get("t").version == 2


def test_offer_strides_copies_and_pins(data):
    x, q = data
    obs.set_mode("on")
    collected = []
    serving = _stub_serving(quality_sample_rate=0.5)
    serving.batcher = types.SimpleNamespace(
        submit_shadow=lambda r: collected.append(r) or [])
    gen = serving.registry.publish(
        "t", types.SimpleNamespace(adaptive=None))
    mon = QualityMonitor(serving, "t")
    assert mon.stride == 2
    reqs = [Request(queries=q[j:j + 1], k=3, prefilter=None,
                    future=Future()) for j in range(4)]
    batch = Batch(requests=reqs, rows=4, bucket=4, prefilter=None,
                  rung=2)
    ext = np.arange(4 * 3, dtype=np.int64).reshape(4, 3)
    h = types.SimpleNamespace(dtype=np.float32)
    mon.offer(batch, gen, h, ext)
    # stride 2 over 4 requests: the 2nd and 4th are sampled, each
    # carrying a COPY of its served ids and its own generation pin
    assert len(collected) == 2 and gen.refs == 2
    s = collected[0].shadow
    assert isinstance(s, ShadowSample) and s.rung == 2 and s.k == 3
    np.testing.assert_array_equal(s.served, ext[1:2, :3])
    assert s.served.base is None              # a copy, not a view
    for r in collected:
        r.shadow.gen.release()

    # overflow hand-back: the monitor releases the dropped pins
    serving.batcher.submit_shadow = lambda r: [r]
    mon.offer(batch, gen, h, ext)
    assert gen.refs == 0

    # obs off: the delivery hook is one module-attribute read — the
    # tick never advances, nothing is queued
    obs.set_mode("off")
    collected.clear()
    tick = mon._tick
    mon.offer(batch, gen, h, ext)
    assert not collected and mon._tick == tick


# ---------------------------------------------------------------------------
# fleet view: Fabric.recall_estimates + helm quality alarms
# ---------------------------------------------------------------------------


def test_fabric_recall_estimates_regroups_federated_series():
    fed = {"metrics": {
        "serve.recall_estimate": {"points": [
            {"labels": {"worker": "w0", "index": "t", "rung": "all"},
             "value": 0.95},
            {"labels": {"index": "t", "rung": "8"}, "value": 0.9}]},
        "serve.recall_ci_low": {"points": [
            {"labels": {"worker": "w0", "index": "t", "rung": "all"},
             "value": 0.91}]},
        "serve.recall_ci_high": {"points": [
            {"labels": {"worker": "w0", "index": "t", "rung": "all"},
             "value": 0.99}]},
    }}
    stub = types.SimpleNamespace(collect_metrics=lambda: fed)
    out = Fabric.recall_estimates(stub)
    assert out["w0|t|all"] == {"estimate": 0.95, "ci_low": 0.91,
                               "ci_high": 0.99}
    # a router-side series (no worker label) files under "router"
    assert out["router|t|8"] == {"estimate": 0.9}


def test_helm_quality_alarms_flag_pooled_proven_breaches_only():
    ests = {
        "w0|t|all": {"estimate": 0.6, "ci_high": 0.7},   # proven breach
        "w0|t|8": {"estimate": 0.1, "ci_high": 0.2},     # per-rung: skip
        "w1|t|all": {"estimate": 0.95, "ci_high": 0.99},
        "w2|t|all": {"estimate": 0.5},                   # no CI yet
    }
    stub = types.SimpleNamespace(
        fabric=types.SimpleNamespace(recall_estimates=lambda: ests),
        _recall_band=0.9)
    assert HelmController._quality_alarms(stub) == \
        [("quality_alarm", "w0|t|all")]
    # a mute fleet scrape degrades the alarm, never the tick
    def boom():
        raise RuntimeError("scrape down")
    stub.fabric.recall_estimates = boom
    assert HelmController._quality_alarms(stub) == []


# ---------------------------------------------------------------------------
# live server integration
# ---------------------------------------------------------------------------


def test_quality_disabled_is_one_attribute_read(data):
    x, q = data
    with serve.Server(_params(warmup=False)) as srv:
        srv.create_index("default", x)
        assert srv._servings["default"].quality is None
        srv.search(q[:4], 4)
        assert srv.stats()["quality"] is None


def test_obs_off_keeps_the_shadow_lane_dark(data):
    x, q = data
    params = _params(warmup=False, quality_sample_rate=1.0)
    with serve.Server(params) as srv:
        srv.create_index("default", x)
        mon = srv._servings["default"].quality
        assert mon is not None and not obs.enabled()
        srv.search(q[:4], 4)          # warm every lazy path first
        qfile = os.path.abspath(quality.__file__)
        tracemalloc.start()
        try:
            base = tracemalloc.take_snapshot()
            for _ in range(20):
                srv.search(q[:4], 4)
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        retained = sum(
            st.size_diff
            for st in after.compare_to(base, "filename")
            if st.traceback and st.traceback[0].filename == qfile)
        # the ENABLED gate is the whole story: no samples, no copies,
        # no pins — nothing attributable to quality.py survives
        assert retained < 256
        assert mon._tick == 0
        assert not srv._servings["default"].batcher._qs
        assert mon.stats()["samples"] == 0


def _wait_samples(mon, n, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if mon.stats()["samples"] >= n:
            return mon.stats()
        time.sleep(0.05)
    raise AssertionError(
        f"shadow lane never scored {n} samples: {mon.stats()}")


def test_shadow_sampling_live_zero_retraces(data):
    x, q = data
    obs.set_mode("on")
    params = _params(quality_sample_rate=1.0, quality_window=8,
                     quality_min_samples=4, max_wait_ms=0.5,
                     max_batch_rows=8, max_k=4)
    with serve.Server(params) as srv:
        srv.create_index("default", x)          # brute_force, warmed
        mon = srv._servings["default"].quality
        for j in range(6):
            srv.search(q[j], 4)
        st = _wait_samples(mon, 4)
        # brute force IS its own oracle: served == truth, recall 1.0
        assert st["estimate"] == 1.0 and st["ci_high"] == 1.0
        assert 0.0 < st["ci_low"] < 1.0
        assert srv.stats()["quality"]["estimate"] == 1.0

        before = serve.trace_cache_sizes()
        # the window caps stats()["samples"] at quality_window (8), so
        # wait on the cumulative counter for 4 more scored samples
        total0 = _value(obs.snapshot(), "serve.shadow_samples_total",
                        index="default")
        for j in range(6):
            srv.search(q[6 + j], 4)
        deadline = time.monotonic() + 60.0
        while _value(obs.snapshot(), "serve.shadow_samples_total",
                     index="default") < total0 + 4:
            assert time.monotonic() < deadline, mon.stats()
            time.sleep(0.05)
        # the oracle re-runs ride warmed (bucket, k) programs only
        assert serve.trace_cache_sizes() == before

        snap = obs.snapshot()
        assert _value(snap, "serve.recall_estimate",
                      index="default", rung="all") == 1.0
        assert _value(snap, "serve.recall_estimate",
                      index="default", rung="exhaustive") == 1.0
        assert _value(snap, "serve.recall_ci_high",
                      index="default", rung="all") == 1.0
        assert _value(snap, "serve.shadow_samples_total",
                      index="default") >= 8
        assert _value(snap, "serve.shadow_batches_total",
                      index="default") >= 1
        # the recall histogram shares the unit-interval preset
        hist = _value(snap, "serve.recall_sample",
                      index="default", rung="exhaustive")
        assert hist["buckets"] == list(obs.UNIT_BUCKETS)
        assert hist["count"] >= 8


@pytest.mark.slow
def test_swap_probation_rollback_e2e():
    """The ISSUE 19 acceptance drill: a hot-swap crippled to
    ``n_probes=1`` degrades pooled recall beyond statistical doubt on
    hard between-cluster queries; the probation window convicts the
    SWAP (the predecessor's baseline was measurably better), rolls it
    back, and the restored generation recovers — with zero new traces
    minted along the way."""
    rng = np.random.default_rng(7)
    centers = rng.standard_normal((16, DIM)).astype(np.float32) * 5
    x = np.concatenate([
        c + rng.standard_normal((64, DIM)).astype(np.float32)
        for c in centers], axis=0)
    hard = ((centers[rng.integers(0, 16, (256,))]
             + centers[rng.integers(0, 16, (256,))]) / 2
            + 0.5 * rng.standard_normal((256, DIM))).astype(np.float32)
    obs.set_mode("on")
    params = serve.ServeParams(
        max_batch_rows=16, max_wait_ms=0.2, max_k=16,
        quality_sample_rate=1.0, quality_min_samples=8,
        quality_window=16, quality_band=0.9, quality_retune=False,
        adaptive_probes=True)
    with serve.Server(params) as srv:
        srv.create_index("t", x, algo="ivf_flat",
                         build_params=ivf_flat.IndexParams(n_lists=16))
        mon = srv._servings["t"].quality

        def traffic(n):
            for _ in range(n):
                srv.submit(hard[rng.integers(0, 256, (4,))], k=8,
                           index="t").result(timeout=60)
                time.sleep(0.005)

        traffic(24)
        _wait_samples(mon, 8)
        assert srv.generation("t") == 1

        # the crippled successor: one probe cannot cover between-
        # cluster queries, so its own exhaustive oracle convicts it
        srv.swap("t", dataset=x,
                 search_params=ivf_flat.SearchParams(n_probes=1),
                 wait=True)
        assert srv.generation("t") == 2
        n_before = serve.total_trace_count()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            traffic(8)
            acts = [a[0] for a in mon.stats()["actions"]]
            if "rollback" in acts:
                break
        st = srv.stats("t")["quality"]
        kinds = [a[0] for a in st["actions"]]
        assert "rollback" in kinds, st
        detail = dict(st["actions"][kinds.index("rollback")][1])
        assert detail["prev_estimate"] is not None
        assert detail["ci_high"] < detail["prev_estimate"] \
            - quality.ROLLBACK_MARGIN
        # the rollback is a fresh generation wrapping the healthy
        # handle — versions stay monotone
        assert srv.generation("t") >= 3
        assert not st["probation_open"]
        # the restored generation recovers inside the band
        traffic(24)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            est = srv.stats("t")["quality"]["estimate"]
            if est is not None and est >= 0.9:
                break
            traffic(8)
        assert srv.stats("t")["quality"]["estimate"] >= 0.9
        # the whole episode — crippled serving, oracle re-runs,
        # rollback, recovery — rode already-warmed programs
        assert serve.total_trace_count() == n_before
