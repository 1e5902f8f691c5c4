"""graft-scope observability tests (ISSUE 4, marker ``obs``).

Covers: span nesting on one thread and ACROSS threads, metric
registry semantics (counter/gauge/histogram bucket edges, label
keying), Prometheus exposition round-trip, flight-recorder dump on an
injected ``dead@stage:search`` fault, the resilience/tuning wiring
(retries, OOM-ladder downshifts, checkpoint counters, dispatch
counts), the GL007 recompile hook, the named device stages of the
search programs, an off-path overhead guard (with the serve path
exercised), and the acceptance run (ivf_pq build+search under
``oom@chunk`` + sharded coverage)."""

import json
import os
import re
import threading
import tracemalloc

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu import obs, resilience, tuning
from raft_tpu.obs import federation as obs_federation
from raft_tpu.obs import flight as obs_flight
from raft_tpu.obs import metrics as obs_metrics
from raft_tpu.obs import spans as obs_spans
from raft_tpu.obs import trace as obs_trace
from raft_tpu.resilience import faultinject

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    monkeypatch.delenv("RAFT_TPU_OBS", raising=False)
    monkeypatch.delenv("RAFT_TPU_OBS_DIR", raising=False)
    obs.set_mode(None)
    obs.reset()
    faultinject.clear()
    yield
    obs.reset()
    obs.set_mode(None)
    faultinject.clear()
    tuning.reload()          # drop OOM-survivor budgets learned in a test


def _value(snap, name, /, **labels):
    """The value of the (name, labels) series in a snapshot, or None."""
    want = {str(k): str(v) for k, v in labels.items()}
    for p in snap["metrics"].get(name, {}).get("points", []):
        if all(p["labels"].get(k) == v for k, v in want.items()):
            return p.get("value", p)
    return None


# ---------------------------------------------------------------------------
# modes + off-path overhead
# ---------------------------------------------------------------------------


def test_default_mode_off():
    assert obs.mode() == "off"
    assert not obs.enabled()


def test_set_mode_validates():
    with pytest.raises(ValueError):
        obs.set_mode("loud")


def test_env_mode_via_reload(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_OBS", "flight")
    obs.reload()
    assert obs.mode() == "flight" and obs.enabled()
    monkeypatch.setenv("RAFT_TPU_OBS", "nonsense")
    obs.reload()
    assert obs.mode() == "off"


def test_off_path_is_shared_singleton_and_registry_silent():
    assert obs.span("a", x=1) is obs.span("b")
    assert obs.entry_span("search", "x", queries=4) is obs.span("c")
    obs.counter("nope", 3, algo="x")
    obs.gauge("nope_g", 1.0)
    obs.observe("nope_h", 2.0)
    obs.event("nope_e")
    with obs.span("quiet") as sp:
        sp.set(a=1).sync(None)
    # graft-trace off contract (ISSUE 13): no ids minted, payloads hand
    # back UNCHANGED (identity, not a copy), stages/finishes silent
    assert obs.start_trace("e") is None
    p = {"q": 1}
    assert obs.traced_payload(p) is p
    obs.trace.stage(None, "rpc", ms=1.0)
    assert obs.trace.finish(None) is None
    assert obs.trace.current() is None
    assert obs.trace_report() == []
    assert obs.snapshot(runtime_gauges=False)["metrics"] == {}
    assert obs.recent() == []
    assert obs.flight_events() == []


def test_off_path_retains_no_allocations():
    from raft_tpu import serve

    # warm every code path first so lazy init cannot count as growth
    obs.counter("warm")
    with obs.span("warm"):
        pass
    x = np.random.default_rng(0).standard_normal((64, 8)).astype(
        np.float32)
    srv = serve.Server(serve.ServeParams(max_batch_rows=4, max_k=4))
    obs_dir = os.path.dirname(obs.__file__)
    try:
        srv.create_index("default", x)
        srv.submit(x[:1], 2).result(timeout=60)
        tracemalloc.start()
        try:
            base = tracemalloc.take_snapshot()
            payload = {"q": 1}
            for i in range(500):
                obs.counter("x", 1, algo="y")
                obs.observe("h", 1.0, stage="s")
                with obs.span("s", a=1) as sp:
                    sp.set(b=2)
                # graft-trace joins the off-path contract
                obs.start_trace("e", k=4)
                obs.traced_payload(payload)
                obs.trace.stage(None, "rpc", ms=1.0)
                obs.trace.finish(None)
                if i % 25 == 0:
                    # a served request: admission, then its batch's
                    # wait, dispatch, device wait and deliver
                    srv.submit(x[i % 64], 2).result(timeout=60)
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
    finally:
        srv.close()
    retained = sum(
        st.size_diff
        for st in after.compare_to(base, "filename")
        if st.traceback and st.traceback[0].filename.startswith(obs_dir)
    )
    # the enabled-check must be the whole story: a real off-path leak
    # (a Span/point/waterfall per call surviving into a registry, tree,
    # or ring) retains tens of KB over 3500 calls; the 2 KB tolerance
    # absorbs tracemalloc's cross-thread/freelist attribution noise
    # under the full suite (the r13 trace calls grew the loop from 3 to
    # 7 obs touches per iteration, and the noise floor with it)
    assert retained < 2048, f"off path retained {retained} bytes"
    assert obs.snapshot(runtime_gauges=False)["metrics"] == {}
    assert obs.recent() == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_label_series():
    obs.set_mode("on")
    obs.counter("hits", 2, algo="a")
    obs.counter("hits", 3, algo="a")
    obs.counter("hits", 7, algo="b")
    obs.gauge("level", 0.5, what="x")
    obs.gauge("level", 0.25, what="x")       # gauges overwrite
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "hits", algo="a") == 5.0
    assert _value(snap, "hits", algo="b") == 7.0
    assert _value(snap, "level", what="x") == 0.25


def test_metric_kind_conflict_raises():
    obs.set_mode("on")
    obs.counter("twice")
    with pytest.raises(ValueError, match="already registered"):
        obs.gauge("twice", 1.0)


def test_histogram_bucket_edges():
    obs.set_mode("on")
    for v in (0.5, 1.0, 1.5, 2.0, 3.0):
        obs.observe("h", v, buckets=(1.0, 2.0))
    point = obs.snapshot(runtime_gauges=False)["metrics"]["h"]["points"][0]
    # value <= edge lands IN that bucket (le semantics): 0.5,1.0 | 1.5,2.0 | 3.0
    assert point["buckets"] == [1.0, 2.0]
    assert point["bucket_counts"] == [2, 2, 1]
    assert point["count"] == 5
    assert point["sum"] == pytest.approx(8.0)


def test_histogram_buckets_fixed_at_first_observation():
    obs.set_mode("on")
    obs.observe("fixed", 1.0, buckets=(10.0,))
    obs.observe("fixed", 100.0, buckets=(1.0, 2.0, 3.0))  # ignored
    point = obs.snapshot(runtime_gauges=False)["metrics"]["fixed"]["points"][0]
    assert point["buckets"] == [10.0]
    assert point["bucket_counts"] == [1, 1]


def test_unit_interval_bucket_preset():
    """ISSUE 19 satellite: the shared unit-interval preset for ratio
    histograms — monotone, capped at exactly 1.0, dense near the top
    where recall bands live (0.9/0.95/0.99 are resolvable edges)."""
    bs = obs.UNIT_BUCKETS
    assert bs[-1] == 1.0
    assert all(a < b for a, b in zip(bs, bs[1:]))
    assert all(0.0 < b <= 1.0 for b in bs)
    for edge in (0.9, 0.95, 0.99):
        assert edge in bs
    # consumers share the preset object, not a drifting copy
    from raft_tpu.serve.batcher import FILL_BUCKETS

    assert FILL_BUCKETS is obs.UNIT_BUCKETS
    obs.set_mode("on")
    obs.observe("serve.batch_fill_ratio", 0.93,
                buckets=FILL_BUCKETS, index="t")
    obs.observe("serve.recall_sample", 1.0,
                buckets=obs.UNIT_BUCKETS, index="t", rung="all")
    snap = obs.snapshot(runtime_gauges=False)["metrics"]
    for name in ("serve.batch_fill_ratio", "serve.recall_sample"):
        assert snap[name]["points"][0]["buckets"] == list(bs)
    fill = snap["serve.batch_fill_ratio"]["points"][0]
    # 0.93 resolves into (0.925, 0.95] — the band-adjacent bucket
    assert fill["bucket_counts"][bs.index(0.95)] == 1
    recall = snap["serve.recall_sample"]["points"][0]
    # perfect recall lands IN 1.0 (le semantics), not the overflow slot
    assert recall["bucket_counts"][bs.index(1.0)] == 1
    assert recall["bucket_counts"][-1] == 0


# ---------------------------------------------------------------------------
# Prometheus exposition round-trip
# ---------------------------------------------------------------------------

_PROM_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'            # metric name
    r'(\{(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*\})?'  # labels
    r' (-?(?:[0-9.]+(?:e[+-]?[0-9]+)?|[+-]?Inf|NaN))$',
    re.IGNORECASE,
)


def _parse_prometheus(text):
    """Tiny exposition-format checker: every line must be a # TYPE/HELP
    comment or a valid sample; returns {name: kind} and sample tuples."""
    types, samples = {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = re.match(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
                         r"(counter|gauge|histogram)$", line)
            assert m, f"bad comment line: {line!r}"
            types[m.group(1)] = m.group(2)
            continue
        m = _PROM_SAMPLE.match(line)
        assert m, f"invalid sample line: {line!r}"
        samples.append((m.group(1), m.group(2) or "", float(m.group(3))))
    return types, samples


def test_prometheus_round_trip():
    obs.set_mode("on")
    obs.counter("queries_total", 8, algo="ivf_pq")
    obs.gauge("shard_coverage", 0.875, what="sharded_knn")
    obs.observe("search_latency_ms", 1.7, algo="ivf_pq")
    obs.observe("search_latency_ms", 300.0, algo="ivf_pq")
    obs.gauge("odd name!", 1.0, **{"with": 'quo"te\nline'})
    text = obs.export_prometheus()
    types, samples = _parse_prometheus(text)
    assert types["raft_tpu_queries_total"] == "counter"
    assert types["raft_tpu_shard_coverage"] == "gauge"
    assert types["raft_tpu_search_latency_ms"] == "histogram"
    assert types["raft_tpu_odd_name_"] == "gauge"
    by = {(n, l): v for n, l, v in samples}
    assert by[("raft_tpu_queries_total", '{algo="ivf_pq"}')] == 8
    # histogram: cumulative buckets, +Inf == count, sum present
    buckets = [(l, v) for n, l, v in samples
               if n == "raft_tpu_search_latency_ms_bucket"]
    vals = [v for _, v in buckets]
    assert vals == sorted(vals), "bucket counts must be cumulative"
    assert buckets[-1][0].endswith('le="+Inf"}') and buckets[-1][1] == 2
    assert by[("raft_tpu_search_latency_ms_count", '{algo="ivf_pq"}')] == 2
    assert by[("raft_tpu_search_latency_ms_sum",
               '{algo="ivf_pq"}')] == pytest.approx(301.7)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_single_thread():
    obs.set_mode("on")
    with obs.span("root", stage="x") as sp:
        with obs.span("child"):
            with obs.span("grandchild"):
                pass
        sp.set(rows=10)
    (thread, tree), = obs.recent()
    assert tree["name"] == "root"
    assert tree["attrs"] == {"stage": "x", "rows": 10}
    assert tree["ms"] >= 0
    assert tree["children"][0]["name"] == "child"
    assert tree["children"][0]["children"][0]["name"] == "grandchild"


def test_span_error_attr_and_stack_healing():
    obs.set_mode("on")
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    (_, tree), = obs.recent()
    assert tree["attrs"]["error"] == "RuntimeError"
    assert obs.current() is None


def test_span_nesting_across_threads():
    obs.set_mode("on")
    barrier = threading.Barrier(2)
    errors = []

    def worker(tag):
        try:
            with obs.span(f"root-{tag}"):
                barrier.wait(timeout=10)     # both roots live concurrently
                with obs.span(f"child-{tag}"):
                    barrier.wait(timeout=10)  # both children live too
        except Exception as e:  # pragma: no cover - surfaced via assert
            errors.append(e)

    ts = [threading.Thread(target=worker, args=(t,), name=f"w{t}")
          for t in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errors
    trees = {tree["name"]: (thread, tree) for thread, tree in obs.recent()}
    for tag in ("a", "b"):
        thread, tree = trees[f"root-{tag}"]
        assert thread == f"w{tag}"
        # a cross-thread leak would parent child-a under root-b (or lose it)
        assert [c["name"] for c in tree.get("children", [])] == \
            [f"child-{tag}"]


def test_span_child_cap_records_drops():
    obs.set_mode("on")
    with obs.span("root"):
        for i in range(obs_spans.MAX_CHILDREN + 5):
            with obs.span(f"c{i}"):
                pass
    (_, tree), = obs.recent()
    assert len(tree["children"]) == obs_spans.MAX_CHILDREN
    assert tree["dropped_children"] == 5


def test_entry_span_emits_search_metrics():
    obs.set_mode("on")
    with obs.entry_span("search", "demo", queries=12, k=5):
        pass
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "queries_total", algo="demo") == 12.0
    hist = snap["metrics"]["search_latency_ms"]["points"][0]
    assert hist["labels"] == {"algo": "demo"} and hist["count"] == 1


def test_entry_span_failure_emits_no_entry_metrics():
    obs.set_mode("on")
    with pytest.raises(ValueError):
        with obs.entry_span("search", "demo", queries=12):
            raise ValueError("boom")
    snap = obs.snapshot(runtime_gauges=False)
    assert "queries_total" not in snap["metrics"]
    assert _value(snap, "span_ms", name="demo.search") is not None


# ---------------------------------------------------------------------------
# named device stages of the search programs
# ---------------------------------------------------------------------------


def _compiled_text(monkeypatch, module, name, call):
    """The compiled HLO text of the jitted ``module.<name>`` as ``call()``
    dispatches it (the last dispatch)."""
    fn = getattr(module, name)
    texts = []

    def spy(*args, **kwargs):
        texts.append(fn.lower(*args, **kwargs).compile().as_text())
        return fn(*args, **kwargs)

    monkeypatch.setattr(module, name, spy)
    call()
    assert texts, f"{name} was not dispatched"
    return texts[-1]


_IVF_STAGES = ("ivf.coarse", "ivf.bucketize", "ivf.scan", "ivf.merge")


@pytest.mark.parametrize("algo", ["ivf_flat", "ivf_pq"])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_search_program_names_its_device_stages(monkeypatch, algo, impl):
    """Each stage of the IVF search programs carries its named scope in
    the compiled program's op metadata, the keep-mask of a filtered
    search under the scan's (IVF-PQ) or in IVF-Flat's per-slot mask
    program, built once per filter and index: what a profiler trace
    names the device ops by."""
    from raft_tpu.core.bitset import Bitset
    from raft_tpu.neighbors import ivf_flat, ivf_pq

    rng = np.random.default_rng(0)
    x = rng.standard_normal((512, 16)).astype(np.float32)
    q = rng.standard_normal((8, 16)).astype(np.float32)
    keep = np.ones(512, bool)
    keep[::7] = False
    bits = Bitset.from_dense(jnp.asarray(keep))
    if algo == "ivf_flat":
        mod, prog = ivf_flat, "_ivf_search"
        index = ivf_flat.build(ivf_flat.IndexParams(n_lists=8), x)
        sp = ivf_flat.SearchParams(n_probes=4, scan_impl=impl)
    else:
        mod, prog = ivf_pq, "_pq_search"
        index = ivf_pq.build(ivf_pq.IndexParams(
            n_lists=8, pq_dim=8, kmeans_n_iters=4), x)
        sp = ivf_pq.SearchParams(n_probes=4, scan_impl=impl)
    text = _compiled_text(monkeypatch, mod, prog,
                          lambda: mod.search(sp, index, q, 4,
                                             prefilter=bits))
    for stage in _IVF_STAGES:
        assert f'op_name="jit({prog})/{stage}/' in text, stage
    assert f"jit({prog})/ivf.scan/" in text
    if algo == "ivf_flat":
        bits = Bitset.from_dense(jnp.asarray(keep))   # a fresh mask build
        text = _compiled_text(monkeypatch, mod, "_build_slot_keep",
                              lambda: mod.search(sp, index, q, 4,
                                                 prefilter=bits))
        assert 'op_name="jit(_build_slot_keep)/filter.keep_mask/' in text
    else:
        assert re.search(rf'op_name="jit\({prog}\)/ivf\.scan/[^"]*'
                         r'filter\.keep_mask/', text)


def test_rerank_program_names_its_device_stage(monkeypatch):
    """The exact rerank of ``ivf_pq.search_refined`` over resident rows
    runs under the ``ivf_pq.rerank`` scope."""
    from raft_tpu.neighbors import ivf_pq, tiered

    rng = np.random.default_rng(1)
    x = rng.standard_normal((512, 16)).astype(np.float32)
    q = rng.standard_normal((8, 16)).astype(np.float32)
    index = ivf_pq.build(ivf_pq.IndexParams(
        n_lists=8, pq_dim=8, kmeans_n_iters=4), x)
    sp = ivf_pq.SearchParams(n_probes=4)
    text = _compiled_text(
        monkeypatch, tiered, "_score_fetched",
        lambda: ivf_pq.search_refined(sp, index, q, 4, refine_ratio=2,
                                      dataset=jnp.asarray(x)))
    assert 'op_name="jit(_score_fetched)/ivf_pq.rerank/' in text


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_and_manual_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_OBS_DIR", str(tmp_path))
    obs.set_mode("flight")
    obs.counter("queries_total", 4, algo="x")
    with obs.span("s"):
        pass
    obs.event("custom", detail=1)
    kinds = [e["kind"] for e in obs.flight_events()]
    assert "metric" in kinds and "span" in kinds and "event" in kinds
    path = obs.flight_dump()
    assert path.startswith(str(tmp_path))
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[-1]["kind"] == "snapshot"
    assert "queries_total" in lines[-1]["metrics"]


def test_flight_auto_dump_on_dead_backend_classification(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv("RAFT_TPU_OBS_DIR", str(tmp_path))
    obs.set_mode("flight")
    obs.counter("queries_total", 1, algo="x")
    resilience.classify(resilience.DeadBackendError("backend went dark"))
    path = obs.last_dump_path()
    assert path is not None and os.path.exists(path)
    lines = [json.loads(ln) for ln in open(path)]
    err = [e for e in lines if e["kind"] == "error"]
    assert err and err[0]["error_kind"] == "dead_backend"
    # once per process: a second fatal must not overwrite the artifact
    resilience.classify(ValueError("later fatal"))
    assert obs.last_dump_path() == path


def test_flight_dump_on_injected_dead_stage_search(tmp_path, monkeypatch):
    """The ISSUE satellite scenario: a dead@stage:search fault mid-stream
    leaves a post-mortem JSONL even though the retry recovers the job."""
    from raft_tpu.neighbors import ivf_flat, stream

    monkeypatch.setenv("RAFT_TPU_OBS_DIR", str(tmp_path))
    obs.set_mode("flight")
    rng = np.random.default_rng(7)
    x = rng.standard_normal((256, 8), np.float32)
    idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=4, kmeans_n_iters=2), x)
    sp = ivf_flat.SearchParams(n_probes=2, scan_impl="xla")
    q = x[:64]
    ref_d, ref_i = stream.search_host_array(ivf_flat, sp, idx, q, 5,
                                            batch_rows=16)
    with faultinject.inject("dead@stage:search"):
        d, i = stream.search_host_array(ivf_flat, sp, idx, q, 5,
                                        batch_rows=16, backoff_s=0.01)
    np.testing.assert_array_equal(i, ref_i)      # retry recovered the job
    path = obs.last_dump_path()
    assert path is not None and os.path.exists(path)
    lines = [json.loads(ln) for ln in open(path)]
    assert any(e["kind"] == "error" and e["error_kind"] == "dead_backend"
               for e in lines)
    assert any(e["kind"] == "event" and e.get("event") == "fault_injected"
               for e in lines)
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "retries", kind="dead_backend") >= 1


# ---------------------------------------------------------------------------
# graft-trace: context, wire format, waterfalls (ISSUE 13)
# ---------------------------------------------------------------------------


def test_trace_context_wire_round_trip():
    obs.set_mode("on")
    ctx = obs.start_trace("fabric.search", index="default", k=4)
    assert ctx is not None and ctx.trace_id != ctx.parent_span_id
    wire = obs.trace.to_wire(ctx)
    assert wire == {"trace_id": ctx.trace_id,
                    "parent_span_id": ctx.parent_span_id}
    back = obs.trace.adopt(wire)
    assert back.trace_id == ctx.trace_id
    assert back.parent_span_id == ctx.parent_span_id
    # malformed wire degrades to untraced, never raises
    assert obs.trace.adopt(None) is None
    assert obs.trace.adopt("garbage") is None
    assert obs.trace.adopt({"trace_id": 7}) is None


def test_trace_ids_unique_across_mints():
    obs.set_mode("on")
    ids = {obs.start_trace("e").trace_id for _ in range(64)}
    assert len(ids) == 64


def test_traced_payload_injects_wire_field():
    obs.set_mode("on")
    ctx = obs.start_trace("e")
    p = obs.traced_payload({"q": 1}, ctx)
    assert p["q"] == 1 and p["trace"]["trace_id"] == ctx.trace_id
    # ambient context used when none passed
    with obs.trace.activate(ctx):
        p2 = obs.traced_payload({"k": 2})
    assert p2["trace"]["trace_id"] == ctx.trace_id
    # no context anywhere: payload unchanged
    p3 = {"k": 3}
    assert obs.traced_payload(p3) is p3


def test_trace_activate_is_thread_local_and_restores():
    obs.set_mode("on")
    ctx = obs.start_trace("e")
    seen = []

    def worker():
        seen.append(obs.trace.current())

    with obs.trace.activate(ctx):
        assert obs.trace.current() is ctx
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
        with obs.span("inner"):
            pass
    assert obs.trace.current() is None
    assert seen == [None]            # ambient context never leaks threads
    # the span opened under the activated context adopted its trace id
    assert obs.recent()[-1][1]["attrs"]["trace_id"] == ctx.trace_id


def test_waterfall_assembly_and_report():
    obs.set_mode("on")
    ctx = obs.start_trace("fabric.search", k=4)
    obs.trace.stage(ctx, "rpc", ms=2.0, worker=0, shard=0)
    obs.trace.stage(ctx, "rpc", ms=3.0, worker=1, shard=0,
                    status="hedge_win")
    obs.trace.stage(ctx, "worker_scan", ms=1.5, worker=1, shard=0,
                    device_complete=True)
    obs.trace.stage(ctx, "merge", ms=0.5)
    wf = obs.trace.finish(ctx, coverage_min=1.0)
    assert wf["status"] == "ok" and wf["ms"] >= 0
    assert [s["stage"] for s in wf["stages"]] == [
        "rpc", "rpc", "worker_scan", "merge"]
    assert wf["stages"][1]["status"] == "hedge_win"
    assert wf["attrs"]["coverage_min"] == 1.0
    # the report finds it, by id and in bulk; late stages are dropped
    assert obs.trace_report(trace_id=wf["trace_id"]) == [wf]
    assert obs.trace_report() == [wf]
    obs.trace.stage(ctx, "rpc", ms=9.0)        # after finish: ignored
    assert len(wf["stages"]) == 4
    assert obs.trace.finish(ctx) is None       # double finish: no-op


def test_waterfall_stage_cap_records_drops():
    obs.set_mode("on")
    ctx = obs.start_trace("e")
    for i in range(obs_trace.MAX_STAGES + 7):
        obs.trace.stage(ctx, "rpc", ms=1.0)
    wf = obs.trace.finish(ctx)
    assert len(wf["stages"]) == obs_trace.MAX_STAGES
    assert wf["dropped_stages"] == 7


def test_waterfall_flight_record_and_counter(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_OBS_DIR", str(tmp_path))
    obs.set_mode("flight")
    ctx = obs.start_trace("fabric.search")
    obs.trace.stage(ctx, "merge", ms=0.1)
    obs.trace.finish(ctx)
    evts = [e for e in obs.flight_events() if e["kind"] == "waterfall"]
    assert len(evts) == 1 and evts[0]["trace_id"] == ctx.trace_id
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "trace.waterfalls_total", status="ok") == 1.0


def test_ring_stats_counts_evictions_honestly():
    obs.set_mode("on")
    for _ in range(5):
        obs.trace.finish(obs.start_trace("e"))
    s = obs_trace.ring_stats()
    assert s == {"completed_total": 5, "retained": 5, "evicted": 0}
    # shrink the window to force eviction (restored after)
    import collections as _c

    orig = obs_trace._done
    obs_trace._done = _c.deque(orig, maxlen=3)
    try:
        obs.trace.finish(obs.start_trace("e"))
        s = obs_trace.ring_stats()
        assert s["completed_total"] == 6 and s["retained"] == 3
        assert s["evicted"] == 3          # truncation is VISIBLE
    finally:
        obs_trace._done = _c.deque(obs_trace._done, maxlen=obs_trace.MAX_DONE)
    obs.reset()
    assert obs_trace.ring_stats()["completed_total"] == 0


def test_waterfall_complete_predicate():
    """The ONE completeness definition the chaos acceptance and the
    loadgen columns share."""
    base = {"status": "ok",
            "attrs": {"covered_shards": [0, 1]},
            "stages": [
                {"stage": "worker_scan", "shard": 0,
                 "device_complete": True},
                {"stage": "worker_scan", "shard": 1,
                 "device_complete": True},
                {"stage": "merge"},
            ]}
    assert obs_trace.waterfall_complete(base)
    import copy

    failed = copy.deepcopy(base)
    failed["status"] = "failed"
    assert not obs_trace.waterfall_complete(failed)
    no_merge = copy.deepcopy(base)
    no_merge["stages"] = no_merge["stages"][:2]
    assert not obs_trace.waterfall_complete(no_merge)
    missing_scan = copy.deepcopy(base)
    missing_scan["stages"][1]["shard"] = 0
    assert not obs_trace.waterfall_complete(missing_scan)
    not_device = copy.deepcopy(base)
    not_device["stages"][0]["device_complete"] = False
    assert not obs_trace.waterfall_complete(not_device)
    # a degraded answer with all ITS covered shards scanned is complete
    degraded = copy.deepcopy(base)
    degraded["status"] = "degraded"
    degraded["attrs"]["covered_shards"] = [0]
    degraded["stages"] = [base["stages"][0], {"stage": "merge"}]
    assert obs_trace.waterfall_complete(degraded)


def test_stage_stats_percentiles_and_hedge_counts():
    obs.set_mode("on")
    for i in range(10):
        ctx = obs.start_trace("e")
        obs.trace.stage(ctx, "rpc", ms=float(i + 1), worker=0)
        obs.trace.finish(ctx)
    ctx = obs.start_trace("e")
    obs.trace.stage(ctx, "rpc", ms=100.0, status="hedge_win")
    obs.trace.stage(ctx, "rpc", status="hedge_loser")
    obs.trace.stage(ctx, "rpc", ms=5.0, status="failed", kind="transient")
    obs.trace.stage(ctx, "retry", status="retry")
    obs.trace.finish(ctx)
    stats = obs_trace.stage_stats(obs.trace_report())
    rpc = stats["rpc"]
    assert rpc["count"] == 13
    assert rpc["hedge_wins"] == 1 and rpc["hedge_losers"] == 1
    assert rpc["failed"] == 1
    # percentiles over ok + hedge_win samples only (failed ms excluded)
    assert rpc["p50_ms"] == 6.0 and rpc["p99_ms"] == 100.0
    assert stats["retry"]["retries"] == 1
    assert stats["retry"]["p50_ms"] is None


def test_flight_dump_same_second_paths_do_not_collide(tmp_path,
                                                      monkeypatch):
    """ISSUE 13 satellite: two dumps from one process in the same
    wall-clock second used to compute the SAME default path and the
    second silently overwrote the first — the monotonic per-process
    sequence suffix keeps every default path distinct."""
    monkeypatch.setenv("RAFT_TPU_OBS_DIR", str(tmp_path))
    obs.set_mode("flight")
    # pin the clock so both paths share the <unix> component for sure
    monkeypatch.setattr(obs_flight.time, "time", lambda: 1234567890.0)
    obs.counter("queries_total", 1, algo="a")
    p1 = obs.flight_dump()
    obs.counter("queries_total", 1, algo="b")
    p2 = obs.flight_dump()
    assert p1 != p2
    assert os.path.exists(p1) and os.path.exists(p2)
    # both artifacts intact (the first was NOT overwritten)
    first = [json.loads(ln) for ln in open(p1)]
    second = [json.loads(ln) for ln in open(p2)]
    assert first[-1]["kind"] == "snapshot"
    assert len(second) > len(first)


def test_federation_merge_and_prometheus_render():
    obs.set_mode("on")
    obs.counter("queries_total", 4, algo="x")
    obs.observe("search_latency_ms", 2.0, algo="x")
    m = obs.snapshot(runtime_gauges=False)["metrics"]
    fed = obs_federation.federated_snapshot({"w0": m, "w1": m})
    assert fed["workers"] == ["w0", "w1"]
    pts = fed["metrics"]["queries_total"]["points"]
    assert {p["labels"]["worker"] for p in pts} == {"w0", "w1"}
    assert all(p["labels"]["algo"] == "x" for p in pts)
    text = obs_federation.render_prometheus(fed["metrics"])
    _parse_prometheus(text)          # valid exposition format
    assert 'raft_tpu_queries_total{algo="x",worker="w0"} 4' in text
    # histogram rendered cumulatively with +Inf == count per worker
    assert text.count('le="+Inf"') == 2


def test_federation_kind_conflict_kept_out_of_exposition():
    fed = obs_federation.merge_metric_maps({
        "a": {"m": {"kind": "counter",
                    "points": [{"labels": {}, "value": 1.0}]}},
        "b": {"m": {"kind": "gauge",
                    "points": [{"labels": {}, "value": 2.0}]}},
    })
    assert len(fed["m"]["points"]) == 1          # first kind wins
    assert "_conflicts" in fed
    text = obs_federation.render_prometheus(fed)
    assert "conflicts" not in text               # meta never exported
    _parse_prometheus(text)


# ---------------------------------------------------------------------------
# resilience + tuning wiring
# ---------------------------------------------------------------------------


def test_errors_total_counts_one_failure_once_across_nested_layers():
    """stream.py nests run_halving around resilience.run — both classify
    the SAME exception; errors_total must advance once, not per layer."""
    obs.set_mode("on")
    e = MemoryError("RESOURCE_EXHAUSTED: one failure")
    assert resilience.classify(e) == resilience.OOM
    assert resilience.classify(e) == resilience.OOM   # nested re-classify
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "errors_total", kind="oom") == 1.0
    # a DISTINCT later failure still counts
    resilience.classify(MemoryError("RESOURCE_EXHAUSTED: another"))
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "errors_total", kind="oom") == 2.0


def test_retry_counter_and_events():
    obs.set_mode("on")
    calls = []

    def flaky():
        if not calls:
            calls.append(1)
            raise resilience.TransientError("UNAVAILABLE: blip")
        return 42

    assert resilience.run(flaky, retries=2, backoff_s=0.01) == 42
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "retries", kind="transient") == 1.0
    assert _value(snap, "errors_total", kind="transient") >= 1.0


def test_oom_ladder_downshift_counter():
    obs.set_mode("on")

    calls = []

    def searcher(batch):
        if len(batch) > 8:
            calls.append(len(batch))
            raise MemoryError("RESOURCE_EXHAUSTED: injected")
        return jnp.asarray(np.asarray(batch) * 2.0)

    out, survived = resilience.degrade.run_halving(
        searcher, jnp.arange(32.0), budget_name="obs_test_budget")
    assert survived == 8
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "oom_ladder_downshifts", path="halving") >= 1.0
    assert _value(snap, "runtime_budget", budget="obs_test_budget") == 8.0


def test_checkpoint_save_resume_counters(tmp_path):
    obs.set_mode("on")
    ck = resilience.StreamCheckpoint(str(tmp_path))
    ck.save("search", 3, {"rows_done": 48}, {"d": np.zeros((48, 5))},
            fingerprint={"k": 5})
    assert ck.load(fingerprint={"k": 5}) is not None
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "checkpoint_saves", phase="search") == 1.0
    assert _value(snap, "checkpoint_resumes", phase="search") == 1.0


def test_tuning_dispatch_counter():
    obs.set_mode("on")
    from raft_tpu.matrix.select_k import dispatch_select_impl

    impl = dispatch_select_impl(4, 4096, 512, jnp.float32)
    snap = obs.snapshot(runtime_gauges=False)
    pts = snap["metrics"]["tuning.dispatch"]["points"]
    assert any(p["labels"]["op"] == "select_k"
               and p["labels"]["impl"] == impl for p in pts)


def test_recompile_hook_counts_new_traces():
    import jax

    obs.set_mode("on")
    from raft_tpu.matrix.select_k import select_k

    jax.clear_caches()
    select_k(jnp.asarray(np.random.rand(4, 128).astype(np.float32)), 8)
    obs.capture_runtime_gauges()                 # baseline cache sizes
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "jit_cache_entries",
                  fn="select_k._select_k") is not None
    select_k(jnp.asarray(np.random.rand(4, 256).astype(np.float32)), 8)
    obs.capture_runtime_gauges()                 # growth -> recompiles
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "recompiles", fn="select_k._select_k") >= 1.0
    # steady state: re-running the SAME shape adds nothing
    before = _value(snap, "recompiles", fn="select_k._select_k")
    select_k(jnp.asarray(np.random.rand(4, 256).astype(np.float32)), 8)
    obs.capture_runtime_gauges()
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "recompiles", fn="select_k._select_k") == before


def test_write_snapshot_sidecar(tmp_path):
    obs.set_mode("on")
    obs.counter("queries_total", 3, algo="x")
    path = obs.write_snapshot(str(tmp_path / "BENCH_x.obs.json"))
    data = json.load(open(path))
    assert data["mode"] == "on"
    assert data["metrics"]["queries_total"]["points"][0]["value"] == 3.0


# ---------------------------------------------------------------------------
# acceptance: instrumented ivf_pq under faults + sharded coverage
# ---------------------------------------------------------------------------


def test_acceptance_ivf_pq_build_search_under_oom(tmp_path):
    """ISSUE 4 acceptance: RAFT_TPU_OBS=on + an ivf_pq build+search run
    under injected oom@chunk faults yields a snapshot with non-zero
    queries_total, search_latency_ms histogram counts, and
    oom_ladder_downshifts, and a valid Prometheus exposition."""
    from raft_tpu.neighbors import ivf_pq, stream

    obs.set_mode("on")
    rng = np.random.default_rng(3)
    x = rng.standard_normal((512, 16), np.float32)
    params = ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=2)
    idx = ivf_pq.build(params, x)
    sp = ivf_pq.SearchParams(n_probes=4, scan_impl="xla")
    ref_d, ref_i = stream.search_host_array(ivf_pq, sp, idx, x[:128], 5,
                                            batch_rows=32)
    with faultinject.inject("oom@chunk:1"):
        d, i = stream.search_host_array(ivf_pq, sp, idx, x[:128], 5,
                                        batch_rows=32)
    np.testing.assert_array_equal(i, ref_i)      # ladder output is bitwise
    snap = obs.snapshot()
    assert _value(snap, "queries_total", algo="ivf_pq") > 0
    hists = snap["metrics"]["search_latency_ms"]["points"]
    assert sum(p["count"] for p in hists) > 0
    assert _value(snap, "oom_ladder_downshifts", path="halving") >= 1.0
    assert _value(snap, "builds_total", algo="ivf_pq") == 1.0
    _parse_prometheus(obs.export_prometheus())   # valid exposition format


def test_acceptance_sharded_coverage_gauge(eight_device_mesh):
    """Per-shard degradation shows up as the shard_coverage gauge (and a
    dropout counter) without the caller lifting a finger."""
    from raft_tpu.comms import sharded

    obs.set_mode("on")
    rng = np.random.default_rng(5)
    x = rng.standard_normal((64, 8), np.float32)
    q = x[:4]
    with faultinject.inject("shard@rank:2"):
        d, i, cov = sharded.sharded_knn(q, x, 3, eight_device_mesh,
                                        partial_ok=True)
    assert float(np.asarray(cov)) == pytest.approx(7 / 8)
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "shard_coverage",
                  what="sharded_knn") == pytest.approx(7 / 8)
    assert _value(snap, "shard_dropouts_total", what="sharded_knn") == 1.0
    assert _value(snap, "queries_total", algo="sharded_knn") == 4.0


def test_sharded_full_coverage_gauge_recorded(eight_device_mesh):
    """The PLAIN (no validity scan) path records shard_coverage = 1 too
    — a dashboard must distinguish "healthy 8/8" from "metric never
    emitted" (ISSUE 6 satellite)."""
    from raft_tpu.comms import sharded

    obs.set_mode("on")
    rng = np.random.default_rng(6)
    x = rng.standard_normal((64, 8)).astype(np.float32)
    q = x[:4]
    d, i = sharded.sharded_knn(q, x, 3, eight_device_mesh)
    snap = obs.snapshot(runtime_gauges=False)
    assert _value(snap, "shard_coverage", what="sharded_knn") == 1.0
