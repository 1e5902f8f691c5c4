"""graft-serve tests (ISSUE 5, marker ``serve``).

Covers the three acceptance criteria — post-warmup trace stability
under a mixed-size stream (the GL007 trace-counting hook), loss-free
hot-swap under concurrent load (every request completes, each from
exactly one generation), and tombstone correctness against fresh
indexes across all four index types — plus the micro-batcher unit
surface (ladder, coalescing, padding, backpressure), the resilience
wiring (injected OOM → bucket-ceiling downshift + split; injected
transient → retried), upsert/side-buffer/compaction behavior,
user-prefilter composition, and generation refcount draining."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from raft_tpu import serve, tuning
from raft_tpu.analysis import lockwatch
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.neighbors.common import BitsetFilter
from raft_tpu.resilience import faultinject
from raft_tpu.serve.batcher import bucket_ladder, choose_bucket, pad_rows

pytestmark = [pytest.mark.serve, pytest.mark.threadsan]

N, DIM = 320, 16


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    # ISSUE 7: the whole serve suite runs with SANITIZED locks — every
    # Server/batcher/registry/mutation lock constructed in these tests
    # goes through analysis/lockwatch, so each run doubles as the
    # zero-inversion / zero-hold-budget-breach acceptance
    monkeypatch.setenv(lockwatch.ENV_VAR, "1")
    faultinject.clear()
    yield
    faultinject.clear()
    # drop any serve_batch_rows OOM budget a test recorded — it would
    # clamp every later server's starting ceiling
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


# ---------------------------------------------------------------------------
# bucket ladder / batcher units
# ---------------------------------------------------------------------------


def test_bucket_ladder_shape():
    assert bucket_ladder(256) == (1, 2, 4, 8, 16, 32, 64, 128, 256)
    assert bucket_ladder(100)[-1] == 128          # rounded up to pow2
    assert bucket_ladder(1) == (1,)


def test_choose_bucket_fallback_and_ceiling():
    lad = bucket_ladder(64)
    assert choose_bucket(lad, 5) == 8
    assert choose_bucket(lad, 64) == 64
    assert choose_bucket(lad, 9, ceiling=8) == 16    # head bigger than cap
    assert choose_bucket(lad, 3, ceiling=8) == 4


def test_pad_rows_host_only():
    q = np.ones((3, 4), np.float32)
    out = pad_rows(q, 8)
    assert out.shape == (8, 4) and (out[3:] == 0).all()
    assert pad_rows(q, 3) is q


def test_submit_result_matches_oracle(data):
    x, q = data
    with serve.Server(_params(warmup=False)) as srv:
        srv.create_index("default", x)
        d, i = srv.search(q[:5], 4)
        gd, gi = brute_force.knn(q[:5], x, 4)
        np.testing.assert_array_equal(i, np.asarray(gi))
        np.testing.assert_array_equal(d, np.asarray(gd))


def test_concurrent_submits_coalesce_and_match(data):
    x, q = data
    gd, gi = brute_force.knn(q, x, 4)
    gi = np.asarray(gi)
    with serve.Server(_params(max_wait_ms=5.0, warmup=False)) as srv:
        srv.create_index("default", x)
        futs = [srv.submit(q[j], 4) for j in range(q.shape[0])]
        for j, f in enumerate(futs):
            _, ids = f.result(timeout=60)
            np.testing.assert_array_equal(ids[0], gi[j])


def test_mixed_k_requests(data):
    x, q = data
    with serve.Server(_params(max_wait_ms=5.0, warmup=False)) as srv:
        srv.create_index("default", x)
        ks = [1, 3, 5, 8, 2, 7]
        futs = [srv.submit(q[j], k) for j, k in enumerate(ks)]
        for j, (f, k) in enumerate(zip(futs, ks)):
            d, ids = f.result(timeout=60)
            assert ids.shape == (1, k)
            _, gi = brute_force.knn(q[j:j + 1], x, k)
            np.testing.assert_array_equal(ids, np.asarray(gi))


def test_non_pow2_max_k_warm_and_served(data):
    x, q = data
    with serve.Server(_params(max_k=10)) as srv:   # warmup on
        srv.create_index("default", x)
        # the k-ladder tops at max_k itself, not the last pow2 below it:
        # submit admits any k <= max_k, so k in (8, 10] must be servable
        # (and warmed — the max_k rung is part of the traced ladder)
        ks = (9, 10, 5)
        # oracle traces its own (unpadded) shapes: keep it out of the
        # serve-side trace-stability window
        oracle = {k: np.asarray(brute_force.knn(q[:3], x, k)[1])
                  for k in ks}
        before = serve.trace_cache_sizes()
        for k in ks:
            _, i = srv.search(q[:3], k)
            assert i.shape == (3, k)
            np.testing.assert_array_equal(i, oracle[k])
        assert serve.trace_cache_sizes() == before


def test_rabitq_rung_serves_trace_stable(data):
    """ISSUE 11: the rabitq multi-stage pipeline is reachable from serve
    (ivf_pq index with a rabitq cache routes through search_refined,
    tombstones composing with the first stage) and steady-state serving
    adds ZERO XLA traces — the warmup ladder covers both pipeline
    stages."""
    from raft_tpu.neighbors import ivf_pq

    x, q = data
    bp = ivf_pq.IndexParams(n_lists=8, pq_dim=16, kmeans_n_iters=4,
                            cache_dtype="rabitq")
    with serve.Server(_params(max_k=8)) as srv:    # warmup on
        srv.create_index("default", x, algo="ivf_pq", build_params=bp,
                         search_params=ivf_pq.SearchParams(n_probes=8))
        before = serve.trace_cache_sizes()
        d, i = srv.search(q[:5], 4)
        assert i.shape == (5, 4)
        assert (np.asarray(i) >= 0).all()
        # delete a served id: the tombstone must compose with the FIRST
        # stage (the deleted row never reaches the rerank shortlist)
        victim = int(np.asarray(i)[0, 0])
        srv.delete([victim])
        _, i2 = srv.search(q[:5], 4)
        assert victim not in np.asarray(i2)
        assert serve.trace_cache_sizes() == before


def test_submit_validation(data):
    x, _ = data
    with serve.Server(_params(warmup=False)) as srv:
        srv.create_index("default", x)
        with pytest.raises(ValueError, match="max_k"):
            srv.submit(x[0], 99)
        with pytest.raises(ValueError, match="max_batch_rows"):
            srv.submit(x[:17], 4)       # > max_batch_rows in one request
        with pytest.raises(ValueError, match="dim"):
            # rejected at the door: coalesced into a batch it would fail
            # every other request at dispatch
            srv.submit(x[0, :-1], 4)
        with pytest.raises(KeyError):
            srv.submit(x[0], 4, index="nope")


def test_overload_rejection_is_transient(data):
    from raft_tpu import resilience

    x, _ = data
    srv = serve.Server(_params(max_queue_rows=2, max_wait_ms=200.0))
    try:
        srv.create_index("default", x, warmup=False)
        futs, rejected = [], None
        for j in range(6):
            try:
                futs.append(srv.submit(x[j], 2))
            except serve.Overloaded as e:
                rejected = e
                break
        assert rejected is not None, "bounded queue never pushed back"
        assert resilience.classify(rejected) == resilience.TRANSIENT
        for f in futs:                       # admitted work still completes
            f.result(timeout=60)
    finally:
        srv.close()


def test_closed_rejection_is_fatal(data):
    from raft_tpu import resilience

    x, _ = data
    srv = serve.Server(_params())
    srv.create_index("default", x, warmup=False)
    srv.close()
    with pytest.raises(serve.Overloaded) as ei:
        srv.submit(x[0], 2)
    # a closed server can never accept again: the rejection must fail
    # fast, not carry the backoff-and-retry advice queue_full does
    assert ei.value.reason == "closed"
    assert resilience.classify(ei.value) == resilience.FATAL
    # mutation/warmup entry points get the same truthful diagnosis, not
    # a KeyError claiming the index was never published
    for call in (lambda: srv.delete([1]),
                 lambda: srv.upsert(x[0], [9000]),
                 lambda: srv.warmup()):
        with pytest.raises(RuntimeError, match="server is closed"):
            call()


def test_submit_before_first_publish_rejected_not_ready(
        data, monkeypatch):
    # create_index registers the serving BEFORE its first publish, and
    # warmup can hold that window open for minutes — a submit landing in
    # it must get a retryable not_ready rejection, not an enqueue whose
    # future later fails with the dispatcher's internal KeyError
    from raft_tpu import resilience

    x, _ = data
    srv = serve.Server(_params())
    installed, gate = threading.Event(), threading.Event()
    real_publish = serve.Server._publish_guarded

    def held_publish(self, name, h):
        installed.set()
        assert gate.wait(timeout=30), "test gate never released"
        return real_publish(self, name, h)

    monkeypatch.setattr(serve.Server, "_publish_guarded", held_publish)
    t = threading.Thread(
        target=lambda: srv.create_index("default", x, warmup=False))
    t.start()
    try:
        assert installed.wait(timeout=30)
        with pytest.raises(serve.Overloaded) as ei:
            srv.submit(x[0], 2)
        assert ei.value.reason == "not_ready"
        assert resilience.classify(ei.value) == resilience.TRANSIENT
    finally:
        gate.set()
        t.join(timeout=30)
    # once the first generation publishes, the same call serves
    d, i = srv.search(x[0], 2)
    assert int(i[0, 0]) == 0
    srv.close()


# ---------------------------------------------------------------------------
# acceptance: trace stability (GL007 hook)
# ---------------------------------------------------------------------------


def test_steady_state_adds_zero_traces(data):
    x, q = data
    rng = np.random.default_rng(7)
    with serve.Server(_params(max_wait_ms=0.5)) as srv:
        srv.create_index("default", x)
        # tombstones + a user filter exercise the filtered paths too
        srv.delete([1, 2, 3])
        filt = Bitset.from_dense(np.arange(N) % 2 == 0)
        srv.search(q[:3], 4, prefilter=filt)
        before = serve.trace_cache_sizes()
        for rows in (1, 3, 7, 2, 11, 16, 5, 1, 9, 13):
            block = rng.standard_normal((rows, DIM)).astype(np.float32)
            for k in (1, 3, 5, 8):
                srv.search(block, k)
        srv.search(q[:5], 4, prefilter=filt)
        srv.delete([9])                      # mutation between batches
        srv.search(q[:2], 3)
        after = serve.trace_cache_sizes()
        assert after == before, (
            f"steady-state serving retraced: {before} -> {after}")
        # upserts advance next_int, which feeds every kernel's STATIC
        # filter_nbits: the pow2 capacity rung (+ re-warm when it or the
        # side buffer grows) must keep serving trace-stable rather than
        # retracing on every single upsert
        srv.upsert(rng.standard_normal(DIM).astype(np.float32), [N + 1])
        before = serve.trace_cache_sizes()
        for rows in (2, 5, 1, 8):
            block = rng.standard_normal((rows, DIM)).astype(np.float32)
            srv.search(block, 4)
        # same capacity rung: no shape changed, so no re-warm happened
        srv.upsert(rng.standard_normal(DIM).astype(np.float32), [N + 2])
        srv.search(q[:3], 4, prefilter=filt)
        srv.search(q[:2], 3)
        after = serve.trace_cache_sizes()
        assert after == before, (
            f"post-upsert serving retraced: {before} -> {after}")


# ---------------------------------------------------------------------------
# acceptance: loss-free hot swap under load
# ---------------------------------------------------------------------------


def test_hot_swap_loss_free_under_load(data):
    x, q = data
    x2 = (x[::-1] * 1.5).copy()              # different content, same shape
    k = 4
    exp = {1: np.asarray(brute_force.knn(q, x, k)[1]),
           2: np.asarray(brute_force.knn(q, x2, k)[1])}
    with serve.Server(_params(max_wait_ms=0.5, warmup=False)) as srv:
        srv.create_index("default", x)
        gen1 = srv.registry.get("default")
        stop = threading.Event()
        results, errors = [], []

        def worker(wid):
            wrng = np.random.default_rng(wid)
            while not stop.is_set():
                j = int(wrng.integers(q.shape[0]))
                f = srv.submit(q[j], k)
                try:
                    _, ids = f.result(timeout=60)
                except Exception as e:  # noqa: BLE001 — the assertion below reports it
                    errors.append(e)
                    return
                results.append((j, f.generation, ids[0].copy()))

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(6)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        fut = srv.swap("default", dataset=x2)
        assert fut.result(timeout=300) == 2
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(timeout=60)

        assert not errors, errors
        assert results, "no requests completed"
        gens = {g for _, g, _ in results}
        assert gens <= {1, 2} and 2 in gens
        # every answer comes from exactly ONE generation: it matches that
        # generation's expected ids bit-for-bit, never a mixture
        for j, g, ids in results:
            np.testing.assert_array_equal(ids, exp[g][j])
        # the retired generation drains once its pins are gone
        assert gen1.drained.wait(timeout=30)
        assert gen1.handle is None


def test_generation_refcount_drain(data):
    x, _ = data
    with serve.Server(_params()) as srv:
        srv.create_index("default", x, warmup=False)
        g1 = srv.registry.pin("default")          # simulated in-flight batch
        srv.swap("default", dataset=x, wait=True)
        assert srv.generation() == 2
        assert not g1.drained.is_set(), "drained while still pinned"
        g1.release()
        assert g1.drained.wait(timeout=10)


def test_swap_rederives_default_search_params(data):
    # default ivf search params (n_probes = n_lists, the exhaustive-
    # probing serving contract) must be re-derived against the NEW
    # index on swap — inheriting the old resolved params would clamp
    # probing at the old index's n_lists and silently serve
    # non-exhaustive results on a bigger successor
    x, _ = data
    rng = np.random.default_rng(11)
    big = rng.standard_normal((N * 4, DIM)).astype(np.float32)
    with serve.Server(_params()) as srv:
        srv.create_index("default", x, algo="ivf_flat", warmup=False)
        h0 = srv.registry.get("default").handle
        assert h0.search_params.n_probes == h0.index.n_lists
        srv.swap("default", dataset=big, wait=True)
        h1 = srv.registry.get("default").handle
        assert h1.index.n_lists > h0.index.n_lists
        assert h1.search_params.n_probes == h1.index.n_lists
        # explicit user params still stick across a swap
        srv.swap("default", dataset=x,
                 search_params=ivf_flat.SearchParams(n_probes=3),
                 wait=True)
        srv.swap("default", dataset=big, wait=True)
        h3 = srv.registry.get("default").handle
        assert h3.search_params.n_probes == 3


def test_warmup_oom_downshifts_instead_of_failing(data, monkeypatch):
    # a device OOM tracing the top warmup bucket must downshift the
    # ladder (like the dispatch path's OOM ladder) and bring the server
    # up serving the buckets that fit — not abort create_index
    from raft_tpu.serve import engine as _eng

    x, q = data
    real = _eng._IndexServing._run_search

    def oom_above_4(self, h, batch, *a, **kw):
        if batch.bucket >= 8:
            raise RuntimeError("RESOURCE_EXHAUSTED: warmup shape too big")
        return real(self, h, batch, *a, **kw)

    monkeypatch.setattr(_eng._IndexServing, "_run_search", oom_above_4)
    with serve.Server(_params()) as srv:
        srv.create_index("default", x)            # warmup on: must survive
        assert srv._serving("default").batcher.ceiling == 4
        d, i = srv.search(q[:2], 3)
        _, gi = brute_force.knn(q[:2], x, 3)
        np.testing.assert_array_equal(i, np.asarray(gi))


def test_load_index_publishes_snapshot(tmp_path, data):
    x, q = data
    idx = brute_force.build(x)
    path = str(tmp_path / "bf.idx")
    brute_force.save(path, idx)
    with serve.Server(_params()) as srv:
        srv.load_index("default", path, algo="brute_force", warmup=False)
        d, i = srv.search(q[:3], 4)
        _, gi = brute_force.knn(q[:3], x, 4)
        np.testing.assert_array_equal(i, np.asarray(gi))


# ---------------------------------------------------------------------------
# acceptance: tombstone correctness across all four index types
# ---------------------------------------------------------------------------


def _fresh_and_served(algo, x, q, k, dead, params=None, **kw):
    """Serve x with `dead` deleted vs the same algo freshly built on the
    survivors; returns (served (d, i-as-original-ids), fresh mapped to
    original ids)."""
    surv = np.setdiff1d(np.arange(x.shape[0]), dead)
    xs = x[surv]
    params = params or _params(max_wait_ms=0.5, warmup=False)
    with serve.Server(params) as srv:
        srv.create_index("default", x, algo=algo, **kw)
        srv.delete(dead)
        sd, si = srv.search(q, k)
    with serve.Server(params) as srv:
        srv.create_index("default", xs, algo=algo, **kw)
        fd, fi = srv.search(q, k)
    fi = np.where(fi >= 0, surv[np.clip(fi, 0, surv.size - 1)], -1)
    return (sd, si), (fd, fi)


@pytest.mark.parametrize("algo,kw", [
    ("brute_force", {}),
    ("ivf_flat", {}),
    ("ivf_pq", {"refine_ratio": 4}),
])
def test_tombstone_matches_fresh_index(data, algo, kw):
    x, q = data
    dead = np.asarray([0, 5, 17, 42, 99, 123, 200, 319])
    (sd, si), (fd, fi) = _fresh_and_served(algo, x, q[:8], 5, dead, **kw)
    assert not np.isin(si, dead).any()
    np.testing.assert_array_equal(si, fi)
    np.testing.assert_array_equal(sd, fd)


@pytest.mark.slow
def test_tombstone_matches_fresh_index_cagra(data):
    """The cagra leg of the tombstone matrix. Graph build + beam-search
    compiles dominate (~3 min on the CPU host even at a reduced set /
    small ladder — dated 2026-08-03, this suite), so like the rest of
    the cagra build tests it rides the full suite's slow lane; tier-1
    covers brute_force/ivf_flat/ivf_pq above."""
    x, q = data
    x = x[:160]
    dead = np.asarray([0, 5, 17, 42, 99, 123])
    bp = cagra.IndexParams(intermediate_graph_degree=32, graph_degree=16)
    (sd, si), (fd, fi) = _fresh_and_served(
        "cagra", x, q[:4], 5, dead,
        params=_params(max_batch_rows=4, max_wait_ms=0.5),
        build_params=bp)
    assert not np.isin(si, dead).any()
    np.testing.assert_array_equal(si, fi)
    np.testing.assert_array_equal(sd, fd)


def test_tombstones_never_leak_when_live_lt_k(data):
    rng = np.random.default_rng(12)
    small = rng.standard_normal((8, DIM)).astype(np.float32)
    with serve.Server(_params(warmup=False, compact_threshold=0)) as srv:
        srv.create_index("default", small)
        # fewer live rows than k: the tombstoned rows ride top-k at the
        # sentinel distance with their REAL ids inside the kernel — the
        # engine must mask them to -1, never hand a deleted id back
        srv.delete([0, 1, 2, 3, 4, 5])
        _, i = srv.search(small[6], 4)
        assert set(i[0].tolist()) == {6, 7, -1}
        assert (i[0] == -1).sum() == 2
        # same through the side buffer: deleted side-resident slots keep
        # their internal ids at the sentinel inside _merge_with_side
        vs = rng.standard_normal((3, DIM)).astype(np.float32)
        srv.upsert(vs, [100, 101, 102])
        srv.delete([101, 102])
        _, i2 = srv.search(small[7], 5)
        assert set(i2[0].tolist()) == {6, 7, 100, -1}
        assert (i2[0] == -1).sum() == 2


def test_delete_is_idempotent_and_counted(data):
    x, _ = data
    with serve.Server(_params()) as srv:
        srv.create_index("default", x, warmup=False)
        assert srv.delete([1, 2, 3]) == 3
        assert srv.delete([2, 3, 4]) == 1          # only 4 newly dead
        assert srv.stats()["tombstoned_rows"] == 4


def test_delete_stays_dead_across_upsert_transition(data):
    """An id deleted in identity mode must not be resurrected when the
    first upsert installs the explicit id translation (review fix)."""
    x, _ = data
    rng = np.random.default_rng(9)
    with serve.Server(_params(compact_threshold=0, warmup=False)) as srv:
        srv.create_index("default", x, warmup=False)
        assert srv.delete([5]) == 1
        srv.upsert(rng.standard_normal(DIM).astype(np.float32), [7777])
        assert srv.delete([5]) == 0                # still dead, not live
        _, i = srv.search(x[5], 5)
        assert 5 not in i


def test_k_beyond_index_rows_rejected():
    rng = np.random.default_rng(10)
    small = rng.standard_normal((6, DIM)).astype(np.float32)
    with serve.Server(_params(max_k=8)) as srv:
        srv.create_index("default", small, warmup=False)
        with pytest.raises(ValueError, match="index rows"):
            srv.submit(small[0], 7)                # 7 > 6 rows
        _, i = srv.search(small[0], 6)             # k == rows is fine
        assert i.shape == (1, 6)


# ---------------------------------------------------------------------------
# upsert / side buffer / compaction
# ---------------------------------------------------------------------------


def test_upsert_reachable_before_compaction(data):
    x, q = data
    rng = np.random.default_rng(3)
    with serve.Server(_params(compact_threshold=0, warmup=False)) as srv:
        srv.create_index("default", x)
        v = rng.standard_normal(DIM).astype(np.float32)
        srv.upsert(v, [7777])
        d, i = srv.search(v, 3)
        assert i[0, 0] == 7777 and d[0, 0] == pytest.approx(0.0, abs=1e-4)
        assert srv.generation() == 1               # no swap happened
        # replacement: upserting an EXISTING id hides the old row
        srv.upsert(v + 1.0, [0])
        d2, i2 = srv.search(v + 1.0, 1)
        assert i2[0, 0] == 0 and d2[0, 0] == pytest.approx(0.0, abs=1e-4)
        # a brand-new id can be deleted again while still side-resident
        srv.upsert(v + 2.0, [8888])
        srv.delete([8888])
        _, i3 = srv.search(v + 2.0, 5)
        assert 8888 not in i3


def test_base_delete_keeps_side_index_cache(data):
    x, _ = data
    with serve.Server(_params(compact_threshold=0, warmup=False)) as srv:
        srv.create_index("default", x)
        v = np.ones(DIM, np.float32)
        srv.upsert(v, [7000])
        srv.search(v, 2)                       # builds the side cache
        h = srv.registry.get("default").handle
        cached = h._side_cache
        assert cached is not None
        srv.delete([5])                        # tombstones a BASE row only
        _, i = srv.search(v, 2)
        assert i[0, 0] == 7000
        assert h._side_cache is cached, (
            "a base-row delete must not rebuild the side brute-force "
            "index — its content did not change")
        srv.upsert(v + 1.0, [7001])            # side content DID change
        srv.search(v, 2)
        assert h._side_cache is not cached


def test_per_index_warmup_override_respected(data, monkeypatch):
    from raft_tpu.serve import engine as serve_engine

    x, _ = data
    calls = []
    monkeypatch.setattr(
        serve_engine._IndexServing, "warmup_handle",
        lambda self, h: calls.append(self.name) or 0)
    # server-wide warmup stays True: the per-call override at
    # create_index must be remembered and gate the implicit re-warms
    # (growing upsert, compaction, swap) too
    with serve.Server(_params(side_capacity=1, compact_threshold=0)) as srv:
        srv.create_index("default", x, warmup=False)
        assert calls == []
        srv.upsert(np.ones(DIM, np.float32), [9000])       # side alloc
        srv.upsert(np.ones(DIM, np.float32) * 2, [9001])   # side grows
        assert calls == [], "warmup=False index re-warmed on upsert"
        srv.swap("default", dataset=x, wait=True)
        assert calls == [], "warmup=False index re-warmed on swap"


def test_compaction_extends_and_swaps(data):
    x, q = data
    rng = np.random.default_rng(4)
    with serve.Server(_params(compact_threshold=0, warmup=False)) as srv:
        srv.create_index("default", x, algo="ivf_flat")
        vecs = rng.standard_normal((3, DIM)).astype(np.float32)
        ids = [9001, 9002, 9003]
        srv.upsert(vecs, ids)
        assert srv.stats()["side_rows"] == 3
        fut = srv.compact(wait=True)
        assert fut.result() == 2                   # one swap
        assert srv.stats()["side_rows"] == 0
        for v, e in zip(vecs, ids):                # now served from main
            _, i = srv.search(v, 1)
            assert i[0, 0] == e
        # deletes recorded before compaction stay deleted after
        srv.delete([9002])
        _, i = srv.search(vecs[1], 3)
        assert 9002 not in i


def test_auto_compaction_at_threshold(data):
    x, _ = data
    rng = np.random.default_rng(5)
    with serve.Server(_params(compact_threshold=4, side_capacity=4,
                              warmup=False)) as srv:
        srv.create_index("default", x)
        for j in range(4):
            srv.upsert(rng.standard_normal(DIM).astype(np.float32),
                       [5000 + j])
        deadline = time.monotonic() + 120
        while srv.generation() < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert srv.generation() >= 2, "auto-compaction never swapped"
        _, i = srv.search(x[:2], 4)               # still serving correctly
        assert (i >= 0).all()


# ---------------------------------------------------------------------------
# prefilter composition
# ---------------------------------------------------------------------------


def test_user_prefilter_composes_with_tombstones(data):
    x, q = data
    allowed = np.arange(N) % 3 != 0
    dead = np.asarray([1, 2, 4, 5, 7, 8])          # all pass the filter?
    dead = dead[allowed[dead]]
    filt = Bitset.from_dense(allowed)
    with serve.Server(_params(max_wait_ms=2.0, warmup=False)) as srv:
        srv.create_index("default", x)
        srv.delete(dead)
        futs = [srv.submit(q[j], 5, prefilter=filt) for j in range(6)]
        eff = allowed.copy()
        eff[dead] = False
        sub = np.where(eff)[0]
        _, gi = brute_force.knn(q[:6], x[sub], 5)
        want = sub[np.asarray(gi)]
        for j, f in enumerate(futs):
            _, ids = f.result(timeout=60)
            np.testing.assert_array_equal(ids[0], want[j])


def test_user_prefilter_mutated_in_place_not_served_stale(data):
    """Bitset's public API mutates in place; the composed-filter device
    cache must key on content (via Bitset._version), not identity alone,
    or the second search serves rows the caller just excluded."""
    x, q = data
    filt = Bitset.from_dense(np.ones(N, dtype=bool))
    with serve.Server(_params(max_wait_ms=1.0, warmup=False)) as srv:
        srv.create_index("default", x)
        _, ids0 = srv.search(q[0], 5, prefilter=filt)
        banned = ids0[0].astype(np.int64)
        filt.set(np.asarray(banned), False)        # in-place mutation
        _, ids1 = srv.search(q[0], 5, prefilter=filt)
        assert not np.intersect1d(ids1[0], banned).size, (
            "stale composed filter served excluded rows")


def test_mixed_filter_traffic_splits_batches(data):
    x, q = data
    f1 = Bitset.from_dense(np.arange(N) < 200)
    with serve.Server(_params(max_wait_ms=5.0, warmup=False)) as srv:
        srv.create_index("default", x)
        futs = [srv.submit(q[0], 4),
                srv.submit(q[1], 4, prefilter=f1),
                srv.submit(q[2], 4)]
        _, i0 = futs[0].result(timeout=60)
        _, i1 = futs[1].result(timeout=60)
        _, i2 = futs[2].result(timeout=60)
        assert (i1 < 200).all()
        _, g0 = brute_force.knn(q[:1], x, 4)
        np.testing.assert_array_equal(i0, np.asarray(g0))


# ---------------------------------------------------------------------------
# resilience wiring
# ---------------------------------------------------------------------------


def test_injected_oom_downshifts_and_splits(data):
    x, q = data
    with serve.Server(_params(max_wait_ms=50.0, warmup=False)) as srv:
        srv.create_index("default", x)
        assert srv.stats()["bucket_ceiling"] == 16
        faultinject.install("oom@stage:serve.dispatch")
        futs = [srv.submit(q[2 * j:2 * j + 2], 4) for j in range(4)]
        _, gi = brute_force.knn(q[:8], x, 4)
        gi = np.asarray(gi)
        for j, f in enumerate(futs):               # every request answered
            _, ids = f.result(timeout=120)
            np.testing.assert_array_equal(ids, gi[2 * j:2 * j + 2])
        assert srv.stats()["bucket_ceiling"] < 16
        assert tuning.runtime_budget("serve_batch_rows") is not None


def test_injected_transient_is_retried(data):
    x, q = data
    with serve.Server(_params(warmup=False)) as srv:
        srv.create_index("default", x)
        faultinject.install("transient@stage:serve.dispatch")
        d, i = srv.search(q[:2], 4)
        _, gi = brute_force.knn(q[:2], x, 4)
        np.testing.assert_array_equal(i, np.asarray(gi))


def test_single_request_oom_fails_cleanly(data):
    x, q = data
    with serve.Server(_params(warmup=False)) as srv:
        srv.create_index("default", x)
        faultinject.install("oom@stage:serve.dispatch*99")
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            srv.search(q[0], 4)
        faultinject.clear()
        _, i = srv.search(q[0], 4)                 # server still healthy
        _, gi = brute_force.knn(q[:1], x, 4)
        np.testing.assert_array_equal(i, np.asarray(gi))


# ---------------------------------------------------------------------------
# observability wiring
# ---------------------------------------------------------------------------


def test_serve_metrics_emitted(data):
    from raft_tpu import obs

    x, q = data
    obs.set_mode("on")
    try:
        obs.reset()
        with serve.Server(_params(max_wait_ms=2.0)) as srv:
            srv.create_index("default", x)
            futs = [srv.submit(q[j], 4) for j in range(6)]
            for f in futs:
                f.result(timeout=60)
            srv.delete([3])
            srv.swap("default", dataset=x, wait=True)
        m = obs.snapshot(runtime_gauges=False)["metrics"]
        for name in ("serve.requests_total", "serve.queries_total",
                     "serve.batches_total", "serve.batch_fill_ratio",
                     "serve.batch_latency_ms", "serve.swaps_total",
                     "serve.deletes_total", "serve.warmup_shapes"):
            assert name in m, f"{name} missing from {sorted(m)}"
        assert sum(p["value"] for p in
                   m["serve.swaps_total"]["points"]) >= 2
    finally:
        obs.set_mode(None)
        obs.reset()


# ---------------------------------------------------------------------------
# graft-race regressions (ISSUE 7): races found dogfooding GL010/GL011
# ---------------------------------------------------------------------------


def test_lower_ceiling_is_monotone():
    """The OOM downshift's atomic clamp: a later, SHALLOWER downshift
    must not raise the ceiling back over a deeper one (the old
    read-modify-write through set_ceiling(min(ceiling, x)) could
    interleave and lose the deeper update)."""
    b = serve.MicroBatcher(lambda batch: None, max_batch_rows=64)
    try:
        assert b.lower_ceiling(8) == 8
        # shallower clamp afterwards: must stay at 8, never go back up
        assert b.lower_ceiling(32) == 8
        assert b.ceiling == 8
        # floor is the smallest ladder rung
        assert b.lower_ceiling(0) == b.ladder[0]
        # set_ceiling remains the explicit (non-monotone) knob
        b.set_ceiling(32)
        assert b.ceiling == 32
    finally:
        b.close(timeout_s=10)


def test_add_on_drain_during_drain_still_fires():
    """A callback registered while _drain is mid-flight (captured its
    list, not yet drained.set()) must still be invoked — it used to be
    appended to a list nobody would ever read again (for the fabric:
    _retire_cluster never fired and workers pinned retired shards)."""
    from raft_tpu.serve.registry import Generation

    gen = Generation("g", 1, handle=object())
    fired = []

    def first(g):
        # runs inside _drain's callback loop: drained is NOT yet set,
        # the capture already happened — the pre-fix window
        assert not g.drained.is_set()
        g.add_on_drain(lambda g2: fired.append("late"))

    gen.add_on_drain(first)
    gen.retire()                      # no pins -> drains inline
    assert gen.drained.is_set()
    assert fired == ["late"], fired


def test_serve_trace_waterfalls_share_batch_span_link(data):
    """graft-trace on the single-process path (ISSUE 13): each submit
    mints a trace at the serving entry; two requests coalesced into ONE
    batch complete as two waterfalls (queue_wait + batch_search) whose
    batch stages carry the SAME batch_seq — the span link tying the
    traces one dispatch served."""
    from raft_tpu import obs

    dataset, queries = data
    obs.set_mode("on")
    try:
        srv = serve.Server(serve.ServeParams(
            max_batch_rows=16, max_wait_ms=150.0, max_k=8))
        srv.create_index("default", dataset, algo="brute_force")
        obs.trace.reset()                 # drop warmup-era records
        f1 = srv.submit(queries[:1], 4)
        f2 = srv.submit(queries[1:2], 4)
        f1.result(timeout=30)
        f2.result(timeout=30)
        wfs = obs.trace_report()
        assert len(wfs) == 2
        seqs = set()
        for wf in wfs:
            assert wf["entry"] == "serve.submit"
            assert wf["status"] == "ok"
            names = [s["stage"] for s in wf["stages"]]
            assert names == ["queue_wait", "batch_search"]
            batch = wf["stages"][1]
            assert batch["bucket"] >= 2 and "linger_ms" in batch
            seqs.add(batch["batch_seq"])
        assert len(seqs) == 1             # one batch served both traces
        # a rejected submit still completes a (tiny) waterfall saying why
        srv.close()
        with pytest.raises(serve.Overloaded):
            srv.submit(queries[:1], 4)
        tail = obs.trace_report()[-1]
        assert tail["status"] == "rejected"
        assert tail["attrs"]["reason"] == "closed"
    finally:
        obs.set_mode(None)
        obs.reset()


# ---------------------------------------------------------------------------
# a served batch's life: request wait, dispatch, device wait, deliver
# ---------------------------------------------------------------------------

_BATCH_INTERVALS = ("serve.dispatch_ms", "serve.device_wait_ms",
                    "serve.deliver_ms")


def _hist(snap, name):
    """(count, sum) of the histogram ``name`` over all its label sets."""
    pts = snap["metrics"].get(name, {}).get("points", [])
    return sum(p["count"] for p in pts), sum(p["sum"] for p in pts)


def _host_spans(logdir, names):
    """{name: [(start_ns, end_ns), ...]} of the profiler trace's host
    events named in ``names``, each list in start order."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return {n: sorted(v) for n, v in out.items()}


@pytest.mark.parametrize("depth", [2, 0], ids=["pipelined", "sync"])
def test_serve_batch_intervals_tile_each_request(data, depth, tmp_path):
    """With obs on, every request records its own wait, and every batch
    its dispatch, device wait and deliver: one sample each, as spans in
    the profiler trace in that order, without overlap. Requests served
    one at a time spend at least the four intervals' sum in the
    server."""
    import jax

    from raft_tpu import obs

    dataset, queries = data
    obs.set_mode("on")
    try:
        with serve.Server(_params(max_wait_ms=2.0,
                                  pipeline_depth=depth)) as srv:
            srv.create_index("default", dataset, algo="ivf_flat",
                             build_params=ivf_flat.IndexParams(n_lists=8))
            obs.reset()
            waited = 0.0
            for row in queries[:6]:
                t0 = time.monotonic()
                srv.submit(row[None], 4).result(timeout=60)
                waited += time.monotonic() - t0
            snap = obs.snapshot(runtime_gauges=False)
            parts = sum(_hist(snap, n)[1] for n in
                        ("serve.request_wait_ms",) + _BATCH_INTERVALS)
            assert 0 < parts <= waited * 1e3

            obs.reset()
            with jax.profiler.trace(str(tmp_path)):
                futs = [srv.submit(row[None], 4) for row in queries]
                for f in futs:
                    f.result(timeout=60)
            snap = obs.snapshot(runtime_gauges=False)
    finally:
        obs.set_mode(None)
        obs.reset()
    batches = sum(p["value"] for p in
                  snap["metrics"]["serve.batches_total"]["points"])
    assert 0 < batches <= len(queries)
    assert _hist(snap, "serve.request_wait_ms")[0] == len(queries)
    for name in _BATCH_INTERVALS:
        assert _hist(snap, name)[0] == batches, name
    spans = _host_spans(tmp_path, ("serve.dispatch", "serve.await",
                                   "serve.deliver"))
    assert all(len(v) == batches for v in spans.values()), spans
    for disp, wait, dlv in zip(spans["serve.dispatch"],
                               spans["serve.await"],
                               spans["serve.deliver"]):
        assert disp[1] <= wait[0] and wait[1] <= dlv[0]
    # each interval holds its span (within clock-read slack per batch)
    for hist, span in (("serve.dispatch_ms", "serve.dispatch"),
                       ("serve.device_wait_ms", "serve.await"),
                       ("serve.deliver_ms", "serve.deliver")):
        held_ms = sum(e - s for s, e in spans[span]) / 1e6
        assert _hist(snap, hist)[1] >= held_ms - 0.05 * batches, hist


def test_serve_batch_intervals_silent_with_obs_off(data):
    from raft_tpu import obs

    dataset, queries = data
    obs.reset()
    with serve.Server(_params(pipeline_depth=2)) as srv:
        srv.create_index("default", dataset, algo="ivf_flat",
                         build_params=ivf_flat.IndexParams(n_lists=8))
        futs = [srv.submit(row[None], 4) for row in queries]
        for f in futs:
            f.result(timeout=60)
    obs.set_mode("on")
    try:
        metrics = obs.snapshot(runtime_gauges=False)["metrics"]
    finally:
        obs.set_mode(None)
    for name in ("serve.request_wait_ms",) + _BATCH_INTERVALS:
        assert name not in metrics


def test_threadsan_suite_verdict_zzz():
    """Suite-level ISSUE-7 acceptance (runs last in file order): every
    serve test above constructed its locks through the sanitizer, and
    the observed acquisition order stayed acyclic with zero hold-budget
    breaches — an inversion/breach would also have failed its own test
    by raising."""
    from raft_tpu.analysis import lockwatch as lw

    s = lw.stats()
    assert s["inversions"] == 0 and s["budget_breaches"] == 0, s
    # the serve hierarchy actually got exercised: the mutation ->
    # engine -> registry -> generation chain appears in the graph
    g = lw.order_graph()
    assert "serve.registry" in g and "serve.generation" in \
        g["serve.registry"], sorted(g)
