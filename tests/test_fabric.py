"""Multi-host serving fabric (ISSUE 6; marker ``multihost``).

Covers: exact routing vs a surviving-shard oracle (bitwise — the oracle
runs the SAME per-shard search + merge code path), hedged retries past
an injected slow worker, circuit breaking + half-open re-admission
after a worker death, the two-phase cluster hot-swap (commit AND
abort-rollback legs), the cross-process SIGKILL kill-and-resume drill,
and the chaos acceptance: a closed-loop load run under injected
``dead@proc`` + ``slow@proc`` faults with a mid-run swap, where every
answer must be bitwise-correct for the shards it reports covered.

Most tests run the in-process :class:`LocalGroup` transport (identical
router semantics, no spawn cost); the kill-and-resume and chaos tests
spawn real ``multiprocessing`` workers. Worker counts and timeouts are
bounded so the suite rides tier-1.
"""

import threading
import time

import numpy as np
import pytest

from raft_tpu import serve, tuning
from raft_tpu.analysis import lockwatch
from raft_tpu.comms import procgroup
from raft_tpu.resilience import ShardDropoutError, faultinject
from raft_tpu.serve import fabric as fabmod

pytestmark = [pytest.mark.multihost, pytest.mark.threadsan]


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    # ISSUE 7: the fabric suite runs with SANITIZED locks (router,
    # health breakers, worker groups) — every run doubles as the
    # zero-inversion / zero-hold-budget-breach acceptance
    monkeypatch.setenv(lockwatch.ENV_VAR, "1")
    faultinject.clear()
    tuning.reload()
    yield
    faultinject.clear()
    tuning.reload()


def _params(**kw):
    base = dict(
        n_workers=3, replication=2, rpc_deadline_s=3.0,
        rpc_retries=2, retry_backoff_s=0.01, hedge_after_ms=25.0,
        halfopen_after_s=0.05, probe_timeout_s=10.0,
        swap_deadline_s=30.0, slow_ms=150.0, auto_probe=False,
        fail_threshold=2,
    )
    base.update(kw)
    return serve.FabricParams(**base)


def _data(n=96, dim=8, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, dim)).astype(np.float32),
            rng.standard_normal((4, dim)).astype(np.float32))


def _oracle(dataset, q, k, n_workers, covered, algo="brute_force"):
    """Surviving-shard oracle: the same shard search + merge code path
    the workers and router run, restricted to ``covered`` shards —
    bitwise identity is the contract, not approximate recall."""
    bounds = fabmod.shard_bounds(dataset.shape[0], n_workers)
    results = {}
    for s in range(n_workers):
        if s not in covered:
            results[s] = None
            continue
        entry = procgroup.build_shard_entry(
            dataset[bounds[s]:bounds[s + 1]], bounds[s], algo)
        d, i = procgroup.search_shard_entry(entry, q, k)
        results[s] = (0, d, i)
    return fabmod.merge_shard_results(n_workers, results, q.shape[0], k)


# ---------------------------------------------------------------------------
# LocalGroup: routing, hedging, circuit breaking, swap protocol
# ---------------------------------------------------------------------------


def test_fabric_matches_oracle_full_coverage():
    ds, q = _data()
    with serve.Fabric(ds, params=_params(), group="local") as fab:
        d, i, cov = fab.search(q, 5)
        assert cov.shape == (4,) and (cov == 1.0).all()
        od, oi, _ = _oracle(ds, q, 5, 3, covered={0, 1, 2})
        np.testing.assert_array_equal(i, oi)
        np.testing.assert_array_equal(d, od)
        # single-row convenience: 1-D query promotes to [1, dim]
        d1, i1, cov1 = fab.search(q[0], 5)
        np.testing.assert_array_equal(i1, oi[:1])
        assert cov1.shape == (1,)


def test_fabric_hedges_past_slow_worker():
    ds, q = _data()
    with serve.Fabric(ds, params=_params(), group="local") as fab:
        fab.search(q, 5)                      # warm every traced shape
        before = fab.stats()["counters"].get("hedges", 0)
        # shard 0's primary owner is worker 0: stall exactly that RPC
        # past the 25ms hedge threshold; the replica (worker 1) covers
        with faultinject.inject("slow@proc:0*1"):
            d, i, cov = fab.search(q, 5)
        assert (cov == 1.0).all()
        od, oi, _ = _oracle(ds, q, 5, 3, covered={0, 1, 2})
        np.testing.assert_array_equal(i, oi)
        np.testing.assert_array_equal(d, od)
        assert fab.stats()["counters"].get("hedges", 0) > before


def test_fabric_dead_worker_degrades_with_honest_coverage():
    ds, q = _data()
    p = _params(replication=1, rpc_deadline_s=0.5)
    with serve.Fabric(ds, params=p, group="local") as fab:
        fab.search(q, 5)
        with faultinject.inject("dead@proc:1"):
            d, i, cov = fab.search(q, 5)
        # shard 1 lost; per-row coverage says so on every row
        np.testing.assert_allclose(cov, 2 / 3)
        od, oi, validity = _oracle(ds, q, 5, 3, covered={0, 2})
        np.testing.assert_array_equal(i, oi)
        np.testing.assert_array_equal(d, od)
        assert not validity[1].any()
        # the confirmed-dead worker's circuit opened
        assert fab.stats()["health"][1] == "open"
        assert fab.stats()["counters"]["dropouts"] >= 1
        # partial_ok=False refuses silent degradation
        with pytest.raises(ShardDropoutError):
            fab.search(q, 5, partial_ok=False)
        # coverage floor: 2/3 < 0.9 floor refuses too
        fab.params.coverage_floor = 0.9
        with pytest.raises(ShardDropoutError):
            fab.search(q, 5)


def test_fabric_halfopen_readmission_after_restart():
    ds, q = _data()
    p = _params(replication=1, rpc_deadline_s=0.5)
    with serve.Fabric(ds, params=p, group="local") as fab:
        fab.search(q, 5)
        with faultinject.inject("dead@proc:1"):
            fab.search(q, 5)
        assert fab.stats()["health"][1] == "open"
        fab.restart_worker(1)                 # fresh worker, no state
        assert fab.stats()["health"][1] == "open"   # not routed yet
        # half-open probe: ping ok but stale -> resync -> closed
        deadline = time.monotonic() + 20.0
        while fab.stats()["health"][1] != "closed":
            fab.probe_now()
            assert time.monotonic() < deadline, fab.stats()
            time.sleep(0.05)
        d, i, cov = fab.search(q, 5)
        assert (cov == 1.0).all()
        od, oi, _ = _oracle(ds, q, 5, 3, covered={0, 1, 2})
        np.testing.assert_array_equal(i, oi)
        c = fab.stats()["counters"]
        assert c.get("restarts", 0) == 1 and c.get("probes", 0) >= 1


def test_fabric_two_phase_swap_commits_everywhere():
    ds, q = _data()
    rng = np.random.default_rng(7)
    ds2 = rng.standard_normal((120, 8)).astype(np.float32)
    with serve.Fabric(ds, params=_params(), group="local") as fab:
        assert fab.generation() == 1
        gen = fab.swap(ds2)
        assert gen == 2 and fab.generation() == 2
        d, i, cov = fab.search(q, 5)
        assert (cov == 1.0).all()
        od, oi, _ = _oracle(ds2, q, 5, 3, covered={0, 1, 2})
        np.testing.assert_array_equal(i, oi)
        np.testing.assert_array_equal(d, od)
        # the retired generation is garbage-collected on every worker
        # once its last router pin drained (retire is async)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            gens = [fab.group.call(r, "ping", {}).result(timeout=5.0)
                    ["gens"] for r in range(3)]
            if all(g == [2] for g in gens):
                break
            time.sleep(0.05)
        assert all(g == [2] for g in gens), gens


def test_fabric_swap_abort_rolls_back_cleanly():
    ds, q = _data()
    rng = np.random.default_rng(8)
    ds2 = rng.standard_normal((120, 8)).astype(np.float32)
    p = _params(swap_deadline_s=1.0)
    with serve.Fabric(ds, params=p, group="local") as fab:
        # one worker's prepare response vanishes -> barrier aborts,
        # every worker rolls back, generation 1 keeps serving
        with faultinject.inject("drop@rpc:prepare"):
            with pytest.raises(serve.FabricSwapError):
                fab.swap(ds2)
        assert fab.generation() == 1
        assert fab.stats()["counters"]["swap_aborts"] == 1
        d, i, cov = fab.search(q, 5)
        assert (cov == 1.0).all()
        od, oi, _ = _oracle(ds, q, 5, 3, covered={0, 1, 2})
        np.testing.assert_array_equal(i, oi)       # still OLD content
        # nothing staged anywhere; the next swap succeeds
        assert fab.swap(ds2) == 3
        d, i, _ = fab.search(q, 5)
        od, oi, _ = _oracle(ds2, q, 5, 3, covered={0, 1, 2})
        np.testing.assert_array_equal(i, oi)


def test_fabric_ivf_flat_workers_match_oracle():
    ds, q = _data(n=120)
    p = _params(worker_algo="ivf_flat")
    with serve.Fabric(ds, params=p, group="local") as fab:
        d, i, cov = fab.search(q, 5)
        assert (cov == 1.0).all()
        od, oi, _ = _oracle(ds, q, 5, 3, covered={0, 1, 2},
                            algo="ivf_flat")
        np.testing.assert_array_equal(i, oi)
        np.testing.assert_array_equal(d, od)


def test_fabric_dropped_rpc_does_not_leak_pending():
    """A response that never arrives (drop@rpc) must not pin its Future
    + query payload in the transport's pending map forever: the router
    forgets abandoned requests at the deadline / on hedge win."""
    ds, q = _data()
    p = _params(replication=1, rpc_deadline_s=0.3, rpc_retries=1)
    with serve.Fabric(ds, params=p, group="local") as fab:
        fab.search(q, 5)
        with faultinject.inject("drop@rpc:search*2"):
            d, i, cov = fab.search(q, 5)
        assert cov.min() < 1.0          # some shard lost its response
        # every abandoned request was forgotten at the transport
        deadline = time.monotonic() + 5.0
        while any(w.pending for w in fab.group._workers):
            assert time.monotonic() < deadline, [
                dict(w.pending) for w in fab.group._workers]
            time.sleep(0.02)


# ---------------------------------------------------------------------------
# graft-trace: waterfall assembly under faults (ISSUE 13)
# ---------------------------------------------------------------------------


@pytest.fixture()
def _obs_on():
    from raft_tpu import obs

    obs.set_mode("on")
    obs.reset()
    yield obs
    obs.reset()
    obs.set_mode(None)


def test_fabric_trace_complete_waterfall_full_coverage(_obs_on):
    obs = _obs_on
    ds, q = _data()
    with serve.Fabric(ds, params=_params(), group="local") as fab:
        fab.search(q, 5)                      # warm (compile noise)
        obs.trace.reset()
        fab.search(q, 5)
        (wf,) = obs.trace_report()
        assert wf["entry"] == "fabric.search" and wf["status"] == "ok"
        assert wf["attrs"]["coverage_min"] == 1.0
        assert wf["attrs"]["covered_shards"] == [0, 1, 2]
        # one ok rpc + one device-complete worker_scan per shard, then
        # the merge closes the waterfall
        for s in range(3):
            assert any(st["stage"] == "rpc" and st["shard"] == s
                       and st["status"] == "ok"
                       for st in wf["stages"])
            assert any(st["stage"] == "worker_scan" and st["shard"] == s
                       and st["device_complete"]
                       for st in wf["stages"])
        assert wf["stages"][-1]["stage"] == "merge"
        # every stage is time-positioned inside the trace
        assert all("t_off_ms" in st for st in wf["stages"]
                   if st.get("ms") is not None)


def test_fabric_trace_partial_waterfall_carries_failure(_obs_on):
    """dead@proc mid-query (no replica): the waterfall completes as
    DEGRADED, carrying the failed rpc attempt for the lost shard while
    the surviving shards' spans are intact — the partial-visibility
    contract."""
    obs = _obs_on
    ds, q = _data()
    p = _params(replication=1, rpc_deadline_s=0.5)
    with serve.Fabric(ds, params=p, group="local") as fab:
        fab.search(q, 5)
        obs.trace.reset()
        with faultinject.inject("dead@proc:1"):
            d, i, cov = fab.search(q, 5)
        np.testing.assert_allclose(cov, 2 / 3)
        (wf,) = obs.trace_report()
        assert wf["status"] == "degraded"
        assert wf["attrs"]["covered_shards"] == [0, 2]
        fails = [st for st in wf["stages"]
                 if st.get("shard") == 1 and st["stage"] == "rpc"]
        assert fails and all(
            st["status"] in ("failed", "timeout") for st in fails)
        assert any(st.get("kind") == "dead_backend" for st in fails)
        # the survivors' scans still ride the same waterfall
        assert {st["shard"] for st in wf["stages"]
                if st["stage"] == "worker_scan"} == {0, 2}


def test_fabric_trace_failover_replica_spans_in_waterfall(_obs_on):
    """dead@proc with a replica: the dead primary's failed attempt AND
    the failover replica's rpc + worker_scan land in ONE waterfall, and
    the answer stays fully covered."""
    obs = _obs_on
    ds, q = _data()
    p = _params(rpc_deadline_s=2.0)
    with serve.Fabric(ds, params=p, group="local") as fab:
        fab.search(q, 5)
        obs.trace.reset()
        with faultinject.inject("dead@proc:0"):
            d, i, cov = fab.search(q, 5)
        assert (cov == 1.0).all()
        (wf,) = obs.trace_report()
        assert wf["status"] == "ok"
        # shard 0's primary owner is worker 0 (died); its replica
        # (worker 1) answered — both attempts visible
        s0 = [st for st in wf["stages"] if st.get("shard") == 0]
        assert any(st["stage"] == "rpc" and st["worker"] == 0
                   and st["status"] in ("failed", "timeout")
                   for st in s0)
        assert any(st["stage"] == "rpc" and st["worker"] == 1
                   and st["status"] == "ok" for st in s0)
        assert any(st["stage"] == "worker_scan" and st["worker"] == 1
                   for st in s0)


def test_fabric_trace_hedged_race_records_both_attempts(_obs_on):
    """A hedged race records BOTH attempts as sibling rpc stages with
    the winner marked hedge_win and the loser hedge_loser."""
    obs = _obs_on
    ds, q = _data()
    # the stalled primary answers a second after the hedge fires: the
    # replica wins however slowly parallel test workers let it answer
    with serve.Fabric(ds, params=_params(slow_ms=1000.0),
                      group="local") as fab:
        fab.search(q, 5)
        obs.trace.reset()
        with faultinject.inject("slow@proc:0*1"):
            d, i, cov = fab.search(q, 5)
        assert (cov == 1.0).all()
        (wf,) = obs.trace_report()
        s0 = [st for st in wf["stages"]
              if st.get("shard") == 0 and st["stage"] == "rpc"]
        assert {st["status"] for st in s0} == {"hedge_win",
                                               "hedge_loser"}
        win = next(st for st in s0 if st["status"] == "hedge_win")
        lose = next(st for st in s0 if st["status"] == "hedge_loser")
        assert win["worker"] == 1 and lose["worker"] == 0
        # the hedge fired AFTER the primary (time-positioned later)
        assert win["t_off_ms"] > lose["t_off_ms"]


def test_fabric_trace_raised_query_finishes_failed(_obs_on):
    """A coverage shortfall that RAISES to the caller must complete its
    waterfall as failed, not degraded/ok — the answered/complete
    columns and the chaos >=99% bar count only queries the caller
    actually got an answer for."""
    obs = _obs_on
    ds, q = _data()
    p = _params(replication=1, rpc_deadline_s=0.5)
    with serve.Fabric(ds, params=p, group="local") as fab:
        fab.search(q, 5)
        obs.trace.reset()
        with faultinject.inject("dead@proc:1"):
            with pytest.raises(ShardDropoutError):
                fab.search(q, 5, partial_ok=False)
        (wf,) = obs.trace_report()
        assert wf["status"] == "failed"
        assert wf["attrs"]["error"] == "ShardDropoutError"
        assert not obs.trace.waterfall_complete(wf)
        # same contract for the coverage floor under partial_ok=True
        fab.params.coverage_floor = 0.9
        obs.trace.reset()
        with faultinject.inject("dead@proc:1"):
            with pytest.raises(ShardDropoutError):
                fab.search(q, 5)
        assert obs.trace_report()[-1]["status"] == "failed"


def test_fabric_trace_ambient_context_linked_not_adopted(_obs_on):
    """An enclosing ambient context must not be adopted as the search's
    own waterfall id (cross-process ids have no local record; a local
    one would be stolen from the caller) — the entry mints its own and
    links the parent."""
    obs = _obs_on
    ds, q = _data()
    with serve.Fabric(ds, params=_params(), group="local") as fab:
        fab.search(q, 5)
        obs.trace.reset()
        outer = obs.start_trace("caller.op")
        with obs.trace.activate(outer):
            fab.search(q, 5)
        (wf,) = obs.trace_report()             # the fabric's record
        assert wf["trace_id"] != outer.trace_id
        assert wf["attrs"]["parent_trace"] == outer.trace_id
        # the caller's own record is untouched and still completable
        done = obs.trace.finish(outer)
        assert done is not None and done["status"] == "ok"


def test_fabric_trace_rpc_payload_carries_context(_obs_on):
    """The propagation contract GL019 enforces, observed live: the
    search RPC payload crossing the transport carries the minted
    (trace_id, parent_span_id) field."""
    obs = _obs_on
    ds, q = _data()
    with serve.Fabric(ds, params=_params(), group="local") as fab:
        seen = []
        orig = fab.group.call

        def spy(rank, method, payload=None):
            if method == "search":
                seen.append(payload.get(obs.trace.WIRE_FIELD))
            return orig(rank, method, payload)

        fab.group.call = spy
        fab.search(q, 5)
        assert seen and all(
            w and set(w) == {"trace_id", "parent_span_id"}
            for w in seen)
        tid = {w["trace_id"] for w in seen}
        assert len(tid) == 1                  # one id names the query
        assert obs.trace_report()[-1]["trace_id"] == tid.pop()


def test_fabric_federation_local_group_shared_registry_not_duplicated(
        _obs_on):
    """LocalGroup workers share the ROUTER's registry: they answer the
    scrape (listed in ``workers``) but hand back NO metrics — the
    shared series arrive once, under worker="router", instead of
    (n_workers+1)x-ing every fleet sum."""
    obs = _obs_on
    ds, q = _data()
    with serve.Fabric(ds, params=_params(), group="local") as fab:
        fab.search(q, 5)
        fed = fab.collect_metrics()
        assert fed["mode"] == "federated"
        assert fed["workers"] == ["w0", "w1", "w2"]   # all answered
        assert fed["shared_registry"] is True
        assert fed["generation"] == 1
        assert fed["worker_health"] == {"w0": "closed", "w1": "closed",
                                        "w2": "closed"}
        # every series appears exactly ONCE, as the router's
        labels = {p["labels"]["worker"]
                  for m in fed["metrics"].values()
                  for p in m.get("points", ())}
        assert labels == {"router"}
        pts = fed["metrics"]["fabric.worker_rpcs_total"]["points"]
        assert len([p for p in pts
                    if p["labels"].get("method") == "search"]) == 1
        # and the whole thing renders as one valid exposition
        text = fab.export_federated_prometheus()
        assert "raft_tpu_fabric_worker_rpcs_total_total" not in text
        assert 'raft_tpu_fabric_worker_rpcs_total{' in text


# ---------------------------------------------------------------------------
# real multiprocessing: SIGKILL kill-and-resume + chaos acceptance
# ---------------------------------------------------------------------------


def test_fabric_kill_and_resume_multiprocess():
    """ISSUE 6 satellite: kill a worker mid-stream (SIGKILL), assert
    partial answers carry correct coverage, the circuit opens, and a
    restarted worker is re-admitted through half-open probing with
    bitwise-identical results vs an uninjected run on the surviving
    shards."""
    ds, q = _data(n=60)
    p = _params(replication=1, rpc_deadline_s=10.0, probe_timeout_s=10.0,
                hedge_after_ms=5000.0)
    fab = serve.Fabric(ds, params=p, group="proc")
    try:
        d0, i0, cov0 = fab.search(q, 5)
        assert (cov0 == 1.0).all()
        fab.group.kill(1)                     # SIGKILL, mid-stream
        d, i, cov = fab.search(q, 5)
        np.testing.assert_allclose(cov, 2 / 3)
        od, oi, _ = _oracle(ds, q, 5, 3, covered={0, 2})
        np.testing.assert_array_equal(i, oi)  # bitwise vs the oracle
        np.testing.assert_array_equal(d, od)
        assert fab.stats()["health"][1] == "open"
        # rejoin: respawn + half-open probing until the circuit closes
        fab.restart_worker(1)
        deadline = time.monotonic() + 60.0
        while fab.stats()["health"][1] != "closed":
            fab.probe_now()
            assert time.monotonic() < deadline, fab.stats()
            time.sleep(0.25)
        d2, i2, cov2 = fab.search(q, 5)
        assert (cov2 == 1.0).all()
        np.testing.assert_array_equal(i2, i0)
        np.testing.assert_array_equal(d2, d0)
    finally:
        fab.close()


def test_fabric_chaos_acceptance_multiprocess():
    """ISSUE 6 acceptance: closed-loop load under injected dead@proc +
    slow@proc faults with a mid-run cluster hot-swap. The fabric must
    return ZERO wrong answers (every answer bitwise-correct for the
    shards it reports covered, per its pinned generation), report
    coverage honestly, never mix generations, complete (or fully roll
    back) the swap, and re-admit the killed worker through half-open
    probing — with counters matching the injected fault script."""
    from raft_tpu import obs

    rng = np.random.default_rng(3)
    ds1 = rng.standard_normal((120, 8)).astype(np.float32)
    ds2 = rng.standard_normal((150, 8)).astype(np.float32)
    datasets = {}
    p = _params(replication=2, rpc_deadline_s=5.0, slow_ms=300.0,
                hedge_after_ms=25.0, probe_timeout_s=10.0,
                swap_deadline_s=60.0)
    obs.set_mode("on")
    obs.reset()        # earlier tests' waterfalls must not ride along
    fab = serve.Fabric(ds1, params=p, group="proc",
                       fault_spec="dead@proc:2,slow@proc:1*2")
    datasets[1] = ds1
    recorded = []
    rec_lock = threading.Lock()
    stop = threading.Event()

    def client(wid):
        crng = np.random.default_rng(100 + wid)
        while not stop.is_set():
            q = crng.standard_normal((1, 8)).astype(np.float32)
            out = fab.search(q, 4, detail=True)
            with rec_lock:
                recorded.append((q,) + out)

    threads = [threading.Thread(target=client, args=(w,), daemon=True)
               for w in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(1.5)                       # faults fire under load
        gen2 = fab.swap(ds2)                  # barrier inside the storm
        assert gen2 == 2
        datasets[2] = ds2
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        # worker 2 died on its first search; rejoin through half-open
        assert fab.stats()["health"][2] == "open"
        fab.restart_worker(2)
        deadline = time.monotonic() + 60.0
        while fab.stats()["health"][2] != "closed":
            fab.probe_now()
            assert time.monotonic() < deadline, fab.stats()
            time.sleep(0.25)
        dF, iF, covF = fab.search(rng.standard_normal(
            (2, 8)).astype(np.float32), 4)
        assert (covF == 1.0).all()
        counters = fab.stats()["counters"]
        health = fab.stats()["health"]
        # federation over REAL worker processes (each owns its own
        # registry): every live worker answers and its series arrive
        # under its own label — the per-worker half LocalGroup's
        # shared-registry twin cannot exercise
        fed = fab.collect_metrics()
        assert fed["workers"] == ["w0", "w1", "w2"]
        assert "shared_registry" not in fed
        pts = fed["metrics"]["fabric.worker_rpcs_total"]["points"]
        per_worker = {p["labels"]["worker"] for p in pts
                      if p["labels"].get("method") == "search"}
        assert {"w0", "w1", "w2"} <= per_worker
    finally:
        fab.close()
        obs.set_mode(None)

    # --- zero wrong answers: bitwise vs the surviving-shard oracle ----
    assert len(recorded) >= 10
    degraded = 0
    for q, d, i, cov, validity, gen_id in recorded:
        assert gen_id in datasets, gen_id     # no phantom generations
        # coverage must restate the validity matrix exactly (honesty)
        np.testing.assert_allclose(cov, validity.mean(axis=0))
        rows_uniform = [validity[s].all() or not validity[s].any()
                        for s in range(3)]
        assert all(rows_uniform)              # no NaN rows in this drill
        covered = {s for s in range(3) if validity[s].all()}
        if len(covered) < 3:
            degraded += 1
        od, oi, _ = _oracle(datasets[gen_id], q, 4, 3, covered=covered)
        np.testing.assert_array_equal(i, oi)
        np.testing.assert_array_equal(d, od)
    # --- counters match the injected fault script ---------------------
    # slow@proc:1*2 stalled two responses 300ms past the 25ms hedge
    # threshold -> hedges fired; dead@proc:2 killed a worker -> its
    # circuit cycled open -> half_open -> closed on rejoin
    assert counters.get("hedges", 0) >= 1
    assert counters.get("restarts", 0) == 1
    assert counters.get("mixed_gen", 0) == 0  # swap atomicity held
    assert counters.get("swaps", 0) == 2      # initial load + mid-run
    assert counters.get("swap_aborts", 0) == 0
    assert health == {0: "closed", 1: "closed", 2: "closed"}
    # --- graft-trace acceptance (ISSUE 13): under the same chaos, the
    # trace layer assembled a COMPLETE end-to-end waterfall for >=99%
    # of answered queries: every shard the answer reports covered
    # contributed a device-complete worker_scan stage from the worker
    # that actually served it, and a merge stage closed the record
    from raft_tpu.obs.trace import waterfall_complete

    wfs = [w for w in obs.trace_report()
           if w["entry"] == "fabric.search"
           and w["status"] in ("ok", "degraded")]
    assert len(wfs) >= len(recorded)          # one per answered query
    complete = sum(1 for w in wfs if waterfall_complete(w))
    assert complete / len(wfs) >= 0.99, (complete, len(wfs))
    # hedge attempts recorded as sibling stages with a marked winner
    all_stages = [s for w in wfs for s in w["stages"]]
    assert any(s["status"] == "hedge_win" for s in all_stages)
    # the dead worker's mid-query failures are visible as failed/timeout
    # rpc attempts inside otherwise-complete waterfalls
    assert any(s["stage"] == "rpc"
               and s["status"] in ("failed", "timeout")
               for s in all_stages)


# ---------------------------------------------------------------------------
# graft-race regressions (ISSUE 7): the call-vs-kill lost-future race
# ---------------------------------------------------------------------------


class _KillingCounter:
    """Deterministic interleave seam: ``call()`` draws its request id
    from this counter BETWEEN its aliveness decision and the future's
    registration in the old code — firing ``kill()`` here reproduces
    exactly the window where the drain ran before the registration and
    the future was never resolved (its caller hung to the deadline)."""

    def __init__(self, group, rank):
        self.group = group
        self.rank = rank
        self.fired = False
        self.n = 1000

    def __iter__(self):
        return self

    def __next__(self):
        if not self.fired:
            self.fired = True
            self.group.kill(self.rank)
        self.n += 1
        return self.n


def test_localgroup_call_racing_kill_never_hangs_future():
    """Register-or-reject must be atomic against the kill drain: a
    future created by a call that razor-raced kill() is either rejected
    immediately or drained by _fail_pending — never left forever
    pending (the pre-fix behavior, which hung the router to its RPC
    deadline)."""
    group = procgroup.LocalGroup(2)
    try:
        group._req_ids = _KillingCounter(group, 0)
        fut = group.call(0, "ping", {})
        # resolved IMMEDIATELY — no waiting on a worker that will never
        # answer
        assert fut.done()
        with pytest.raises(Exception, match="not alive|killed"):
            fut.result(timeout=0)
        # the untouched worker still answers
        assert group.call(1, "ping", {}).result(timeout=10)["rank"] == 1
    finally:
        group.close()


def test_procgroup_fail_pending_blocks_later_calls():
    """The _ProcWorker.dead_reason seam: once a worker's futures were
    drained, a racing call() must see the verdict under the same lock
    and fail fast instead of registering into the void. (LocalGroup's
    spawn-free twin exercises the same contract above; here we pin the
    parent-side bookkeeping without paying a process spawn.)"""
    from concurrent.futures import Future

    w = procgroup._ProcWorker(0, None, None, None)
    assert w.dead_reason is None
    f1: Future = Future()
    with w.lock:
        w.pending[1] = f1
    # the drain marks the worker dead and fails everything registered
    pg = procgroup.ProcGroup.__new__(procgroup.ProcGroup)
    pg._fail_pending(w, "worker 0 killed")
    assert w.dead_reason == "worker 0 killed"
    assert f1.done() and f1.exception() is not None
    assert w.pending == {}


def test_fabric_threadsan_suite_verdict_zzz():
    """Suite-level ISSUE-7 acceptance (runs last in file order): the
    fabric tier's observed lock order stayed acyclic under sanitized
    locks, with zero hold-budget breaches."""
    s = lockwatch.stats()
    assert s["inversions"] == 0 and s["budget_breaches"] == 0, s
