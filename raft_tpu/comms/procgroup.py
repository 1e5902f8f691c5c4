"""Process-boundary worker groups for the multi-host serving fabric.

The reference's cluster tier is raft-dask: one OS process per GPU, an
index shard per worker, queries broadcast and per-worker top-ks merged
(PAPER.md; raft_dask/common/comms.py). This module is the TPU-repo
analog of that *process* layer — everything above one process boundary
and below the router (:mod:`raft_tpu.serve.fabric`):

* :class:`WorkerRuntime` — the worker-side state machine. It owns
  per-generation shard indexes (built with the repo's own
  ``brute_force``/``ivf_flat`` paths, warmed at prepare time) and
  answers a small RPC vocabulary: ``search`` / ``ping`` (data plane)
  and ``prepare`` / ``publish`` / ``abort`` / ``retire`` (the two-phase
  hot-swap control plane, docs/serving.md §10).
* :class:`ProcGroup` — N real ``multiprocessing`` (spawn) children,
  one :class:`WorkerRuntime` each, request/response queues per worker
  and a parent-side receiver thread matching responses to futures.
  This is the tier the SIGKILL / machine-loss failure modes live in.
* :class:`LocalGroup` — the in-process twin: the SAME runtime on
  daemon threads. Every router behavior (hedging, circuit breaking,
  two-phase swap, coverage) is exercised without process-spawn cost —
  the fabric counterpart of the CPU-mesh
  ``--xla_force_host_platform_device_count`` strategy the sharded
  tests use.

Failure semantics are *absences*, not exceptions: a dead worker never
answers (the router diagnoses the timeout), a dropped RPC loses only
its response, a slow worker answers late enough to trigger hedging.
The deterministic fault points come from
:func:`raft_tpu.resilience.faultinject.proc_action` /
:func:`~raft_tpu.resilience.faultinject.rpc_dropped`
(``dead@proc:R``, ``slow@proc:R*K``, ``drop@rpc:METHOD``).
"""

from __future__ import annotations

import itertools
import multiprocessing as mp
import os
import queue as _pyqueue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import numpy as np

from raft_tpu import obs
from raft_tpu.analysis import lockwatch
from raft_tpu.obs import trace as obs_trace
from raft_tpu.resilience import errors as _rerrors
from raft_tpu.resilience import faultinject

# sentinel statuses a worker's handle() can return instead of a reply
DIE = "__die__"       # hard-exit, no response (dead@proc)
DROP = "__drop__"     # swallow the response (drop@rpc)

# methods that count as the data plane: dead@proc / slow@proc faults
# fire here (a worker that died takes its control plane with it anyway,
# but arming death on control RPCs would kill workers during their own
# bootstrap prepare/publish — nondeterministic and not the failure mode
# under test)
DATA_PLANE = ("search", "ping")

_NO_GEN = "no_gen"


class RemoteWorkerError(RuntimeError):
    """A failure serialized back from a worker process. ``fault_kind``
    carries the worker-side :func:`raft_tpu.resilience.classify`
    verdict so the router's classification agrees with the worker's."""

    def __init__(self, msg: str, kind: Optional[str] = None):
        super().__init__(msg)
        if kind in _rerrors.KINDS:
            self.fault_kind = kind


def is_no_gen(exc: BaseException) -> bool:
    """True when a worker rejected an RPC because it does not hold the
    requested generation — a *stale* worker (missed a publish while
    partitioned), not a broken one; the router re-syncs instead of
    circuit-breaking."""
    return _NO_GEN in str(exc)


def _remote_error(payload: dict) -> RemoteWorkerError:
    return RemoteWorkerError(
        str(payload.get("error", "worker error")),
        kind=payload.get("kind"),
    )


# ---------------------------------------------------------------------------
# shard index construction/search — shared by workers and the tests'
# surviving-shard oracle (bitwise identity demands one code path)
# ---------------------------------------------------------------------------


def build_shard_entry(vectors: np.ndarray, offset: int,
                      algo: str = "brute_force") -> tuple:
    """Build one shard's index over ``vectors`` whose global row ids
    start at ``offset``. Returns an opaque entry for
    :func:`search_shard_entry`."""
    vectors = np.ascontiguousarray(vectors, dtype=np.float32)
    if algo == "ivf_flat":
        from raft_tpu.neighbors import ivf_flat

        params = ivf_flat.IndexParams(
            n_lists=max(1, min(16, vectors.shape[0] // 8)))
        idx = ivf_flat.build(params, vectors)
        # exhaustive probing: the fabric's correctness contract is that
        # a covered shard's answer is exact for that shard
        sp = ivf_flat.SearchParams(n_probes=idx.n_lists,
                                   compute_dtype="f32",
                                   local_recall_target=1.0)
        return ("ivf_flat", idx, sp, int(offset), int(vectors.shape[0]))
    from raft_tpu.neighbors import brute_force

    idx = brute_force.build(vectors)
    return ("brute_force", idx, None, int(offset), int(vectors.shape[0]))


def search_shard_entry(entry: tuple, q: np.ndarray,
                       k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Search one shard entry at ``k``, returning host ``(d, i)`` with
    GLOBAL row ids, column-padded to exactly ``k`` with the
    worst-possible sentinel (a shard smaller than ``k`` can only
    contribute its real rows)."""
    algo, idx, sp, offset, rows = entry
    kq = int(min(k, rows))
    if algo == "ivf_flat":
        from raft_tpu.neighbors import ivf_flat

        # graft-lint: allow-hand-wired-pipeline deliberate single-stage fast path: the fabric worker runs one per-shard scan; the router owns the multi-stage tail
        d, i = ivf_flat.search(sp, idx, q, kq)
    else:
        from raft_tpu.neighbors import brute_force

        # graft-lint: allow-hand-wired-pipeline deliberate single-stage fast path: exact per-shard scan, no pipeline to plan
        d, i = brute_force.search(idx, q, kq)
    d = np.asarray(d).astype(np.float32, copy=False)
    i = np.asarray(i).astype(np.int32, copy=False)
    i = np.where(i >= 0, i + np.int32(offset), np.int32(-1))
    if kq < k:
        pad = k - kq
        d = np.concatenate(
            [d, np.full((d.shape[0], pad), np.inf, np.float32)], axis=1)
        i = np.concatenate(
            [i, np.full((i.shape[0], pad), -1, np.int32)], axis=1)
    return d, i


# ---------------------------------------------------------------------------
# the worker-side state machine
# ---------------------------------------------------------------------------


class WorkerRuntime:
    """One fabric worker's state: per-generation shard indexes and the
    RPC vocabulary. Transport-agnostic — :class:`ProcGroup` runs one
    per child process, :class:`LocalGroup` one per daemon thread."""

    def __init__(self, rank: int, algo: str = "brute_force",
                 slow_s: float = 0.15, shared_registry: bool = False):
        self.rank = int(rank)
        self.algo = algo
        self.slow_s = float(slow_s)
        # True for LocalGroup's in-process twin: every worker thread
        # shares the ROUTER's metrics registry, so collect_metrics must
        # not hand the same registry back once per worker (the fleet
        # sum would multiply (n_workers+1)x)
        self.shared_registry = bool(shared_registry)
        self.current_gen = 0
        # gen_id -> {shard_id: entry}; staged holds prepared-not-published
        self.gens: Dict[int, Dict[int, tuple]] = {}
        self.staged: Dict[int, Dict[int, tuple]] = {}

    def handle(self, method: str, payload: Optional[dict]):
        """Dispatch one RPC. Returns ``("ok", reply)`` / ``("err",
        {"error", "kind"})``, or the :data:`DIE` / :data:`DROP`
        sentinels when an injected process fault demands an absence
        instead of an answer."""
        if method in DATA_PLANE:
            action = faultinject.proc_action(self.rank)
            if action == "die":
                return DIE, None
            if action == "slow":
                time.sleep(self.slow_s)
        if faultinject.rpc_dropped(method):
            return DROP, None
        try:
            faultinject.check(stage=f"fabric.{method}")
            fn = getattr(self, "_do_" + method, None)
            if fn is None:
                raise ValueError(f"unknown fabric RPC {method!r}")
            obs.counter("fabric.worker_rpcs_total", method=method)
            return "ok", fn(payload or {})
        except BaseException as e:  # noqa: BLE001 — classified here, re-classified by the router from the serialized kind
            kind = _rerrors.classify(e)
            return "err", {"error": f"{type(e).__name__}: {e}",
                           "kind": kind}

    # -- data plane ---------------------------------------------------------

    def _do_ping(self, payload: dict) -> dict:
        return {"rank": self.rank, "gen": self.current_gen,
                "gens": sorted(self.gens)}

    def _do_search(self, payload: dict) -> dict:
        gen = int(payload["gen"])
        shards = self.gens.get(gen)
        if shards is None:
            raise KeyError(
                f"{_NO_GEN}: worker {self.rank} does not hold "
                f"generation {gen} (has {sorted(self.gens)})")
        sid = int(payload["shard"])
        entry = shards.get(sid)
        if entry is None:
            raise KeyError(
                f"{_NO_GEN}: worker {self.rank} holds generation {gen} "
                f"but not shard {sid}")
        q = np.asarray(payload["q"])
        k = int(payload["k"])
        if not obs.enabled():
            d, i = search_shard_entry(entry, q, k)
            return {"gen": gen, "shard": sid, "d": d, "i": i}
        # graft-trace adoption (ISSUE 13): the RPC's trace context
        # becomes this thread's ambient context, so the spans the
        # search itself opens (brute_force/ivf_flat entry spans) carry
        # the SAME trace id the router minted — and a compact span
        # summary piggybacks on the reply, which is how the router
        # assembles the per-query waterfall without a second round
        # trip. No extra span is opened here: the entry span inside
        # search_shard_entry already names this work, and the serving
        # hot path pays for every per-RPC obs call in the loadgen A/B
        # overhead budget (FABRIC_r13.json). search_shard_entry
        # returns host numpy (it np.asarray's the device result), so
        # the measured ms is device-COMPLETE scan time, not dispatch
        # wall-clock.
        ctx = obs_trace.adopt(payload.get(obs_trace.WIRE_FIELD))
        with obs_trace.activate(ctx):
            t0 = time.perf_counter()
            d, i = search_shard_entry(entry, q, k)
            scan_ms = (time.perf_counter() - t0) * 1e3
        return {"gen": gen, "shard": sid, "d": d, "i": i,
                "spans": [{"name": "worker_scan", "worker": self.rank,
                           "shard": sid, "ms": round(scan_ms, 4),
                           "device_complete": True}]}

    def _do_collect_metrics(self, payload: dict) -> dict:
        """Fleet federation (ISSUE 13): hand the router this worker's
        whole metrics registry as a snapshot-shaped map. The router
        merges every worker's map under a ``worker`` label into one
        Prometheus exposition / JSON snapshot
        (:mod:`raft_tpu.obs.federation`). A shared-registry runtime
        (LocalGroup threads) answers with an EMPTY map and says so —
        its series already reach the router as its own registry, and
        returning them per worker would multiply every fleet sum."""
        if self.shared_registry:
            return {"rank": self.rank, "mode": obs.mode(),
                    "shared_registry": True, "metrics": {}}
        metrics = (obs.snapshot(runtime_gauges=False)["metrics"]
                   if obs.enabled() else {})
        return {"rank": self.rank, "mode": obs.mode(), "metrics": metrics}

    # -- two-phase swap control plane ---------------------------------------

    def _do_prepare(self, payload: dict) -> dict:
        gen = int(payload["gen"])
        built: Dict[int, tuple] = {}
        for sid, (vec, offset) in payload["shards"].items():
            vec = np.asarray(vec, dtype=np.float32)
            entry = build_shard_entry(vec, int(offset), self.algo)
            # warm: trace the search once now so publish -> first query
            # adds no compile on the serving path
            search_shard_entry(
                entry, np.zeros((1, vec.shape[1]), np.float32),
                int(min(4, vec.shape[0])))
            built[int(sid)] = entry
        self.staged[gen] = built
        return {"gen": gen, "shards": sorted(built)}

    def _do_publish(self, payload: dict) -> dict:
        gen = int(payload["gen"])
        if gen in self.gens:
            self.current_gen = max(self.current_gen, gen)
            return {"gen": gen}               # idempotent re-publish
        staged = self.staged.pop(gen, None)
        if staged is None:
            raise KeyError(
                f"{_NO_GEN}: worker {self.rank} has no staged "
                f"generation {gen} to publish")
        self.gens[gen] = staged
        # max, not assignment: a router resync of an OLDER generation
        # racing a newer publish must not regress the current pointer
        self.current_gen = max(self.current_gen, gen)
        return {"gen": gen}

    def _do_abort(self, payload: dict) -> dict:
        gen = int(payload["gen"])
        self.staged.pop(gen, None)
        return {"gen": gen}

    def _do_retire(self, payload: dict) -> dict:
        gen = int(payload["gen"])
        if gen != self.current_gen:
            self.gens.pop(gen, None)
        return {"gen": gen}

    def _do_set_faults(self, payload: dict) -> dict:
        faultinject.install(payload.get("spec") or None)
        return {"ok": True}


# ---------------------------------------------------------------------------
# multiprocessing transport
# ---------------------------------------------------------------------------


def _proc_worker_main(rank: int, req_q, resp_q, algo: str, slow_s: float,
                      fault_spec: Optional[str],
                      platform: Optional[str],
                      obs_mode: Optional[str] = None) -> None:
    """Child-process entry: run one :class:`WorkerRuntime` over the
    request queue until a ``stop``. A ``dead@proc`` fault hard-exits
    (``os._exit``) with no response — the honest SIGKILL analog."""
    if platform:
        # belt-and-braces: the parent already swapped the env before
        # spawn; a fabric worker runs on the host and must never reach
        # for the chip, which its parent may hold
        os.environ.setdefault("JAX_PLATFORMS", platform)
    if obs_mode is not None:
        # inherit the PARENT's resolved obs mode, not just the env: a
        # parent that called obs.set_mode("on") (tests, loadgen) would
        # otherwise spawn blind workers and the federation / worker-span
        # half of every trace would silently be empty
        obs.set_mode(obs_mode)
    if fault_spec:
        faultinject.install(fault_spec)
    rt = WorkerRuntime(rank, algo=algo, slow_s=slow_s)
    while True:
        msg = req_q.get()
        if msg is None:
            return
        req_id, method, payload = msg
        if method == "stop":
            return
        status, out = rt.handle(method, payload)
        if status is DIE:
            os._exit(17)
        if status is DROP:
            continue
        resp_q.put((req_id, status == "ok", out))


# one lock for the spawn-time environment swap (XLA_FLAGS /
# JAX_PLATFORMS are process-global; concurrent spawns must not
# interleave their save/restore)
_SPAWN_ENV_LOCK = lockwatch.make_lock("comms.spawn_env")


class _ProcWorker:
    __slots__ = ("rank", "proc", "req_q", "resp_q", "pending", "lock",
                 "stopping", "receiver", "dead_reason")

    def __init__(self, rank, proc, req_q, resp_q):
        self.rank = rank
        self.proc = proc
        self.req_q = req_q
        self.resp_q = resp_q
        self.pending: Dict[int, Future] = {}
        # graft-race sanitizer node "comms.procworker"
        self.lock = lockwatch.make_lock("comms.procworker")
        self.stopping = False
        # set (under `lock`) the moment the worker is declared dead and
        # its pending futures are drained: `call` checks it under the
        # SAME lock hold that registers the future, closing the window
        # where a registration racing the drain was never resolved
        self.dead_reason: Optional[str] = None
        self.receiver: Optional[threading.Thread] = None


class ProcGroup:
    """N fabric workers as real OS processes (``multiprocessing`` spawn
    context — fork after JAX initialization is unsafe).

    Parent-side API (shared with :class:`LocalGroup`):

    * :meth:`call` — fire an RPC, get a :class:`Future` (resolves with
      the reply payload, or raises the classified failure);
    * :meth:`alive` / :meth:`kill` / :meth:`restart` — process
      lifecycle (``kill`` is SIGKILL: the machine-loss drill);
    * :meth:`add_worker` / :meth:`retire` — dynamic admission and
      retirement (ISSUE 18): ranks are append-only and stable; a
      retired rank's slot stays (dead) so in-flight routing indexed by
      rank never dangles. Membership mutation is single-actor by
      contract — the control plane (graft-helm) or the owning test,
      never concurrent mutators;
    * :meth:`close` — stop everything.

    Children inherit the parent environment minus the
    ``--xla_force_host_platform_device_count`` test flag (a worker
    needs one device, not eight virtual ones) and with
    ``JAX_PLATFORMS`` pinned to ``platform`` (default ``cpu``: workers
    run on the host; a chip belongs to one process, and the parent may
    hold it. Pinning one worker to each chip is a feature of its own).
    """

    def __init__(self, n_workers: int, algo: str = "brute_force",
                 slow_s: float = 0.15, fault_spec: Optional[str] = None,
                 platform: Optional[str] = "cpu"):
        self.n_workers = int(n_workers)
        self.algo = algo
        self.slow_s = float(slow_s)
        self.fault_spec = fault_spec
        self.platform = platform
        self._ctx = mp.get_context("spawn")
        self._req_ids = itertools.count(1)
        # incarnation deaths per rank — the parent-side flap budget
        # (faultinject.respawned_spec): each child holds its own copy
        # of the fault plan, so the cross-incarnation charge lives here
        self._deaths: Dict[int, int] = {}
        self._workers: List[_ProcWorker] = [
            self._spawn(r, fault_spec) for r in range(self.n_workers)
        ]

    # -- lifecycle ----------------------------------------------------------

    def ranks(self) -> List[int]:
        """All member ranks ever admitted (retired/dead slots included —
        liveness is :meth:`alive`'s question)."""
        return list(range(len(self._workers)))

    def _spawn(self, rank: int, fault_spec: Optional[str]) -> _ProcWorker:
        req_q = self._ctx.Queue()
        resp_q = self._ctx.Queue()
        proc = self._ctx.Process(
            target=_proc_worker_main,
            args=(rank, req_q, resp_q, self.algo, self.slow_s,
                  fault_spec, self.platform, obs.mode()),
            daemon=True,
            name=f"raft-tpu-fabric-w{rank}",
        )
        with _SPAWN_ENV_LOCK:
            saved = {k: os.environ.get(k)
                     for k in ("XLA_FLAGS", "JAX_PLATFORMS")}
            flags = " ".join(
                tok for tok in (saved["XLA_FLAGS"] or "").split()
                if "xla_force_host_platform_device_count" not in tok)
            if flags:
                os.environ["XLA_FLAGS"] = flags
            else:
                os.environ.pop("XLA_FLAGS", None)
            if self.platform:
                os.environ["JAX_PLATFORMS"] = self.platform
            try:
                proc.start()
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
        w = _ProcWorker(rank, proc, req_q, resp_q)
        w.receiver = threading.Thread(
            target=self._recv_loop, args=(w,), daemon=True,
            name=f"raft-tpu-fabric-recv-{rank}")
        w.receiver.start()
        return w

    def _recv_loop(self, w: _ProcWorker) -> None:
        while not w.stopping:
            try:
                msg = w.resp_q.get(timeout=0.1)
            except _pyqueue.Empty:
                if not w.proc.is_alive():
                    # drain what the child flushed before dying, then
                    # fail everything still outstanding
                    while True:
                        try:
                            self._resolve(w, w.resp_q.get_nowait())
                        except _pyqueue.Empty:
                            break
                    self._fail_pending(
                        w, f"fabric worker {w.rank} process died")
                    return
                continue
            except (OSError, EOFError, ValueError):
                # queue torn down under us (close/kill)
                self._fail_pending(
                    w, f"fabric worker {w.rank} channel closed")
                return
            self._resolve(w, msg)

    def _resolve(self, w: _ProcWorker, msg) -> None:
        req_id, ok, payload = msg
        with w.lock:
            fut = w.pending.pop(req_id, None)
        if fut is None or fut.done():
            return                      # hedge loser / timed-out caller
        if ok:
            fut.set_result(payload)
        else:
            fut.set_exception(_remote_error(payload))

    def _fail_pending(self, w: _ProcWorker, msg: str) -> None:
        with w.lock:
            w.dead_reason = msg
            pending = list(w.pending.values())
            w.pending.clear()
        for fut in pending:
            if not fut.done():
                fut.set_exception(_rerrors.DeadBackendError(msg))

    # -- the RPC surface ----------------------------------------------------

    def call(self, rank: int, method: str,
             payload: Optional[dict] = None) -> Future:
        w = self._workers[rank]
        fut: Future = Future()
        req_id = next(self._req_ids)
        fut._raft_req_id = req_id
        # register-or-reject ATOMICALLY against _fail_pending: the old
        # unlocked aliveness check let a kill/close land between the
        # check and the registration — the drain saw an empty pending
        # map, the future was registered after it, and nobody ever
        # resolved it (the caller hung to its timeout)
        with w.lock:
            dead = w.dead_reason
            if dead is None and (w.stopping or not w.proc.is_alive()):
                dead = f"fabric worker {rank} process is not alive"
            if dead is None:
                w.pending[req_id] = fut
        if dead is not None:
            fut.set_exception(_rerrors.DeadBackendError(dead))
            return fut
        try:
            w.req_q.put((req_id, method, payload))
        except BaseException as e:  # noqa: BLE001 — classified: a torn queue is the dead-worker signal
            _rerrors.classify(e)
            with w.lock:
                w.pending.pop(req_id, None)
            if not fut.done():
                fut.set_exception(_rerrors.DeadBackendError(
                    f"fabric worker {rank} request channel broken: {e}"))
        return fut

    def forget(self, rank: int, fut: Future) -> None:
        """Abandon one outstanding call: drop its pending entry so a
        response that never arrives (dropped RPC, hung-but-alive
        worker) cannot pin the Future + payload until process death. A
        late response for a forgotten id is discarded by
        :meth:`_resolve`."""
        req_id = getattr(fut, "_raft_req_id", None)
        if req_id is None:
            return
        w = self._workers[rank]
        with w.lock:
            w.pending.pop(req_id, None)

    def alive(self, rank: int) -> bool:
        w = self._workers[rank]
        return not w.stopping and w.proc.is_alive()

    def kill(self, rank: int) -> None:
        """SIGKILL the worker — the machine-loss drill. Outstanding
        futures fail with :class:`DeadBackendError`."""
        w = self._workers[rank]
        w.proc.kill()
        w.proc.join(timeout=10.0)
        self._fail_pending(w, f"fabric worker {rank} killed")

    def restart(self, rank: int,
                fault_spec: Optional[str] = None,
                inherit_faults: bool = False) -> None:
        """Respawn ``rank`` as a fresh process with NO index state (the
        router must re-sync it). The fresh incarnation installs no
        fault plan unless one is given explicitly — or
        ``inherit_faults=True``, which installs the spawn-time plan
        rewritten by :func:`faultinject.respawned_spec` (flap budgets
        charged one death per prior incarnation, dead specs kept
        permanent): the control plane's respawn path, where the drills
        need the schedule to survive the respawn it provoked."""
        old = self._workers[rank]
        old.stopping = True
        if old.proc.is_alive():
            old.proc.kill()
        old.proc.join(timeout=10.0)
        self._fail_pending(old, f"fabric worker {rank} restarted")
        self._deaths[rank] = self._deaths.get(rank, 0) + 1
        if fault_spec is None and inherit_faults:
            fault_spec = faultinject.respawned_spec(
                self.fault_spec, rank, self._deaths[rank])
        self._workers[rank] = self._spawn(rank, fault_spec)

    def add_worker(self, fault_spec: Optional[str] = None) -> int:
        """Admit one new worker (autoscale-up): spawn it under the next
        rank and return that rank. The newcomer owns no shards until a
        generation that places some on it is published
        (``Fabric.rebalance``)."""
        rank = len(self._workers)
        self._workers.append(self._spawn(rank, fault_spec))
        self.n_workers = len(self._workers)
        return rank

    def retire(self, rank: int, timeout_s: float = 10.0) -> None:
        """Retire one worker for good (autoscale-down): graceful stop,
        SIGKILL past the timeout. The rank slot stays, dead — ranks are
        stable for the life of the group."""
        w = self._workers[rank]
        w.stopping = True
        try:
            w.req_q.put((0, "stop", None))
        except BaseException as e:  # noqa: BLE001 — classified: retiring an already-dead queue
            _rerrors.classify(e)
        w.proc.join(timeout=timeout_s)
        if w.proc.is_alive():
            w.proc.kill()
            w.proc.join(timeout=5.0)
        self._fail_pending(w, f"fabric worker {rank} retired")

    def close(self, timeout_s: float = 10.0) -> None:
        for w in self._workers:
            w.stopping = True
            try:
                w.req_q.put((0, "stop", None))
            except BaseException as e:  # noqa: BLE001 — classified: shutdown of an already-dead queue
                _rerrors.classify(e)
        deadline = time.monotonic() + timeout_s
        for w in self._workers:
            w.proc.join(timeout=max(deadline - time.monotonic(), 0.1))
            if w.proc.is_alive():
                w.proc.kill()
                w.proc.join(timeout=5.0)
            self._fail_pending(w, f"fabric worker {w.rank} closed")


# ---------------------------------------------------------------------------
# in-process transport
# ---------------------------------------------------------------------------


class _LocalWorker:
    __slots__ = ("rank", "runtime", "q", "pending", "lock", "dead",
                 "thread")

    def __init__(self, rank, runtime):
        self.rank = rank
        self.runtime = runtime
        self.q: "_pyqueue.Queue" = _pyqueue.Queue()
        self.pending: Dict[int, Future] = {}
        # graft-race sanitizer node "comms.localworker"; `dead` is
        # written under it (see _fail_pending) so `call` can
        # register-or-reject atomically against a concurrent kill
        self.lock = lockwatch.make_lock("comms.localworker")
        self.dead = False
        self.thread: Optional[threading.Thread] = None


class LocalGroup:
    """The in-process twin of :class:`ProcGroup`: the same
    :class:`WorkerRuntime` per worker, on daemon threads. Identical
    parent-side semantics — a "died" worker stops answering forever
    (:meth:`alive` goes False, outstanding futures fail) rather than
    raising, so every router failure path is exercised without spawn
    cost. Fault plans are the AMBIENT :mod:`faultinject` plan (one
    process, one plan), matching each runtime by its rank."""

    def __init__(self, n_workers: int, algo: str = "brute_force",
                 slow_s: float = 0.05, fault_spec: Optional[str] = None,
                 platform: Optional[str] = None):
        del platform                    # one process, one platform
        if fault_spec:
            faultinject.install(fault_spec)
        self.n_workers = int(n_workers)
        self.algo = algo
        self.slow_s = float(slow_s)
        self._req_ids = itertools.count(1)
        self._workers: List[_LocalWorker] = [
            self._spawn(r) for r in range(self.n_workers)
        ]

    def ranks(self) -> List[int]:
        return list(range(len(self._workers)))

    def _spawn(self, rank: int) -> _LocalWorker:
        w = _LocalWorker(rank, WorkerRuntime(rank, algo=self.algo,
                                             slow_s=self.slow_s,
                                             shared_registry=True))
        w.thread = threading.Thread(
            target=self._loop, args=(w,), daemon=True,
            name=f"raft-tpu-fabric-local-w{rank}")
        w.thread.start()
        return w

    def _loop(self, w: _LocalWorker) -> None:
        while True:
            msg = w.q.get()
            if msg is None:
                return
            req_id, method, payload = msg
            with w.lock:
                dead = w.dead           # guarded read: kill/close write
                #                         it under the same lock
            if dead:
                continue                # the dead answer nothing, ever
            status, out = w.runtime.handle(method, payload)
            if status is DIE:
                self._fail_pending(
                    w, f"fabric worker {w.rank} died (injected)")
                continue
            if status is DROP:
                with w.lock:
                    w.pending.pop(req_id, None)
                continue
            with w.lock:
                fut = w.pending.pop(req_id, None)
            if fut is None or fut.done():
                continue
            if status == "ok":
                fut.set_result(out)
            else:
                fut.set_exception(_remote_error(out))

    def _fail_pending(self, w: _LocalWorker, msg: str) -> None:
        """Declare ``w`` dead and drain its futures — `dead` flips under
        the SAME lock hold that empties ``pending``, so `call`'s
        register-or-reject can never interleave between the two."""
        with w.lock:
            w.dead = True
            pending = list(w.pending.values())
            w.pending.clear()
        for fut in pending:
            if not fut.done():
                fut.set_exception(_rerrors.DeadBackendError(msg))

    def call(self, rank: int, method: str,
             payload: Optional[dict] = None) -> Future:
        w = self._workers[rank]
        fut: Future = Future()
        req_id = next(self._req_ids)
        fut._raft_req_id = req_id
        # atomic register-or-reject (see _ProcWorker.dead_reason): the
        # old unlocked `if w.dead` check raced kill() — a future
        # registered after the drain was never resolved and its caller
        # hung to the RPC deadline
        with w.lock:
            dead = w.dead
            if not dead:
                w.pending[req_id] = fut
        if dead:
            fut.set_exception(_rerrors.DeadBackendError(
                f"fabric worker {rank} is not alive"))
            return fut
        w.q.put((req_id, method, payload))
        return fut

    def forget(self, rank: int, fut: Future) -> None:
        req_id = getattr(fut, "_raft_req_id", None)
        if req_id is None:
            return
        w = self._workers[rank]
        with w.lock:
            w.pending.pop(req_id, None)

    def alive(self, rank: int) -> bool:
        return not self._workers[rank].dead

    def kill(self, rank: int) -> None:
        # _fail_pending flips `dead` and drains atomically
        self._fail_pending(self._workers[rank],
                           f"fabric worker {rank} killed")

    def restart(self, rank: int,
                fault_spec: Optional[str] = None,
                inherit_faults: bool = False) -> None:
        # inherit_faults is a no-op here by design: one process, one
        # AMBIENT plan — a respawned local runtime sees the same specs,
        # with flap budgets already decremented by the deaths they
        # caused (the cross-incarnation charge ProcGroup has to
        # replicate parent-side)
        del inherit_faults
        old = self._workers[rank]
        self._fail_pending(old, f"fabric worker {rank} restarted")
        old.q.put(None)                 # let the old thread exit
        if fault_spec:
            faultinject.install(fault_spec)
        self._workers[rank] = self._spawn(rank)

    def add_worker(self, fault_spec: Optional[str] = None) -> int:
        if fault_spec:
            faultinject.install(fault_spec)
        rank = len(self._workers)
        self._workers.append(self._spawn(rank))
        self.n_workers = len(self._workers)
        return rank

    def retire(self, rank: int, timeout_s: float = 10.0) -> None:
        del timeout_s
        w = self._workers[rank]
        self._fail_pending(w, f"fabric worker {rank} retired")
        w.q.put(None)

    def close(self, timeout_s: float = 10.0) -> None:
        for w in self._workers:
            self._fail_pending(w, f"fabric worker {w.rank} closed")
            w.q.put(None)
        for w in self._workers:
            if w.thread is not None:
                w.thread.join(timeout=timeout_s)
