"""Dynamic micro-batching: the request queue and the bucket ladder.

The TPU serving problem (docs/serving.md §2): XLA compiles one program
per input *shape*, so a query stream with arbitrary row counts would
retrace constantly — the exact failure mode the GL007 recompile audit
gates against. The fix is the FusionANNS/TPU-KNN serving shape: requests
land in a thread-safe queue, a dispatcher coalesces whatever is pending
into a batch padded up to a **fixed bucket ladder** (powers of two up to
``max_batch_rows``), and every bucket × k-rung combination is traced
once at warmup — steady-state serving then never compiles.

Pieces here:

* :func:`bucket_ladder` / :func:`choose_bucket` — the ladder and the
  measured bucket choice (``tuning.choose("serve_bucket", ...)``: a
  dispatch table can prefer padding further up the ladder when the
  larger matmul measures faster than the smaller one plus a second
  dispatch);
* :class:`Overloaded` — the bounded-queue admission rejection,
  classified through ``resilience.classify`` (``queue_full`` is
  transient — the client's correct move is backoff-and-retry;
  ``closed`` is fatal — the server can never accept again);
* :class:`MicroBatcher` — the queue + linger/drain dispatcher loop with
  ``max_wait_ms`` and ``max_batch_rows`` knobs.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from raft_tpu import obs
from raft_tpu.analysis import lockwatch
from raft_tpu.obs import trace as obs_trace
from raft_tpu.resilience import errors as _rerrors
from raft_tpu.utils.math import next_pow2

# batch_fill_ratio histogram edges: rows / bucket after padding — the
# shared unit-interval preset (ISSUE 19), so fill ratios land on the
# same [0,1] resolution as the recall estimates
FILL_BUCKETS: Tuple[float, ...] = obs.UNIT_BUCKETS


class Overloaded(RuntimeError):
    """Admission rejection. ``reason="queue_full"`` (bounded queue),
    ``reason="not_ready"`` (first generation still building/warming),
    ``reason="quota"`` (per-index admission quota, docs/serving.md §13),
    and ``reason="deadline"`` (the request's SLO deadline cannot be met
    — shed instead of served late) carry ``fault_kind = "transient"``
    so :func:`raft_tpu.resilience.classify` files them with the
    retryable kinds — all are backoff-and-retry (or re-budget) signals,
    not errors in the request. ``reason="closed"`` is the opposite
    contract: the server can never accept again, so it classifies
    ``fatal`` and resilience-aware clients fail fast instead of
    retrying a shutdown forever."""

    def __init__(self, msg: str, reason: str = "queue_full"):
        super().__init__(msg)
        self.reason = reason
        self.fault_kind = (_rerrors.FATAL if reason == "closed"
                           else _rerrors.TRANSIENT)


def bucket_ladder(max_rows: int) -> Tuple[int, ...]:
    """The fixed bucket ladder: powers of two ``1..next_pow2(max_rows)``.

    Every batch dispatches at exactly one of these row counts, so the
    set of traced shapes is finite and warmable."""
    top = next_pow2(max(int(max_rows), 1))
    out, b = [], 1
    while b <= top:
        out.append(b)
        b <<= 1
    return tuple(out)


def choose_bucket(ladder: Sequence[int], rows: int,
                  ceiling: Optional[int] = None) -> int:
    """Pick the dispatch bucket for ``rows`` pending rows.

    The analytic fallback is the smallest ladder rung >= rows; the
    choice is registered with ``tuning/`` under op ``serve_bucket`` so a
    measured table can prefer the next rung up (on a TPU the 2x-wider
    matmul can cost the same wall-clock, and the wider trace doubles as
    headroom for the next batch — a TPU-shaped PROJECTION:
    ``tables/cpu.json`` carries no ``serve_bucket`` entries, so the
    fallback always wins until ``capture_dispatch_tables.py`` runs on a
    chip).
    ``ceiling`` (the OOM-downshifted max) caps the answer except when
    a single oversized request needs the bigger rung anyway — the
    dispatcher's splitter handles that.
    """
    from raft_tpu import tuning

    rows = max(int(rows), 1)
    eligible = [b for b in ladder if b >= rows]
    if not eligible:
        return ladder[-1]
    if ceiling is not None:
        capped = [b for b in eligible if b <= ceiling]
        eligible = capped or eligible[:1]
    fallback = eligible[0]
    cands = [str(b) for b in eligible[:2]]   # this rung or one up
    w = tuning.choose("serve_bucket", {"rows_bucket": fallback},
                      cands, str(fallback))
    try:
        return int(w)
    except (TypeError, ValueError):
        return fallback


@dataclasses.dataclass
class Request:
    """One queued ``submit`` call: ``rows`` query rows answered together."""

    queries: np.ndarray           # [rows, dim] host array
    k: int
    prefilter: object             # user filter (batch-grouping key)
    future: Future
    t_enqueue: float = 0.0
    # SLO deadline as an ABSOLUTE time.monotonic() value (ISSUE 14):
    # deadline-carrying requests ride the priority lane, skip linger
    # when their slack drops under the measured service estimate, and
    # are shed/downshifted at dispatch when they would certainly miss
    deadline: Optional[float] = None
    # graft-trace context (ISSUE 13): minted at submit, carried by the
    # batch as a span LINK (one batch serves many traces), completed at
    # delivery — None when obs is off
    trace: Optional[obs_trace.TraceContext] = None
    # graft-gauge shadow payload (ISSUE 19): the quality monitor's
    # sample record (pinned generation + the SERVED ids to score
    # against the oracle re-run). Non-None marks a shadow request —
    # the future is a placeholder nobody awaits.
    shadow: object = None

    @property
    def rows(self) -> int:
        return int(self.queries.shape[0])


@dataclasses.dataclass
class Batch:
    """One coalesced dispatch unit: requests sharing a user prefilter,
    padded up to ``bucket`` rows."""

    requests: List[Request]
    rows: int
    bucket: int
    prefilter: object
    seq: int = 0
    # the head request's formation wait — the linger attribution every
    # member trace's batch stage carries
    linger_ms: float = 0.0
    # the probe rung this batch dispatches at (ISSUE 14): None = the
    # non-adaptive/exhaustive path; set by the engine's split-by-rung
    # partition (and by warmup, which forces each ladder rung once)
    rung: Optional[int] = None
    # graft-gauge (ISSUE 19): True for a shadow-oracle batch drained
    # from the best-effort lane — the engine routes it to the quality
    # monitor's exhaustive re-run instead of the serving path
    shadow: bool = False

    @property
    def k_max(self) -> int:
        return max(r.k for r in self.requests)


class MicroBatcher:
    """Thread-safe request queue + coalescing dispatcher.

    ``submit`` enqueues and returns immediately (backpressure: a full
    queue raises :class:`Overloaded`); a daemon dispatcher thread
    lingers up to ``max_wait_ms`` for the queue to fill toward the
    bucket ceiling, drains a filter-homogeneous run of requests, and
    hands the padded :class:`Batch` to ``dispatch_fn`` (the engine's
    resilience-wrapped search). The ceiling is dynamic: the engine's OOM
    ladder calls :meth:`set_ceiling` to downshift it.
    """

    def __init__(
        self,
        dispatch_fn: Callable[[Batch], None],
        *,
        max_batch_rows: int = 256,
        max_wait_ms: float = 2.0,
        max_queue_rows: int = 4096,
        shadow_queue_rows: int = 256,
        name: str = "default",
    ):
        self.ladder = bucket_ladder(max_batch_rows)
        self.max_batch_rows = self.ladder[-1]
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.max_queue_rows = int(max_queue_rows)
        self.name = name
        self._dispatch = dispatch_fn
        self._q: "collections.deque[Request]" = collections.deque()
        # the priority lane (ISSUE 14): deadline-carrying requests queue
        # here and are drained ahead of the normal lane — an SLO-bound
        # request must not wait behind a backlog of best-effort work
        self._qp: "collections.deque[Request]" = collections.deque()
        # the best-effort shadow lane (ISSUE 19): quality-monitor
        # oracle re-runs queue here and drain ONLY when both live lanes
        # are empty. Its rows never count against ``max_queue_rows``
        # (a full shadow lane must not backpressure live admission) —
        # it is bounded separately by ``shadow_queue_rows`` with
        # drop-oldest overflow, surfaced to the caller so generation
        # pins ride out with the dropped samples.
        self._qs: "collections.deque[Request]" = collections.deque()
        self._shadow_cap = int(shadow_queue_rows)
        self._shadow_rows = 0
        # per-bucket service-time samples (ms), fed back by the engine
        # after each dispatch; the deadline-aware linger reads their p95
        # (falling back to the dispatch table's serve_service medians —
        # never a hardcoded guess)
        self._svc: dict = {}
        self._pending_rows = 0
        self._ceiling = self.max_batch_rows
        self._closed = False
        self._seq = 0
        # graft-race sanitizer node "serve.batcher" (RAFT_TPU_THREADSAN)
        self._lock = lockwatch.make_lock("serve.batcher")
        self._cond = threading.Condition(self._lock)
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"raft-tpu-serve-batcher-{name}",
        )
        self._thread.start()

    # -- admission ---------------------------------------------------------

    def submit(self, queries: np.ndarray, k: int,
               prefilter=None, deadline: Optional[float] = None) -> Future:
        """Enqueue ``queries`` ([rows, dim]) at ``k``; returns the Future
        the dispatcher resolves with ``(distances, ids)`` host arrays.
        ``deadline`` (absolute ``time.monotonic()``) routes the request
        through the priority lane with deadline-aware linger.

        Raises :class:`Overloaded` (classified transient) when admission
        would push the queue past ``max_queue_rows`` — bounded queues
        are the backpressure contract: reject at the door, never grow
        an unbounded latency tail."""
        with obs.span("serve.submit", index=self.name,
                      rows=int(queries.shape[0]), k=int(k)):
            req = Request(queries=queries, k=int(k), prefilter=prefilter,
                          future=Future(), deadline=deadline)
            # the serving entry mints the trace (ISSUE 13): the id is
            # minted BEFORE admission so a rejection still completes a
            # (tiny) waterfall naming why the query died at the door
            req.trace = obs_trace.start_trace(
                "serve.submit", index=self.name, rows=req.rows,
                k=int(k))
            if req.rows > self.max_batch_rows:
                obs_trace.finish(req.trace, status="rejected",
                                 reason="oversized")
                raise ValueError(
                    f"request rows={req.rows} exceeds max_batch_rows="
                    f"{self.max_batch_rows}; split the query block or "
                    "raise ServeParams.max_batch_rows"
                )
            reason = None
            with self._cond:
                if self._closed or \
                        self._pending_rows + req.rows > self.max_queue_rows:
                    reason = "closed" if self._closed else "queue_full"
                    pending = self._pending_rows
                else:
                    req.t_enqueue = time.monotonic()
                    (self._qp if req.deadline is not None
                     else self._q).append(req)
                    self._pending_rows += req.rows
                    depth = self._pending_rows
                    self._cond.notify_all()
            # bookkeeping OUTSIDE the admission lock: classify() in
            # flight mode synchronously dumps the 4096-event ring to
            # disk for the fatal `closed` rejection — doing that under
            # _cond would stall every concurrent submit and the
            # dispatcher for the dump's duration
            if reason is not None:
                obs.counter("serve.rejects_total", index=self.name,
                            reason=reason)
                obs_trace.finish(req.trace, status="rejected",
                                 reason=reason)
                exc = Overloaded(
                    f"serve[{self.name}]: {reason} "
                    f"(pending={pending} rows, "
                    f"max_queue_rows={self.max_queue_rows})",
                    reason=reason,
                )
                _rerrors.classify(exc)   # file with errors_total/flight
                raise exc
            obs.gauge("serve.queue_depth", depth, index=self.name)
            obs.counter("serve.requests_total", index=self.name)
            return req.future

    # graft-lint: allow-unspanned-entry shadow lane is off the latency path by contract; its only telemetry is the serve.shadow_* counters
    def submit_shadow(self, req: Request) -> List[Request]:
        """Enqueue a shadow-oracle sample on the best-effort lane
        (ISSUE 19). Never raises and never backpressures live traffic:
        past ``shadow_queue_rows`` the OLDEST queued samples are
        dropped to make room (fresh samples estimate current quality;
        stale ones estimate history). Returns the dropped requests —
        ``req`` itself when the batcher is closed or the sample alone
        exceeds the cap — so the caller can release their generation
        pins and count the drops."""
        dropped: List[Request] = []
        with self._cond:
            if self._closed or req.rows > self._shadow_cap:
                return [req]
            while self._qs and \
                    self._shadow_rows + req.rows > self._shadow_cap:
                old = self._qs.popleft()
                self._shadow_rows -= old.rows
                dropped.append(old)
            self._qs.append(req)
            self._shadow_rows += req.rows
            self._cond.notify_all()
        return dropped

    def drain_shadow(self) -> List[Request]:
        """Remove and return every queued shadow sample (close-time
        cleanup: the caller releases their generation pins)."""
        with self._cond:
            leftovers = list(self._qs)
            self._qs.clear()
            self._shadow_rows = 0
        return leftovers

    # -- knobs -------------------------------------------------------------

    @property
    def ceiling(self) -> int:
        return self._ceiling

    def set_ceiling(self, rows: int) -> None:
        """Set the dispatch bucket ceiling (clamped to the ladder)."""
        with self._cond:
            self._ceiling = max(min(int(rows), self.max_batch_rows),
                                self.ladder[0])
            obs.gauge("serve.bucket_ceiling", self._ceiling,
                      index=self.name)

    def lower_ceiling(self, rows: int) -> int:
        """Monotonically clamp the ceiling DOWN to ``rows`` (never up),
        atomically. The OOM ladder's downshift used to read ``ceiling``
        then call :meth:`set_ceiling` with the min — two concurrent OOM
        batches could interleave the read-modify-write and the later,
        SHALLOWER downshift would raise the ceiling back over the
        deeper one (a GL010/GL011 lost update). Returns the new
        ceiling."""
        with self._cond:
            self._ceiling = max(min(self._ceiling, int(rows)),
                                self.ladder[0])
            obs.gauge("serve.bucket_ceiling", self._ceiling,
                      index=self.name)
            return self._ceiling

    def depth_rows(self) -> int:
        with self._lock:
            return self._pending_rows

    # -- service-time feedback (the deadline slack test's estimate) --------

    def note_service_ms(self, bucket: int, ms: float,
                        rung: Optional[int] = None) -> None:
        """Record one dispatch's service time for the (bucket, rung)
        shape (called by the engine after every batch); the
        deadline-aware linger and the engine's shed/downshift decisions
        read the p95. Keyed per RUNG on purpose: an exhaustive-rung
        batch costs a multiple of a floor-rung one, and a pooled
        estimate would neither shed the former nor spare the latter.

        A shape's FIRST sample is discarded: without warmup it is the
        XLA compile, a 10-100x outlier that would poison the tail
        estimate and shed healthy requests until the ring ages it
        out."""
        with self._lock:
            ring = self._svc.get((int(bucket), rung))
            if ring is None:
                self._svc[(int(bucket), rung)] = collections.deque(
                    maxlen=64)
                return
            ring.append(float(ms))

    def service_p95_ms(self, bucket: int,
                       rung: Optional[int] = None) -> float:
        """The (bucket, rung) shape's measured p95 service time (ms).
        Falls back: exact-shape samples -> the bucket's samples across
        all rungs -> the dispatch table's captured ``serve_service``
        median (scripts/capture_dispatch_tables.py --ops
        serve_service) -> the deadline headroom budget — never a
        hardcoded guess."""
        with self._lock:
            xs, pooled = self._svc_samples_locked(bucket, rung)
        return self._p95_from(xs, pooled, bucket, rung)

    def _service_p95_locked(self, bucket: int,
                            rung: Optional[int] = None) -> float:
        """:meth:`service_p95_ms` for callers already holding ``_cond``
        (the dispatcher's linger) — ``_cond`` wraps the SAME lock, and
        re-acquiring it from the public entry deadlocks the loop."""
        xs, pooled = self._svc_samples_locked(bucket, rung)
        return self._p95_from(xs, pooled, bucket, rung)

    def _svc_samples_locked(self, bucket: int, rung: Optional[int]):
        xs = sorted(self._svc.get((int(bucket), rung), ()))
        pooled = sorted(
            v for (b, _r), ring in self._svc.items()
            if b == int(bucket) for v in ring)
        return xs, pooled

    @staticmethod
    def _p95_from(xs, pooled, bucket: int, rung: Optional[int]) -> float:
        from raft_tpu.serve import adaptive as _adaptive

        if len(xs) >= 8:
            return xs[min(len(xs) - 1, int(0.95 * len(xs)))]
        # pooled LIVE samples of this index beat the dispatch table's
        # capture (measured on a fixed toy index, keyed only by
        # (bucket, rung)) — a much bigger served index would otherwise
        # be gated by the toy's far smaller medians and admit work
        # that certainly misses its SLO
        if len(pooled) >= 8:
            return pooled[min(len(pooled) - 1, int(0.95 * len(pooled)))]
        est = _adaptive.service_estimate_ms(bucket, rung)
        if est is not None:
            return est
        if pooled:
            return pooled[-1]
        return _adaptive.deadline_headroom_ms()

    # -- lifecycle ---------------------------------------------------------

    def close(self, timeout_s: float = 30.0) -> None:
        """Stop admissions, drain the queue through the dispatcher, join."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=timeout_s)

    # -- the dispatcher loop ----------------------------------------------

    def _loop(self) -> None:
        while True:
            batch = self._next_batch()
            if batch is None:
                return
            try:
                self._dispatch(batch)
            except BaseException as e:  # noqa: BLE001 — classified by the engine; the loop must survive to fail ONLY this batch
                for r in batch.requests:
                    obs_trace.finish(r.trace, status="error",
                                     error=type(e).__name__)
                    if not r.future.done():
                        r.future.set_exception(e)

    def _next_batch(self) -> Optional[Batch]:
        with self._cond:
            while True:
                while not self._q and not self._qp and not self._qs \
                        and not self._closed:
                    self._cond.wait(timeout=0.1)
                lane = self._qp if self._qp else self._q
                if not lane:
                    if self._closed:
                        # leftover shadow samples are NOT dispatched on
                        # close — drain_shadow() hands them back so the
                        # owner can release their pins
                        return None              # closed and drained
                    if self._qs:
                        # both live lanes idle: drain one shadow batch
                        # immediately, no linger — best-effort work
                        # must never hold the lock waiting for more
                        # best-effort work while live requests queue
                        return self._drain_shadow_locked()
                    continue                     # spurious wake
                # linger: let the queue fill toward the ceiling, but
                # never hold the head request past max_wait_ms — and
                # never past a deadline request's slack: when the head's
                # remaining budget minus the measured service estimate
                # (p95 at the ceiling bucket, plus the headroom budget)
                # is already spent, it skips linger entirely
                head = lane[0]
                deadline = head.t_enqueue + self.max_wait_s
                if head.deadline is not None:
                    from raft_tpu.serve import adaptive as _adaptive

                    # reserve TWICE the headroom the dispatch gate
                    # keeps: a request released at exactly the gate's
                    # margin would be sheddable by the time it drains
                    est_s = (self._service_p95_locked(self._ceiling)
                             + 2 * _adaptive.deadline_headroom_ms()) / 1e3
                    deadline = min(deadline, head.deadline - est_s)
                while (not self._closed and lane
                       and self._head_run_rows_locked(lane)
                       < self._ceiling):
                    if lane is self._q and self._qp:
                        break        # a priority request arrived: yield
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
                lane = self._qp if self._qp else self._q
                if not lane:                     # close raced the linger
                    continue
                return self._drain_locked(lane)

    def _head_run_rows_locked(self, lane=None) -> int:
        """Rows in the longest filter-homogeneous run at the queue head
        (only those can coalesce into one batch); caller holds
        ``_cond``."""
        if lane is None:
            lane = self._qp if self._qp else self._q
        if not lane:
            return 0
        key = id(lane[0].prefilter) if lane[0].prefilter is not None \
            else None
        rows = 0
        for r in lane:
            rk = id(r.prefilter) if r.prefilter is not None else None
            if rk != key:
                break
            rows += r.rows
            if rows >= self._ceiling:
                # the linger loop only compares against the ceiling, so
                # scanning past it is wasted work done under the shared
                # admission lock on every dispatcher wake — bound each
                # scan at the ceiling instead of the full backlog
                break
        return rows

    def _drain_locked(self, lane=None) -> Batch:
        if lane is None:
            lane = self._qp if self._qp else self._q
        head = lane[0]
        key = id(head.prefilter) if head.prefilter is not None else None
        cap = max(self._ceiling, head.rows)   # oversized head still goes
        taken: List[Request] = []
        rows = 0
        while lane:
            r = lane[0]
            rk = id(r.prefilter) if r.prefilter is not None else None
            if rk != key or (taken and rows + r.rows > cap):
                break
            taken.append(lane.popleft())
            rows += r.rows
        self._pending_rows -= rows
        obs.gauge("serve.queue_depth", self._pending_rows, index=self.name)
        bucket = choose_bucket(self.ladder, rows, ceiling=cap)
        self._seq += 1
        obs.counter("serve.batches_total", index=self.name,
                    bucket=str(bucket))
        obs.observe("serve.batch_fill_ratio", rows / bucket,
                    buckets=FILL_BUCKETS, index=self.name)
        now = time.monotonic()
        linger_ms = (now - head.t_enqueue) * 1e3
        obs.observe("serve.queue_wait_ms", linger_ms, index=self.name)
        # per-request queue_wait stages: each member trace records ITS
        # enqueue->drain wait, with the batch seq as the span link tying
        # the traces this batch serves together
        for r in taken:
            obs_trace.stage(r.trace, "queue_wait",
                            ms=(now - r.t_enqueue) * 1e3,
                            batch_seq=self._seq, bucket=bucket)
        return Batch(requests=taken, rows=rows, bucket=bucket,
                     prefilter=head.prefilter, seq=self._seq,
                     linger_ms=linger_ms)

    def _drain_shadow_locked(self) -> Batch:
        """Drain one filter-homogeneous run off the shadow lane into a
        ``shadow=True`` batch (caller holds ``_cond``). Deliberately
        skips ALL live-lane bookkeeping — no ``_pending_rows``, no
        fill-ratio/queue-wait series, no trace stages (shadow requests
        carry no trace): the shadow lane must not perturb the signals
        the live dispatcher and its SLOs are steered by."""
        head = self._qs[0]
        key = id(head.prefilter) if head.prefilter is not None else None
        cap = max(self._ceiling, head.rows)
        taken: List[Request] = []
        rows = 0
        while self._qs:
            r = self._qs[0]
            rk = id(r.prefilter) if r.prefilter is not None else None
            if rk != key or (taken and rows + r.rows > cap):
                break
            taken.append(self._qs.popleft())
            rows += r.rows
        self._shadow_rows -= rows
        bucket = choose_bucket(self.ladder, rows, ceiling=cap)
        self._seq += 1
        obs.counter("serve.shadow_batches_total", index=self.name)
        return Batch(requests=taken, rows=rows, bucket=bucket,
                     prefilter=head.prefilter, seq=self._seq,
                     shadow=True)


def pad_rows(queries: np.ndarray, bucket: int) -> np.ndarray:
    """Zero-pad ``queries`` up to ``bucket`` rows ON THE HOST (numpy):
    the pad must happen before the device transfer so the traced program
    only ever sees ladder shapes — a ``jnp.pad`` here would itself trace
    once per distinct input row count, defeating the ladder."""
    rows = queries.shape[0]
    if rows == bucket:
        return queries
    pad = np.zeros((bucket - rows,) + queries.shape[1:], queries.dtype)
    return np.concatenate([queries, pad], axis=0)
