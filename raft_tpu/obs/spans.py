"""Structured spans: host wall-clock trees that still show up in XLA traces.

``span(name, **attrs)`` is a context manager capturing host wall-clock
(and, via :meth:`Span.sync`, device-complete time) into a PER-THREAD
span tree; every span also opens a ``jax.profiler.TraceAnnotation`` so
the same names line up in an XLA/TPU profiler capture (the analog of
the reference's NVTX ranges).

Trace-time semantics: a span opened inside jit-traced Python executes
host-side AT TRACE TIME only — it measures trace/compile attribution
(the compile-vs-dispatch split TPU-KNN's analysis method needs) and is
absent from steady-state cached dispatches. Spans at the public entry
points (outside jit) measure real per-call wall-clock.

Off mode (:data:`raft_tpu.obs.config.ENABLED` False) returns a shared
no-op singleton: one module-attribute read, no allocation that outlives
the call.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Optional

from raft_tpu.obs import config
from raft_tpu.obs import metrics
from raft_tpu.obs import trace as _trace

# children kept per span before truncation (a 100k-chunk streamed search
# must not grow an unbounded tree); the drop count is recorded
MAX_CHILDREN = 64
# completed per-thread root trees kept for inspection
MAX_COMPLETED = 256


class _NullSpan:
    """Shared off-mode singleton: a reusable, reentrant no-op span."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def sync(self, value=None):
        return value


_NULL_SPAN = _NullSpan()

_tls = threading.local()
_lock = threading.Lock()
_completed: "collections.deque" = collections.deque(maxlen=MAX_COMPLETED)


def _stack(create: bool = True):
    s = getattr(_tls, "stack", None)
    if s is None and create:
        s = _tls.stack = []
    return s


class Span:
    """One timed, attributed node in the per-thread span tree."""

    __slots__ = ("name", "attrs", "t0", "ms", "device_ms", "children",
                 "dropped_children", "_ta")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.ms: Optional[float] = None
        self.device_ms: Optional[float] = None
        self.children = []
        self.dropped_children = 0
        self._ta = None

    # -- context protocol --------------------------------------------------

    def __enter__(self):
        try:
            import jax

            # scalar attrs ride into the profiler trace as annotation
            # metadata
            meta = {k: v for k, v in self.attrs.items()
                    if isinstance(v, (str, int, float, bool))}
            self._ta = jax.profiler.TraceAnnotation(self.name, **meta)
            self._ta.__enter__()
        except Exception:  # noqa: BLE001  # graft-lint: allow-unclassified-swallow profiler annotation is best-effort; span timing must survive a profiler-less runtime
            self._ta = None
        # graft-trace adoption (ISSUE 13): a span opened under an
        # activated cross-process context carries the shared trace id,
        # so one trace id names worker-side spans, router spans, and
        # flight-dumped trees alike — the stitch key obs_report uses
        tid = _trace.current_id()
        if tid is not None and "trace_id" not in self.attrs:
            self.attrs["trace_id"] = tid
        _stack().append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.ms = (time.perf_counter() - self.t0) * 1e3
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        if self._ta is not None:
            try:
                self._ta.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001 — profiler teardown is best-effort (see __enter__)
                pass
        stack = _stack(create=False)
        if stack and stack[-1] is self:
            stack.pop()
        elif stack and self in stack:          # unbalanced exit: heal
            stack.remove(self)
        metrics.observe("span_ms", self.ms, name=self.name)
        self._finish(stack)
        return False

    def _finish(self, stack) -> None:
        if stack:
            parent = stack[-1]
            if len(parent.children) < MAX_CHILDREN:
                parent.children.append(self)
            else:
                parent.dropped_children += 1
        else:
            entry = (threading.current_thread().name, self.to_dict())
            with _lock:
                _completed.append(entry)
            if config.FLIGHT:
                from raft_tpu.obs import flight

                flight.record("span", thread=entry[0], tree=entry[1])

    # -- user API ----------------------------------------------------------

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-span (chosen impl, rows...)."""
        self.attrs.update(attrs)
        return self

    def sync(self, value=None):
        """Optional device-sync timestamp: block until ``value`` (any
        pytree of jax arrays) is ready and record the elapsed time as
        ``device_ms`` — the device-complete latency, distinct from the
        dispatch wall-clock ``ms``. Returns ``value``."""
        if value is not None:
            try:
                import jax

                jax.block_until_ready(value)
            except Exception:  # noqa: BLE001  # graft-lint: allow-unclassified-swallow non-array values legitimately pass through unsynced
                pass
        self.device_ms = (time.perf_counter() - self.t0) * 1e3
        return value

    def to_dict(self) -> dict:
        d = {"name": self.name, "ms": self.ms}
        if self.device_ms is not None:
            d["device_ms"] = self.device_ms
        if self.attrs:
            d["attrs"] = {k: _jsonable(v) for k, v in self.attrs.items()}
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        if self.dropped_children:
            d["dropped_children"] = self.dropped_children
        return d


def _jsonable(v):
    return v if isinstance(v, (str, int, float, bool, type(None))) else str(v)


class _EntrySpan(Span):
    """A span at a public search/build entry point: on exit it also
    feeds the standard entry metrics (``queries_total`` /
    ``builds_total``, ``<op>_latency_ms{algo}``)."""

    __slots__ = ("op", "algo", "queries")

    def __init__(self, op: str, algo: str, queries: Optional[int],
                 attrs: dict):
        super().__init__(f"{algo}.{op}", attrs)
        self.op = op
        self.algo = algo
        self.queries = queries

    def __exit__(self, exc_type, exc, tb):
        out = super().__exit__(exc_type, exc, tb)
        if exc_type is None:
            if self.op == "search":
                if self.queries:
                    metrics.counter("queries_total", self.queries,
                                    algo=self.algo)
                metrics.observe("search_latency_ms", self.ms, algo=self.algo)
            elif self.op == "build":
                metrics.counter("builds_total", algo=self.algo)
                metrics.observe("build_latency_ms", self.ms, algo=self.algo)
        return out


def span(name: str, **attrs):
    """Open a structured span (see module docstring). Off mode returns
    the shared no-op singleton."""
    if not config.ENABLED:
        return _NULL_SPAN
    return Span(name, attrs)


def entry_span(op: str, algo: str, queries: Optional[int] = None, **attrs):
    """A :func:`span` for a public ``search``/``build`` entry point that
    also emits the standard entry metrics on clean exit:
    ``queries_total{algo}`` (+= ``queries``) and
    ``search_latency_ms{algo}`` for ``op="search"``;
    ``builds_total{algo}`` and ``build_latency_ms{algo}`` for
    ``op="build"``. The latency is the HOST wall-clock of the entry
    (trace + dispatch; device compute overlaps asynchronously) — call
    ``.sync(result)`` before exit for device-complete timing."""
    if not config.ENABLED:
        return _NULL_SPAN
    if queries is not None:
        attrs.setdefault("queries", int(queries))
        queries = int(queries)
    return _EntrySpan(op, algo, queries, attrs)


def current() -> Optional[Span]:
    """The innermost live span on THIS thread, or None."""
    s = _stack(create=False)
    return s[-1] if s else None


def recent(limit: int = MAX_COMPLETED):
    """Most recent completed per-thread root span trees, newest last:
    ``[(thread_name, tree_dict), ...]``."""
    with _lock:
        items = list(_completed)
    return items[-limit:]


def reset() -> None:
    """Drop completed trees (tests). Live per-thread stacks are left
    alone — a reset mid-span must not orphan the exit."""
    with _lock:
        _completed.clear()
