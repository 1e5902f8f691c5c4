"""Flight recorder: a bounded ring of recent span/metric/error events.

In ``RAFT_TPU_OBS=flight`` mode every completed root span, metric
update, and classified error lands in a fixed-size ring buffer
(:data:`DEFAULT_CAPACITY` events, oldest evicted first). The ring is
dumpable as JSONL on demand (:func:`dump`) and dumps ITSELF — once per
process — when :func:`on_error` sees a classified ``fatal`` or
``dead_backend`` failure, so a wedged TPU job leaves a post-mortem
artifact under ``RAFT_TPU_OBS_DIR``.

Dump grammar: one JSON object per line, every line carrying ``t``
(unix seconds) and ``kind``:

* ``{"kind": "span", "thread": ..., "tree": {nested span dict}}``
* ``{"kind": "metric", "name": ..., "value": ..., "labels": {...}}``
* ``{"kind": "error", "error_kind": "oom"|..., "type": ..., "message": ...}``
* ``{"kind": "event", "event": ..., ...}`` — library breadcrumbs
  (retries, ladder downshifts, injected faults, checkpoint saves)
* ``{"kind": "waterfall", "trace_id": ..., "stages": [...], ...}`` — a
  completed graft-trace waterfall (:mod:`raft_tpu.obs.trace`); dumps
  from different processes stitch by ``trace_id``
  (``scripts/obs_report.py stitch``)
* a final ``{"kind": "snapshot", "metrics": {...}}`` line — the full
  registry at dump time.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import List, Optional

from raft_tpu.obs import config
from raft_tpu.obs import metrics

DEFAULT_CAPACITY = 4096

_lock = threading.Lock()
_events: "collections.deque" = collections.deque(maxlen=DEFAULT_CAPACITY)
_auto_dumped = False
_last_dump_path: Optional[str] = None
# monotonic per-process dump sequence: two dumps in the same wall-clock
# second used to compute the same flight-<pid>-<unix>.jsonl path and the
# second silently OVERWROTE the first (ISSUE 13 satellite) — the
# counter makes every default path distinct for the process lifetime
_dump_seq = itertools.count(1)


def record(kind: str, **fields) -> None:
    """Append one event to the ring (no-op outside flight mode)."""
    if not config.FLIGHT:
        return
    evt = {"t": time.time(), "kind": kind}
    evt.update(fields)
    with _lock:
        _events.append(evt)


def event(name: str, **fields) -> None:
    """A library breadcrumb (``kind="event"``): retries, ladder
    downshifts, fault injections, checkpoint saves..."""
    record("event", event=name, **fields)


def events() -> List[dict]:
    """The current ring contents, oldest first."""
    with _lock:
        return list(_events)


def clear() -> None:
    global _auto_dumped, _last_dump_path
    with _lock:
        _events.clear()
        _auto_dumped = False
        _last_dump_path = None


def last_dump_path() -> Optional[str]:
    return _last_dump_path


def dump(path: Optional[str] = None, reason: str = "manual") -> str:
    """Write the ring + a final metrics-snapshot line as JSONL.

    ``path`` defaults to ``RAFT_TPU_OBS_DIR`` (or cwd) /
    ``flight-<pid>-<unix>-<seq>.jsonl`` — ``seq`` is a monotonic
    per-process counter, so two dumps landing in the same second get
    distinct paths instead of the later overwriting the earlier.
    Returns the path written.
    """
    global _last_dump_path
    if path is None:
        d = config.obs_dir()
        os.makedirs(d, exist_ok=True)
        path = os.path.join(
            d, f"flight-{os.getpid()}-{int(time.time())}"
               f"-{next(_dump_seq):03d}.jsonl")
    with _lock:
        evts = list(_events)
    with open(path, "w") as fp:
        for evt in evts:
            fp.write(json.dumps(evt, default=str) + "\n")
        fp.write(json.dumps({
            "t": time.time(), "kind": "snapshot", "reason": reason,
            "metrics": metrics.snapshot(runtime_gauges=False)["metrics"],
        }, default=str) + "\n")
    with _lock:
        # guarded like clear()'s write: last_dump_path() from another
        # thread (the exit guard, tests) must not read a torn update
        _last_dump_path = path
    metrics.counter("flight_dumps", reason=reason)
    return path


# fatal/dead_backend spellings duplicated from resilience.errors — obs
# must stay import-leaf (resilience imports obs, never the reverse)
_AUTO_DUMP_KINDS = ("fatal", "dead_backend")

# one failure traverses NESTED recovery layers (stream.py: run_halving
# wraps resilience.run, both classify the same exception), so repeat
# classifications of the same live exception object must count once.
# The seen-marker lives ON the exception (builtin exceptions accept
# attributes but not weakrefs, and an id()-keyed cache could suppress a
# new failure at a recycled address); the rare attribute-less exception
# type just counts every time.
_COUNTED_ATTR = "_raft_tpu_obs_counted"


def _already_counted(exc: BaseException) -> bool:
    if getattr(exc, _COUNTED_ATTR, False):
        return True
    try:
        setattr(exc, _COUNTED_ATTR, True)
    except (AttributeError, TypeError):
        pass                     # immutable exception: count every time
    return False


def on_error(kind: str, exc: Optional[BaseException] = None,
             where: Optional[str] = None) -> None:
    """The resilience layer's error hook: counts
    ``errors_total{kind}`` (once per distinct exception object, however
    many nested recovery layers classify it), records an error event,
    and — in flight mode, once per process — auto-dumps the ring when
    ``kind`` is ``fatal`` or ``dead_backend``. Never raises: a broken
    disk must not mask the error being recorded."""
    global _auto_dumped
    if not config.ENABLED:
        return
    try:
        if exc is not None and _already_counted(exc):
            return
        metrics.counter("errors_total", kind=kind)
        record("error", error_kind=kind, where=where,
               type=type(exc).__name__ if exc is not None else None,
               message=(str(exc)[:500] if exc is not None else None))
        if kind in _AUTO_DUMP_KINDS and config.FLIGHT:
            with _lock:
                if _auto_dumped:
                    return
                _auto_dumped = True
            dump(reason=f"auto:{kind}")
    except Exception:  # noqa: BLE001
        pass
