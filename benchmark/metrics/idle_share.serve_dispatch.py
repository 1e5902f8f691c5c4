"""Share of the served window in which the device sat idle while the
batcher held a ``serve.dispatch`` span open, so that the device waited
on the host's dispatch of a drained batch; from the profiler trace of
the window (idle: outside the union of the device's op intervals),
averaged over the chips traced. Only the batcher's thread opens that
span. Nothing where the program opens no such span."""

import numpy as np


def _merged(intervals) -> np.ndarray:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.array(out, np.float64).reshape(-1, 2)


def _busy_before(busy: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Busy time before each time in ``t``, over the sorted disjoint
    ``busy`` intervals."""
    if not len(busy):
        return np.zeros_like(t)
    starts, ends = busy[:, 0], busy[:, 1]
    done = np.concatenate([[0.0], np.cumsum(ends - starts)])
    # intervals started by t: all of them but the last, and the last up
    # to t
    n = np.searchsorted(starts, t, side="right")
    last = np.maximum(n - 1, 0)
    part = np.clip(t - starts[last], 0.0, ends[last] - starts[last])
    return np.where(n > 0, done[last] + part, 0.0)


def read(run):
    tl = run.timelines.get("bench.window")
    if tl is None or not tl.devices:
        return None
    lo, hi = tl.span("bench.window")
    spans = _merged((max(s, lo), min(e, hi)) for s, e, name in tl.host
                    if name == "serve.dispatch" and e > lo and s < hi)
    if not len(spans):
        return None
    held = float(np.sum(spans[:, 1] - spans[:, 0]))
    idle = []
    for dev in tl.devices:
        busy = tl.busy_intervals(dev, lo, hi)
        overlap = (_busy_before(busy, spans[:, 1])
                   - _busy_before(busy, spans[:, 0]))
        idle.append(held - float(np.sum(overlap)))
    return sum(idle) / len(idle) / (hi - lo)
