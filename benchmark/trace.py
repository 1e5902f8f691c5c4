"""From a profiler trace to device busy time, kernel time and a breakdown.

A traced phase runs inside :func:`capture`, which brackets it with a host
annotation of the phase's name. :func:`load` reads the ``.xplane.pb`` the
profiler wrote into a :class:`Timeline`: the device operations of each
chip (the ``XLA Ops`` line of each ``/device:`` plane) and the host spans
(every event of the ``/host:`` planes: the program's ``obs`` spans and the
benchmark's own ``bench.*`` annotations). Both are on the profiler's clock.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
# a TPU op event is named by its whole HLO instruction; the breakdown
# keeps its name, shape and operation without layouts and attributes
_LAYOUT = re.compile(r"\{[^{}]*\}")
_ATTRS = re.compile(r"\), (kind|calls|custom_call_target|to_apply|"
                    r"dimensions|operand_layout_constraints)=.*$")


def short_op(name: str, limit: int = 160) -> str:
    """``%fusion.9 = s32[909312]{0:T(1024)} fusion(...), kind=...`` ->
    ``fusion.9 = s32[909312] fusion(...)``, at most ``limit`` letters."""
    s = _ATTRS.sub(")", _LAYOUT.sub("", name)).lstrip("%")
    return s if len(s) <= limit else s[:limit - 3] + "..."


@contextlib.contextmanager
def capture(phase: str, out: dict):
    """Trace the body into a fresh directory under ``TMPDIR``; on exit
    ``out[phase]`` holds its :class:`Timeline` and the directory is gone.
    The body runs inside a host annotation named ``phase``."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1      # annotations (TraceMe level 1) only
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(phase):
            yield
    finally:
        jax.profiler.stop_trace()
        try:
            out[phase] = load(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


class Timeline:
    """Device op intervals per chip and host spans, in nanoseconds."""

    def __init__(self, device_ops: Dict[str, list], host: list):
        # device name -> (starts, ends, names), sorted by start
        self.devices = {}
        for dev, evs in sorted(device_ops.items()):
            evs.sort(key=lambda e: e[0])
            self.devices[dev] = (np.array([e[0] for e in evs], np.float64),
                                 np.array([e[1] for e in evs], np.float64),
                                 [e[2] for e in evs])
        host.sort(key=lambda e: e[0])
        self.host = host
        self._host_starts = [e[0] for e in host]

    # -- windows ---------------------------------------------------------

    def span(self, name: str) -> Tuple[float, float]:
        """(start, end) of the longest host span named ``name``."""
        hits = [e for e in self.host if e[2] == name]
        if not hits:
            raise KeyError(f"no host span {name!r} in the trace")
        s, e, _ = max(hits, key=lambda h: h[1] - h[0])
        return s, e

    # -- device time -----------------------------------------------------

    def busy_intervals(self, dev: str, lo: float, hi: float) -> np.ndarray:
        """Union of the device's op intervals, clipped to [lo, hi]."""
        starts, ends, _ = self.devices[dev]
        keep = (ends > lo) & (starts < hi)
        s = np.clip(starts[keep], lo, hi)
        e = np.clip(ends[keep], lo, hi)
        out: List[List[float]] = []
        for a, b in zip(s, e):
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return np.array(out, np.float64).reshape(-1, 2)

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds in which an op ran, averaged over the chips traced."""
        if not self.devices:
            return 0.0
        tot = [float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0
               for iv in (self.busy_intervals(d, lo, hi)
                          for d in self.devices)]
        return sum(tot) / len(tot) / 1e9

    def op_seconds(self, patterns: Sequence[str], lo: float, hi: float
                   ) -> Tuple[float, int]:
        """Summed device seconds (averaged over chips) and count of the
        ops whose instruction name (before `` = ``) contains any of
        ``patterns``, inside [lo, hi]."""
        total, count = 0.0, 0
        for starts, ends, names in self.devices.values():
            for s, e, n in zip(starts, ends, names):
                head = n.split(" = ", 1)[0]
                if s >= lo and e <= hi and any(p in head for p in patterns):
                    total += e - s
                    count += 1
        n_dev = max(len(self.devices), 1)
        return total / n_dev / 1e9, count

    # -- breakdown -------------------------------------------------------

    def top_ops(self, lo: float, hi: float, n: int = 10) -> list:
        """[[op name, seconds]] of the ``n`` ops that took most device time
        (summed over their calls, averaged over chips)."""
        acc: Dict[str, float] = {}
        for starts, ends, names in self.devices.values():
            for s, e, nm in zip(starts, ends, names):
                if s >= lo and e <= hi:
                    key = short_op(nm)
                    acc[key] = acc.get(key, 0.0) + (e - s)
        n_dev = max(len(self.devices), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / n_dev / 1e9] for k, v in top]

    def idle_gaps(self, lo: float, hi: float, n: int = 10,
                  skip: Sequence[str] = ()) -> list:
        """[[host span, seconds]]: the device's idle time inside [lo, hi]
        (first chip), summed by the host span open at each gap's middle;
        the ``n`` largest. Spans named in ``skip`` (the phase brackets)
        never name a gap while an inner span is open."""
        if not self.devices:
            return []
        dev = next(iter(self.devices))
        iv = self.busy_intervals(dev, lo, hi)
        edges = [lo] + [x for pair in iv for x in pair] + [hi]
        acc: Dict[str, float] = {}
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            name = self._host_name(0.5 * (a + b), skip)
            acc[name] = acc.get(name, 0.0) + (b - a)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def _host_name(self, t: float, skip: Sequence[str]) -> str:
        i = bisect.bisect_right(self._host_starts, t) - 1
        fallback = None
        for j in range(i, max(i - 4096, -1), -1):
            s, e, name = self.host[j]
            if e > t:
                if name not in skip:
                    return name
                fallback = fallback or name
        return fallback or "(no host span)"


def load(path: str) -> Timeline:
    """Read the trace under ``path`` (a profiler log directory or one
    ``.xplane.pb`` file)."""
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(find_xplane(path)))


def from_profile(pd) -> Timeline:
    """A :class:`Timeline` of a ``jax.profiler.ProfileData``."""
    device_ops: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        name = plane.name
        if _DEVICE_PLANE.match(name) and not name.startswith("/device:CPU"):
            evs = device_ops.setdefault(name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    evs.append((s, s + float(ev.duration_ns), ev.name))
        elif name.startswith("/host:") and name != "/host:metadata":
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    host.append((s, s + float(ev.duration_ns), ev.name))
    return Timeline(device_ops, host)


def window_reading(tl: Optional[Timeline], phase: str) -> Optional[dict]:
    """busy_s and window_s of a traced phase (its host bracket)."""
    if tl is None:
        return None
    lo, hi = tl.span(phase)
    return {"lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
            "busy_s": tl.busy_s(lo, hi)}


def idle_share(tl: Optional[Timeline], phase: str) -> Optional[float]:
    """1 - busy / window of a traced phase; None where it was not traced
    or no device op ran in it."""
    w = window_reading(tl, phase)
    if w is None or w["busy_s"] <= 0:
        return None
    return 1.0 - w["busy_s"] / w["window_s"]
