"""Device idle share of the served window: 1 - busy / window, from the
profiler trace of the window (busy: union of the device's op intervals)."""

from benchmark.trace import idle_share


def read(run):
    return idle_share(run.timelines.get("bench.window"), "bench.window")
