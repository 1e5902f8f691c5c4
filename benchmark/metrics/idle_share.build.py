"""Device idle share of the index build in set-up: 1 - busy / build
seconds, from the profiler trace of the build (traced runs only)."""

from benchmark.trace import idle_share


def read(run):
    return idle_share(run.timelines.get("bench.build"), "bench.build")
