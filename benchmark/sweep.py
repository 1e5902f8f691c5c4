"""Sweep the offered rate of an open-loop cell to find its knee (not part
of the benchmark's own runs).

    python3 benchmark/sweep.py --workload <name> --seed <n> --rates 1000,2000 [--seconds 8]

One set-up (data, build, server warm-up), then one window per rate, in
order. Each prints a JSON line: offered and completed rate, p50 and p99
of the latency from each request's due time, refusals, misses, how late
the generator ran, and the p99 of the window's first and last fifths (a
backlog that grows shows as a last fifth far above the first).
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
        sys.path.pop(0)
    sys.path.insert(0, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    from benchmark import data, harness

    run = harness.Run(harness.BenchSpec(ROOT), args.workload, args.seed,
                      args.seconds, False)
    harness.check_devices(int(run.workload["chips"]))
    entry = run.bench.module("entries", run.cfg["entry"])
    driver = run.bench.module("drivers", run.traffic["kind"])
    x, q = jax.block_until_ready(data.generate(run.cfg, args.seed))
    index = jax.block_until_ready(entry.build(run.cfg, x))
    st = driver.setup(run, entry, index, x, q)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            gc.collect()
            gc.freeze()
            run.traffic = dict(run.traffic, rate_qps=rate)
            win = driver.window(run, st, args.seconds)
            info = dict(win["info"], **win["readings"])
            info["fifths_p99_ms"] = _fifths_p99(win)
            print(json.dumps({"rate_qps": rate, **info}), flush=True)
    finally:
        driver.close(st)
    return 0


def _fifths_p99(win) -> list:
    lat = win.get("latency_ms")
    if lat is None:
        return []
    out = []
    for part in np.array_split(lat, 5):
        s = np.sort(part)
        out.append(float(s[math.ceil(0.99 * len(s)) - 1]))
    return out


if __name__ == "__main__":
    sys.exit(main())
