"""Read the numbers that ``correct`` compares from the control, on given
seeds, in one process (not part of the benchmark's own runs).

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 [--seconds 3]

The control is the plain reference in the program's place: the exact
search with both operands rounded to the configuration's
``control_dtype``, the precision below the one the configuration states.
It answers the whole query pool in one batch per call, in a short window.
Each seed prints one JSON line with its checks; a limit is sound only
where every control line reads past it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
        sys.path.pop(0)
    sys.path.insert(0, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from benchmark import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            r = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                 control=True)
        except harness.NoAccelerator as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": r["correct"], "checks": r["checks"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
