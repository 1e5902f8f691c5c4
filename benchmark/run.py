"""Run one cell of the benchmark once and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

(``python3 -m benchmark.run`` from the checkout's root does the same.)
Run from the root of a checkout that holds ``BENCHMARK.json``. The last
line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` a ``breakdown``,
and ``checks``: each number compared beside its limit); the line before
it says what set-up did (whether the build compiled, the device's peak
bytes, programs loaded inside the window, how late the load generator
ran). Without an accelerator, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _configure_jax_cache() -> None:
    """Every program in the persistent cache inside the checkout, at a
    fixed path, so that only a cell's first run there compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
        sys.path.pop(0)
    sys.path.insert(0, ROOT)
    _configure_jax_cache()
    try:
        import raft_tpu
    except ImportError as e:
        print(f"bench: the program under test is missing: {e}",
              file=sys.stderr)
        return 2
    if os.path.dirname(os.path.dirname(os.path.abspath(
            raft_tpu.__file__))) != ROOT:
        print(f"bench: raft_tpu imports from {raft_tpu.__file__}, not from "
              f"this checkout", file=sys.stderr)
        return 2
    from benchmark import harness

    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, trace_on=bool(args.trace),
                                  t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
