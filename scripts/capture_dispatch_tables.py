#!/usr/bin/env python
"""Capture the per-backend dispatch table (the measurement artifact the
reference generates with cpp/scripts/heuristics/select_k and bakes into
matrix/detail/select_k-inl.cuh:51-79).

Times the competing implementations behind every tuned hot-path
dispatch — select_k / merge_topk (lax.top_k vs tournament vs
hierarchical), ivf_scan (fused Pallas kernel vs XLA bucketized scan),
ivf_scan_extract (in-kernel extraction arms incl. the unextracted
fold), fused_topk_tile (brute-force scan vs fused kernel per
variant/row-tile), pq_scan (i8/i4/pq4/rabitq cache kinds — the rabitq
arm races its whole rerank pipeline at matched recall, and arms that
cannot hit the recall band are filtered before timing),
graph_join (nn-descent local join: XLA einsum+merge vs the fused
kernel per node tile, ISSUE 15), beam_step_tile (the beam kernel's
query-tile geometry over real packed rows), and
serve_service (per-(bucket, probe-rung) end-to-end service medians the
serve deadline machinery reads, ISSUE 14) — over a shape
grid, plus the environment byte budgets, and writes
``raft_tpu/tuning/tables/<backend>.json``. Consumers pick these
winners up automatically through ``raft_tpu.tuning.choose`` (knob:
``RAFT_TPU_TUNING``; docs/dispatch_tuning.md).

Run on CPU today (committed table), re-run the moment a TPU answers —
it is part of the r5+ measurement battery (scripts/r5_measure_all.py).

    python scripts/capture_dispatch_tables.py                # quick grid
    python scripts/capture_dispatch_tables.py --full         # wide grid
    python scripts/capture_dispatch_tables.py --out /path.json
    python scripts/capture_dispatch_tables.py --ops select_k,merge_topk
    python scripts/capture_dispatch_tables.py --interpret    # time the
        # pallas kernel in interpret mode on CPU (debug-only numbers)
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="output path (default: the packaged "
                         "raft_tpu/tuning/tables/<backend>.json)")
    ap.add_argument("--backend", default=None,
                    help="override the table's backend name")
    ap.add_argument("--full", action="store_true",
                    help="wide grid (quick grid is the default)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--ops", default=None,
                    help="comma list: select_k,merge_topk,ivf_scan,"
                         "pq_scan,ivf_scan_extract,fused_topk_tile,"
                         "graph_join,beam_step_tile,serve_service "
                         "(kernel arms need a TPU, or --interpret on "
                         "CPU). A subset capture MERGES into the "
                         "existing table at --out instead of "
                         "clobbering the other ops' entries")
    ap.add_argument("--interpret", action="store_true",
                    help="on CPU, also time the Pallas kernels in "
                         "interpret mode (debug-only numbers)")
    ap.add_argument("--deadline", type=float, default=1500.0,
                    help="wall-clock budget (s) for the capture incl. "
                         "one transient retry (resilience.run)")
    args = ap.parse_args(argv)

    import jax

    from raft_tpu import resilience, tuning
    from raft_tpu.tuning import microbench

    backend = args.backend or tuning.backend_name()
    print(f"devices: {jax.devices()}  backend table: {backend}",
          flush=True)
    # resilience wrap: a transient blip costs one classified retry
    # inside --deadline instead of the whole capture; OOM/fatal
    # failures propagate
    table = resilience.run(
        microbench.capture,
        backend=backend,
        quick=not args.full,
        include_interpret=args.interpret,
        reps=args.reps,
        ops=args.ops.split(",") if args.ops else None,
        retries=1,
        backoff_s=15,
        deadline_s=args.deadline,
        retry_on=(resilience.TRANSIENT,),
    )
    out = args.out or os.path.join(tuning.tables_dir(), backend + ".json")
    if args.ops and os.path.exists(out):
        # subset re-capture (e.g. --ops serve_service after the serve
        # layer grows a rung): fold the fresh entries into the existing
        # table — a partial capture must never throw away the other
        # ops' measured winners
        from raft_tpu.tuning.table import DispatchTable

        prior = DispatchTable.load(out)
        prior.data["captured"] = table.data["captured"]
        prior.data["device"] = table.data["device"]
        for op, body in table.data["ops"].items():
            prior.data["ops"][op] = body
        prior.data["budgets"].update(table.data["budgets"])
        table = prior
    table.save(out)
    print(f"wrote {out}: ops={table.ops()} entries={table.n_entries()} "
          f"budgets={table.data['budgets']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
