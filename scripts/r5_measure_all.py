#!/usr/bin/env python
"""Measurement battery: run the measurement scripts in value-per-minute
order, each stage in its own subprocess with a hard timeout, artifacts
written incrementally, so a partial chip window still captures the most
important numbers.

One process per chip: this runner never imports JAX (nor raft_tpu, which
does), so each stage's child is the only process on the chip while it
runs. Each stage checks the device itself (bench.py exits non-zero
without a TPU).

Stages (artifact, rough budget):
  1. bench.py         — BENCH_r05_local.json   (~45 min, headline configs)
  2. deep100m         — DEEP100M_r05.json      (~30 min total at 100M)
  3. r4_sweep         — SWEEP_r05.json         (~25 min, flat+cagra levers)
  4. latency_table    — LATENCY_r05.json       (~10 min, batch 1/10/100)
  5. select_crossover — SELECT_CROSSOVER_r05.json (~10 min)
  6. dispatch_tables  — raft_tpu/tuning/tables/tpu.json (~15 min)

Run: python scripts/r5_measure_all.py [--only stage1,stage2] [--skip ...]
                                      [--obs-snapshot] [--serve]

--serve appends the optional graft-serve load-generator stage
(scripts/serve_loadgen.py -> SERVE_r05.json; docs/serving.md §7).
Progress + per-stage rc stream to stdout and R5_MEASURE_STATUS.json.

--obs-snapshot runs every stage instrumented (RAFT_TPU_OBS=flight in the
child env, flight dumps under OBS_r05/) and asks bench.py for its
BENCH_r05_local.obs.json metrics sidecar — each artifact then carries
the dispatch winners, latency histograms, and retry/ladder counters that
explain it (docs/observability.md).
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable


STAGES = [
    # (name, argv, timeout_s)
    ("bench", [PY, "bench.py"], 5400),
    ("deep100m", [PY, "scripts/deep100m.py", "DEEP100M_r05.json"], 4200),
    ("sweep", [PY, "scripts/r4_sweep.py", "both"], 3600),
    # graph rung (ISSUE 15): nn-descent rebuild A/B (sample-then-gather
    # vs the old full-two-hop gather, bitwise-identical graphs) + the
    # 1M-row blocked build with bounded per-iteration transients —
    # GRAPH_r{N}.json re-captured at chip service times
    ("graph_bench", [PY, "scripts/graph_bench.py", "GRAPH_r15.json"],
     3600),
    ("latency", [PY, "scripts/latency_table.py"], 1800),
    ("crossover", [PY, "scripts/select_crossover.py"], 1800),
    # per-backend dispatch table (select/merge/scan winners + budgets):
    # writes raft_tpu/tuning/tables/tpu.json the instant a chip answers —
    # commit the artifact so tuning.choose serves measured winners
    ("dispatch_tables",
     [PY, "scripts/capture_dispatch_tables.py", "--full"], 1800),
]

# OPTIONAL stages (run with --serve, or name them in --only): the
# graft-serve closed-loop load generator — SERVE_r05.json latency/
# throughput sidecar + obs metrics snapshot (docs/serving.md §7) —
# and the multi-host fabric loadgen — FABRIC_r06.json (QPS, p99,
# coverage, hedges, dropouts; docs/serving.md §10)
OPTIONAL_STAGES = [
    ("serve_loadgen",
     [PY, "scripts/serve_loadgen.py", "--n", "200000", "--dim", "96",
      "--algo", "ivf_flat", "--concurrency", "32", "--duration-s", "60",
      "--k", "1,10,100", "--out", "SERVE_r05.json",
      "--obs-snapshot", "SERVE_r05.obs.json"], 900),
    ("fabric_loadgen",
     [PY, "scripts/serve_loadgen.py", "--fabric", "--n", "120000",
      "--dim", "96", "--fabric-workers", "4",
      "--fabric-replication", "2", "--concurrency", "16",
      "--duration-s", "45", "--k", "1,10,100",
      "--out", "FABRIC_r06.json",
      "--obs-snapshot", "FABRIC_r06.obs.json"], 900),
    # graft-trace acceptance (ISSUE 13): chaos fabric loadgen with the
    # tracing A/B (off-vs-on QPS recorded in FABRIC_r13.json), per-stage
    # waterfall columns, and the federated fleet snapshot archived under
    # OBS_r13/ (JSON + Prometheus text; flight dumps land there too when
    # the battery runs --obs-snapshot)
    ("fabric_trace",
     [PY, "scripts/serve_loadgen.py", "--fabric", "--n", "120000",
      "--dim", "96", "--fabric-workers", "4",
      "--fabric-replication", "2", "--concurrency", "8",
      "--duration-s", "45", "--k", "1,10,100",
      "--fault", "dead@proc:2,slow@proc:1*3", "--swap-mid-run",
      "--ab-obs", "--out", "FABRIC_r13.json",
      "--federate-out", "OBS_r13/FEDERATED_r13.json",
      "--obs-snapshot", "FABRIC_r13.obs.json"], 1200),
    # graft-plan acceptance (ISSUE 20): compiled-plan serving vs the
    # legacy library dispatch at identical batch shapes (QPS/recall/
    # retrace columns + bitwise verdict), plus the hybrid dense+sparse
    # score_fuse plan served end-to-end vs a fused numpy oracle
    ("plan_ab",
     [PY, "scripts/serve_loadgen.py", "--plan-ab", "--n", "20000",
      "--dim", "64", "--n-lists", "16", "--k", "10",
      "--query-pool", "256", "--max-batch-rows", "32",
      "--duration-s", "10", "--out", "PLAN_r20.json"], 900),
    # graft-helm acceptance (ISSUE 18): the self-healing chaos curve —
    # primary-vs-p2c balancer A/B at matched topology, then a scripted
    # slow/flap/permanent-dead schedule under the HelmController with a
    # low/high/low traffic ramp; coverage timeline, repair latency,
    # autoscale trace, and bitwise oracle checks land in FABRIC_r18.json
    ("fabric_helm",
     [PY, "scripts/serve_loadgen.py", "--chaos-curve", "--n", "60000",
      "--dim", "64", "--fabric-workers", "4",
      "--fabric-replication", "2", "--concurrency", "16",
      "--duration-s", "15", "--k", "1,10,100",
      "--out", "FABRIC_r18.json",
      "--obs-snapshot", "FABRIC_r18.obs.json"], 1200),
    # tiered-memory acceptance (ISSUE 12, ROADMAP item 3): host/mmap
    # originals + shortlist-only fetch vs the full-upload baseline,
    # then a Zipf(1.0) serve run whose hot-row hit-rate / zero-retrace
    # columns merge into the same artifact
    ("tiered_deep100m",
     [PY, "scripts/deep100m.py", "--tiered-only", "--n", "1000000",
      "--tiered-out", "TIERED_r12.json"], 2700),
    # SLO acceptance (ISSUE 14, ROADMAP item 5): the closed-loop
    # deadline harness — calibrate capacity, hold the p99 target under
    # 1x and 2x overload with adaptive probe rungs, recall band vs the
    # exhaustive baseline, mean probed-list reduction. Flags match the
    # committed SLO_r14.json so the stage REPRODUCES the artifact (on
    # chip day the same run re-captures it at TPU service times)
    ("slo_loadgen",
     [PY, "scripts/serve_loadgen.py", "--slo-p99-ms", "250",
      "--n", "20000", "--dim", "64", "--n-lists", "16", "--k", "10",
      "--query-pool", "512", "--max-batch-rows", "8",
      "--max-wait-ms", "2", "--concurrency", "8", "--duration-s", "10",
      "--out", "SLO_r14.json"], 1200),
    # flags match the committed SERVE_TIERED_r12.json exactly, so the
    # stage REPRODUCES the artifact (result cache off on purpose: with
    # it on, repeats never reach the engine and the hot-ROW tier idles
    # at ~0.4 hit rate — the result cache's own under-load evidence is
    # the r12 run recorded in CHANGES.md and tests/test_tiered.py)
    ("tiered_serve_zipf",
     [PY, "scripts/serve_loadgen.py", "--n", "20000", "--dim", "96",
      "--tiered", "--zipf", "1.0", "--query-pool", "256",
      "--refine-ratio", "3", "--result-cache", "0",
      "--hot-rows", "16384", "--max-batch-rows", "16",
      "--concurrency", "8", "--duration-s", "30", "--k", "1,10",
      "--out", "SERVE_TIERED_r12.json",
      "--merge-into", "TIERED_r12.json"], 1200),
    # graft-gauge acceptance (ISSUE 19, ROADMAP item 9): the closed-
    # loop quality drill — a loose-margin retune-recovery leg (seeded
    # serve_probe_margin/floor budgets, bounded tighten steps walk the
    # pooled Wilson estimate back inside the band), then a crippled
    # n_probes=1 hot-swap the probation window convicts and rolls
    # back. Flags match the committed QUALITY_r19.json so the stage
    # REPRODUCES the artifact (zero-retrace columns re-checked at TPU
    # service times on chip day)
    ("quality_drift",
     [PY, "scripts/serve_loadgen.py", "--drift", "--n", "1024",
      "--dim", "16", "--n-lists", "16", "--k", "8",
      "--query-pool", "256", "--duration-s", "30", "--seed", "7",
      "--out", "QUALITY_r19.json"], 1200),
    # graft-flow acceptance (ISSUE 16): serial vs pipelined memmap
    # tiered rerank under injected slow fetch — wall-clock speedup,
    # stall totals, overlap fraction, bitwise verdict (PIPE_r16.json;
    # on chip day the score-side injection is dropped and the overlap
    # hides real device scan time)
    ("pipeline",
     [PY, "scripts/deep100m.py", "--pipeline-only", "--n", "1000000",
      "--pipeline-out", "PIPE_r16.json"], 2700),
]


def main():
    only = skip = None
    if "--only" in sys.argv:
        only = set(sys.argv[sys.argv.index("--only") + 1].split(","))
    if "--skip" in sys.argv:
        skip = set(sys.argv[sys.argv.index("--skip") + 1].split(","))
    obs_on = "--obs-snapshot" in sys.argv
    child_env = None
    if obs_on:
        # children self-instrument in flight mode: a stage that dies with
        # a classified fatal/dead_backend leaves its flight JSONL under
        # OBS_r05/ even when its artifact never materialized
        child_env = dict(os.environ,
                         RAFT_TPU_OBS="flight",
                         RAFT_TPU_OBS_DIR=os.path.join(ROOT, "OBS_r05"))
    status = {"started": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
              "stages": {}, "obs": bool(obs_on)}

    def flush():
        with open(os.path.join(ROOT, "R5_MEASURE_STATUS.json"), "w") as f:
            json.dump(status, f, indent=1)

    failed = 0
    stages = list(STAGES)
    if "--serve" in sys.argv or (
            only is not None
            and any(n in only for n, _, _ in OPTIONAL_STAGES)):
        stages += OPTIONAL_STAGES
    for name, argv, tmo in stages:
        if only is not None and name not in only:
            continue
        if skip is not None and name in skip:
            continue
        t0 = time.time()
        stage_argv = list(argv)
        if obs_on and argv[1] == "bench.py":
            stage_argv += ["--obs-snapshot", "BENCH_r05_local.obs.json"]
        print(f"=== {name}: {' '.join(stage_argv)} (timeout {tmo}s)",
              flush=True)

        try:
            r = subprocess.run(stage_argv, timeout=tmo, cwd=ROOT,
                               capture_output=True, env=child_env)
            out = r.stdout.decode(errors="replace")
            err = r.stderr.decode(errors="replace")
            status["stages"][name] = {
                "rc": r.returncode, "s": round(time.time() - t0, 1),
                "tail": (out + err)[-2000:],
            }
            # bench.py prints its JSON line to stdout — persist it, and
            # thread its roofline columns (peak_fraction / bytes_per_row
            # per op, docs/kernels.md §roofline) into the stage summary
            # so the battery's status file answers "how close to the
            # hardware ceiling" without opening the artifact
            if name == "bench" and r.returncode == 0:
                last = [ln for ln in out.splitlines() if ln.startswith("{")]
                if last:
                    with open(os.path.join(ROOT, "BENCH_r05_local.json"),
                              "w") as f:
                        f.write(last[-1] + "\n")
                    try:
                        extra = json.loads(last[-1]).get("extra", {})
                        status["stages"][name]["roofline"] = {
                            kk: vv.get("value", vv)
                            if isinstance(vv, dict) else vv
                            for kk, vv in extra.items()
                            if kk.endswith(("_peak_fraction",
                                            "_bytes_per_row"))
                        }
                    except (ValueError, KeyError):
                        pass
            print(f"--- {name}: rc={r.returncode} "
                  f"{round(time.time() - t0, 1)}s", flush=True)
            print((out + err)[-1500:], flush=True)
            failed += r.returncode != 0
        except subprocess.TimeoutExpired:
            status["stages"][name] = {"rc": "timeout", "s": tmo}
            print(f"--- {name}: TIMEOUT after {tmo}s", flush=True)
            failed += 1
        flush()
    flush()
    print(f"battery complete, {failed} stage(s) failed", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
