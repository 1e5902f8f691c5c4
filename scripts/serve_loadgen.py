#!/usr/bin/env python
"""Closed-loop load generator for the graft-serve engine (ISSUE 5)
and the multi-host fabric (ISSUE 6, ``--fabric``).

Builds an index, stands up a :class:`raft_tpu.serve.Server`, and drives
it with ``--concurrency`` worker threads in closed loop (each worker
submits, waits, submits again) or at a target open-loop ``--qps``;
requests draw k uniformly from the mixed ``--k`` list and optionally
carry a delete/upsert mutation mix. Emits a latency/throughput sidecar
(default ``SERVE_r05.json``):

    {"config": {...}, "throughput_qps": ..., "completed": ...,
     "rejected": ..., "latency_ms": {"p50": ..., "p90": ..., "p99": ...},
     "per_k": {...}, "server": {...}}

``--obs-snapshot PATH`` additionally turns graft-scope on and writes the
full metrics snapshot (queue depth, per-bucket fill/latency histograms,
admission rejects, swap counts — docs/serving.md §7) next to it.

``--fabric`` stands up a :class:`raft_tpu.serve.Fabric` (N worker
processes owning index shards, docs/serving.md §10) instead of the
single-process Server and drives ``fab.search`` directly, emitting a
``FABRIC_r13.json`` sidecar (QPS, latency percentiles, per-row
coverage, hedge/retry/dropout counters, worker health — plus the
graft-trace columns, ISSUE 13: per-stage p50/p99 waterfall attribution
for queue_wait / rpc / worker_scan / merge / rerank, hedge-win counts
per stage, and the complete-waterfall fraction). ``--fault`` installs
a process-level fault spec (e.g. ``slow@proc:1*50``) in the workers so
degraded-mode numbers are measurable on demand. ``--ab-obs`` measures
the tracing-overhead A/B the acceptance bar (<5% on-mode overhead)
reads from the artifact: three swap-free probe legs (off / on / off,
fresh fabrics, half duration each — the off bracket cancels machine
drift) before the main instrumented run. ``--federate-out``
scrapes every worker's metrics registry through the
``collect_metrics`` RPC at the end of the run and archives the merged
fleet snapshot (JSON + Prometheus text).

``--plan-ab`` runs the graft-plan acceptance A/B (ISSUE 20,
docs/plans.md): the compiled-plan serving path vs the legacy library
dispatch it replaced, at identical batch shapes on the same
ivf_pq/rabitq index — QPS / recall@k / steady-state retrace columns
plus the bitwise verdict, then the hybrid dense+sparse ``score_fuse``
plan served end-to-end through the batcher against a fused numpy
oracle. Emits ``PLAN_r20.json`` and exits non-zero if any acceptance
bar fails.

Wired as the optional ``serve_loadgen`` / ``fabric_loadgen`` /
``plan_ab`` stages of ``scripts/r5_measure_all.py`` (pass ``--serve``
there, or select with ``--only``).

Examples:
    python scripts/serve_loadgen.py --n 20000 --dim 64 --algo ivf_flat \
        --concurrency 16 --duration-s 10 --k 1,10,32
    python scripts/serve_loadgen.py --qps 500 --swap-mid-run \
        --obs-snapshot SERVE_r05.obs.json
    python scripts/serve_loadgen.py --fabric --fabric-workers 4 \
        --concurrency 16 --duration-s 30 --k 1,10,100
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _percentiles(lat_ms):
    if not lat_ms:
        return {}
    a = np.asarray(lat_ms)
    return {
        "mean": round(float(a.mean()), 3),
        "p50": round(float(np.percentile(a, 50)), 3),
        "p90": round(float(np.percentile(a, 90)), 3),
        "p99": round(float(np.percentile(a, 99)), 3),
        "max": round(float(a.max()), 3),
        "n": int(a.size),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=20000, help="index rows")
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--algo", default="brute_force",
                    choices=["brute_force", "ivf_flat", "ivf_pq", "cagra"])
    ap.add_argument("--concurrency", type=int, default=8,
                    help="closed-loop worker threads")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="target aggregate QPS (0 = closed loop, no pacing)")
    ap.add_argument("--duration-s", type=float, default=5.0)
    ap.add_argument("--requests", type=int, default=0,
                    help="stop after N completed requests; a time "
                         "failsafe of max(--duration-s, 60s) still "
                         "bounds the run so persistent rejects/errors "
                         "cannot hang it")
    ap.add_argument("--k", default="1,10,32",
                    help="comma list; each request draws one uniformly")
    ap.add_argument("--zipf", type=float, default=0.0,
                    help="query-skew exponent s: requests draw from a "
                         "finite pool of --query-pool distinct queries "
                         "with rank-r probability ~ 1/r^s (0 = every "
                         "request a fresh query). The knob that makes "
                         "the tiered hot-row / result caches "
                         "measurable (docs/serving.md §12)")
    ap.add_argument("--query-pool", type=int, default=512,
                    help="distinct queries behind --zipf sampling")
    ap.add_argument("--tiered", action="store_true",
                    help="serve with the tiered-memory rerank: host-"
                         "resident originals, shortlist-only fetch, "
                         "HBM hot-row cache (forces --algo ivf_pq; "
                         "docs/serving.md §12)")
    ap.add_argument("--refine-ratio", type=int, default=3,
                    help="rerank over-fetch ratio for --tiered")
    ap.add_argument("--hot-rows", type=int, default=None,
                    help="HBM hot-row cache budget (default: the "
                         "tuning.budget('tiered_hot_rows') knob)")
    ap.add_argument("--result-cache", type=int, default=0,
                    help="serve result-cache entries (0 = off)")
    ap.add_argument("--merge-into", default=None,
                    help="also merge the tiered/zipf summary into this "
                         "existing JSON artifact under 'serve_zipf' "
                         "(the TIERED_r12.json acceptance wiring)")
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    help="graft-flow dispatch pipeline depth (tickets "
                         "in flight past async dispatch; 0 = classic "
                         "synchronous dispatch, default: the "
                         "pipeline_depth tuning budget). The report's "
                         "'pipeline' section carries the stall/occupancy "
                         "columns for the depth-0-vs-N overlap A/B")
    ap.add_argument("--max-batch-rows", type=int, default=128)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--max-queue-rows", type=int, default=2048)
    ap.add_argument("--delete-every", type=int, default=0,
                    help="every Nth completed request also deletes one id")
    ap.add_argument("--upsert-every", type=int, default=0,
                    help="every Nth completed request also upserts one row")
    ap.add_argument("--swap-mid-run", action="store_true",
                    help="trigger one background rebuild+hot-swap halfway")
    ap.add_argument("--fabric", action="store_true",
                    help="drive the multi-host fabric (serve.Fabric) "
                         "instead of the single-process Server")
    ap.add_argument("--fabric-workers", type=int, default=3)
    ap.add_argument("--fabric-replication", type=int, default=2)
    ap.add_argument("--fabric-group", default="proc",
                    choices=["proc", "local"],
                    help="worker transport: real processes or the "
                         "in-process thread twin")
    ap.add_argument("--fabric-algo", default="brute_force",
                    choices=["brute_force", "ivf_flat"])
    ap.add_argument("--fault", default=None,
                    help="RAFT_TPU_FAULTS-grammar spec installed in the "
                         "fabric workers (e.g. 'slow@proc:1*50')")
    ap.add_argument("--balance", default=None,
                    choices=["p2c", "primary"],
                    help="fabric replica read balancer (default: the "
                         "FabricParams default, p2c; 'primary' is the "
                         "always-first-owner A/B baseline)")
    ap.add_argument("--chaos-curve", action="store_true",
                    help="the ISSUE 18 self-healing drill (implies "
                         "--fabric): a matched-topology primary-vs-p2c "
                         "balancer A/B, then a scripted "
                         "slow/flap/permanent-dead schedule under a "
                         "running HelmController with a low/high/low "
                         "traffic ramp — coverage timeline, repair "
                         "latency, autoscale events, and oracle checks "
                         "land in FABRIC_r18.json")
    ap.add_argument("--ab-obs", action="store_true",
                    help="fabric only: run an uninstrumented "
                         "(RAFT_TPU_OBS=off) leg first and record the "
                         "off/on QPS pair as the tracing-overhead A/B")
    ap.add_argument("--federate-out", default=None,
                    help="fabric only: archive the end-of-run federated "
                         "fleet metrics snapshot here (JSON; a .prom "
                         "Prometheus exposition lands next to it)")
    ap.add_argument("--adaptive", action="store_true",
                    help="serve with SLO-aware adaptive probing "
                         "(ServeParams.adaptive_probes; docs/serving.md "
                         "§13)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLO deadline (ms); late work is "
                         "shed/downshifted and counted in obs")
    ap.add_argument("--slo-p99-ms", type=float, default=0.0,
                    help="closed-loop SLO mode (ISSUE 14): clustered "
                         "easy/hard query mix, a calibration leg, then "
                         "paced legs at 1x and 2x the measured capacity "
                         "with this p99 target as every request's "
                         "deadline — emits the SLO_r14.json acceptance "
                         "artifact (p99-vs-target, recall band, mean "
                         "probed-list reduction)")
    ap.add_argument("--slo-recall-band", type=float, default=0.01,
                    help="allowed recall loss vs the exhaustive "
                         "baseline in SLO mode")
    ap.add_argument("--easy-frac", type=float, default=0.85,
                    help="fraction of the SLO-mode query pool drawn "
                         "near dataset rows (easy); the rest sit at "
                         "cluster midpoints (ambiguous)")
    ap.add_argument("--n-lists", type=int, default=16,
                    help="IVF lists for the SLO-mode index (the "
                         "exhaustive baseline probes all of them)")
    ap.add_argument("--drift", action="store_true",
                    help="the graft-gauge quality drill (ISSUE 19): a "
                         "loose-margin retune-recovery leg, then a "
                         "crippled-swap probation-rollback leg, both "
                         "closed loop against the shadow-oracle recall "
                         "estimator (docs/serving.md §14) — emits the "
                         "QUALITY_r19.json acceptance artifact")
    ap.add_argument("--quality-rate", type=float, default=1.0,
                    help="shadow-oracle sample rate for --drift")
    ap.add_argument("--quality-band", type=float, default=0.9,
                    help="recall band the --drift monitor defends")
    ap.add_argument("--drift-margin-bp", type=int, default=100,
                    help="loosened serve_probe_margin budget (basis "
                         "points) the retune leg starts from — low "
                         "enough that ambiguous queries read as easy")
    ap.add_argument("--drift-floor-bp", type=int, default=50,
                    help="loosened serve_probe_floor budget (bp) for "
                         "the retune leg")
    ap.add_argument("--plan-ab", action="store_true",
                    help="graft-plan A/B (ISSUE 20): serve through the "
                         "compiled-plan dispatch vs the legacy library "
                         "entry point at identical batch shapes — "
                         "QPS/recall/retrace columns + bitwise verdict, "
                         "plus the hybrid dense+sparse score_fuse plan "
                         "served end-to-end vs a fused numpy oracle "
                         "(PLAN_r20.json)")
    ap.add_argument("--out", default=None,
                    help="report path (default SERVE_r05.json, or "
                         "FABRIC_r13.json with --fabric)")
    ap.add_argument("--obs-snapshot", default=None,
                    help="also write the graft-scope metrics snapshot here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.chaos_curve:
        args.fabric = True

    from raft_tpu import obs, serve

    if args.tiered:
        if args.algo not in ("ivf_pq",):
            args.algo = "ivf_pq"
        if obs.mode() == "off" and not os.environ.get("RAFT_TPU_OBS"):
            # the hit-rate/bytes-moved columns need the metrics
            # registry; same env-wins contract as --obs-snapshot below
            obs.set_mode("on")
    if args.obs_snapshot and obs.mode() == "off":
        # the snapshot needs metrics recording, but an env-selected mode
        # must win: r5_measure_all runs this stage under RAFT_TPU_OBS=
        # flight so a classified fatal mid-run leaves a flight dump —
        # forcing "on" here would silently downgrade that post-mortem
        obs.set_mode("on")

    if args.fabric and obs.mode() == "off" \
            and not os.environ.get("RAFT_TPU_OBS"):
        # the waterfall stage columns need graft-trace recording; same
        # env-wins contract as --obs-snapshot (r5 children run flight)
        obs.set_mode("on")

    ks = sorted({max(1, int(s)) for s in args.k.split(",") if s.strip()})
    rng = np.random.default_rng(args.seed)
    if args.slo_p99_ms > 0:
        if obs.mode() == "off" and not os.environ.get("RAFT_TPU_OBS"):
            obs.set_mode("on")    # rung/shed/miss counters feed the report
        return _run_slo(args, ks, rng, obs, serve)
    if args.drift:
        if obs.mode() == "off" and not os.environ.get("RAFT_TPU_OBS"):
            obs.set_mode("on")    # the recall gauges ARE the drill signal
        return _run_drift(args, ks, rng, obs, serve)
    if args.plan_ab:
        return _run_plan_ab(args, ks, rng, obs, serve)
    dataset = rng.standard_normal((args.n, args.dim)).astype(np.float32)

    if args.out is None:
        args.out = ("FABRIC_r18.json" if args.chaos_curve
                    else "FABRIC_r13.json" if args.fabric
                    else "SERVE_r05.json")
    if args.chaos_curve:
        return _run_chaos_curve(args, ks, dataset, rng, obs, serve)
    if args.fabric:
        return _run_fabric(args, ks, dataset, rng, obs, serve)

    params = serve.ServeParams(
        max_batch_rows=args.max_batch_rows,
        max_wait_ms=args.max_wait_ms,
        max_queue_rows=args.max_queue_rows,
        max_k=max(ks),
        tiered_rerank=args.tiered,
        tiered_hot_rows=args.hot_rows,
        result_cache_entries=args.result_cache,
        adaptive_probes=args.adaptive,
        deadline_ms=args.deadline_ms,
        pipeline_depth=args.pipeline_depth,
    )
    srv = serve.Server(params)
    t_build = time.perf_counter()
    srv.create_index("default", dataset, algo=args.algo,
                     refine_ratio=args.refine_ratio if args.tiered else 1)
    build_s = time.perf_counter() - t_build
    print(f"index up: {args.algo} n={args.n} d={args.dim} "
          f"tiered={args.tiered} zipf={args.zipf} "
          f"(build+warmup {build_s:.1f}s)", flush=True)
    # steady state starts HERE: create_index warmed the whole ladder
    # (buckets x k-rungs x tiered fetch rungs), so any trace-cache
    # growth during the run is a zero-retrace violation worth a column
    traces_before = serve.total_trace_count()

    # --zipf: a finite pool of distinct queries, rank-r probability
    # ~ 1/r^s — the repeated-query head that makes residency and the
    # result cache do work (JUNO's skewed-workload shape)
    qpool = rng.standard_normal(
        (args.query_pool, args.dim)).astype(np.float32)
    zipf_p = None
    if args.zipf > 0:
        ranks = np.arange(1, args.query_pool + 1, dtype=np.float64)
        zipf_p = 1.0 / ranks ** args.zipf
        zipf_p /= zipf_p.sum()

    stop = threading.Event()
    lock = threading.Lock()
    lat_ms: list = []
    per_k = {k: [] for k in ks}
    counts = {"completed": 0, "rejected": 0, "errors": 0,
              "deletes": 0, "upserts": 0}
    # pacing gate for --qps: tokens added by a timer thread
    interval = (args.concurrency / args.qps) if args.qps > 0 else 0.0

    def worker(wid: int):
        wrng = np.random.default_rng(args.seed + 1000 + wid)
        next_t = time.monotonic()
        while not stop.is_set():
            if interval:
                next_t += interval
                pause = next_t - time.monotonic()
                if pause > 0:
                    time.sleep(pause)
            k = int(wrng.choice(ks))
            if zipf_p is not None:
                q = qpool[int(wrng.choice(args.query_pool, p=zipf_p))]
            else:
                q = wrng.standard_normal(args.dim).astype(np.float32)
            t0 = time.perf_counter()
            try:
                d, ids = srv.search(q, k, timeout_s=60.0)
            except serve.Overloaded:
                with lock:
                    counts["rejected"] += 1
                time.sleep(0.001 * (1 + wrng.random()))
                continue
            except Exception:  # noqa: BLE001  # graft-lint: allow-unclassified-swallow loadgen accounting only; the server already classified the failure
                with lock:
                    counts["errors"] += 1
                continue
            ms = (time.perf_counter() - t0) * 1e3
            with lock:
                counts["completed"] += 1
                done = counts["completed"]
                lat_ms.append(ms)
                per_k[k].append(ms)
                if args.requests and done >= args.requests:
                    stop.set()
            if args.delete_every and done % args.delete_every == 0:
                srv.delete([int(wrng.integers(args.n))])
                with lock:
                    counts["deletes"] += 1
            if args.upsert_every and done % args.upsert_every == 0:
                srv.upsert(wrng.standard_normal(args.dim).astype(np.float32),
                           [args.n + done])
                with lock:
                    counts["upserts"] += 1

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(args.concurrency)]
    t_run = time.perf_counter()
    for t in threads:
        t.start()
    swap_version = None
    if args.swap_mid_run:
        time.sleep(args.duration_s / 2)
        print("mid-run hot swap...", flush=True)
        swap_version = srv.swap("default", dataset=dataset,
                                wait=True).result()
    deadline = t_run + (max(args.duration_s, 60.0) if args.requests
                        else args.duration_s)
    while not stop.is_set():
        if time.perf_counter() >= deadline:
            break
        time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    wall_s = time.perf_counter() - t_run

    stats = srv.stats()
    traces_after = serve.total_trace_count()
    snap = obs.snapshot() if obs.enabled() else {"metrics": {}}
    srv.close()

    def _metric(name, **labels):
        want = {str(k): str(v) for k, v in labels.items()}
        for p in snap["metrics"].get(name, {}).get("points", []):
            if all(p["labels"].get(k) == v for k, v in want.items()):
                return p.get("value")
        return None

    lookups = _metric("tiered.lookups_total") or 0
    hbm_hits = _metric("tiered.hits_total", tier="hbm") or 0
    tiered_cols = {
        "zipf_s": args.zipf,
        "query_pool": args.query_pool if args.zipf > 0 else None,
        "hot_hit_rate": (round(hbm_hits / lookups, 4) if lookups
                         else None),
        "hot_lookups": int(lookups),
        "bytes_moved_total": _metric("tiered.bytes_moved_total",
                                     link="host_to_device"),
        "evictions": _metric("tiered.evictions_total") or 0,
        "result_cache_hits": _metric("serve.result_cache_hits_total",
                                     index="default") or 0,
        "result_cache_misses": _metric("serve.result_cache_misses_total",
                                       index="default") or 0,
        "steady_state_retraces": int(traces_after - traces_before),
    }

    def _hist(name, **labels):
        want = {str(k): str(v) for k, v in labels.items()}
        for p in snap["metrics"].get(name, {}).get("points", []):
            if all(p["labels"].get(k) == v for k, v in want.items()):
                return p
        return None

    from raft_tpu.core import pipeline as _gf

    stall = _hist("pipeline.stall_ms", path="serve.dispatch")
    pipe_cols = {
        # backpressure stalls = the batcher blocked on a full ticket
        # queue; run the depth-0 vs depth-N A/B to derive the overlap
        # fraction 1 - stall(N)/stall(0) (docs/observability.md)
        "depth": _gf.resolve_depth(args.pipeline_depth),
        "stall_ms_total": (round(stall["sum"], 1) if stall else 0.0),
        "stalls": (int(stall["count"]) if stall else 0),
        "occupancy": _metric("pipeline.occupancy", path="serve.dispatch"),
    }
    report = {
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": {
            "algo": args.algo, "n": args.n, "dim": args.dim,
            "concurrency": args.concurrency, "qps_target": args.qps,
            "k": ks, "max_batch_rows": args.max_batch_rows,
            "max_wait_ms": args.max_wait_ms,
            "max_queue_rows": args.max_queue_rows,
            "tiered": args.tiered, "refine_ratio": args.refine_ratio,
            "hot_rows": args.hot_rows, "result_cache": args.result_cache,
            "pipeline_depth": pipe_cols["depth"],
            "duration_s": round(wall_s, 2), "build_s": round(build_s, 2),
        },
        "tiered": tiered_cols,
        "pipeline": pipe_cols,
        "throughput_qps": round(counts["completed"] / max(wall_s, 1e-9), 1),
        **counts,
        "swap_generation": swap_version,
        "latency_ms": _percentiles(lat_ms),
        "per_k": {str(k): _percentiles(v) for k, v in per_k.items()},
        "server": stats,
    }
    with open(os.path.join(ROOT, args.out), "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    if args.obs_snapshot:
        obs.write_snapshot(os.path.join(ROOT, args.obs_snapshot))
    if args.merge_into:
        # the TIERED_r12.json acceptance wiring: the serve-level Zipf
        # numbers (hot hit rate, retraces, bytes moved) land in the
        # deep100m artifact as its 'serve_zipf' section
        merge_path = os.path.join(ROOT, args.merge_into)
        try:
            with open(merge_path) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
        merged["serve_zipf"] = {
            "date": report["date"], "artifact": args.out,
            "throughput_qps": report["throughput_qps"],
            **tiered_cols,
        }
        with open(merge_path, "w") as f:
            json.dump(merged, f, indent=1)
            f.write("\n")
        print(f"merged serve_zipf into {args.merge_into}", flush=True)
    # every printed number names its artifact + capture date (the GL005
    # stale-claim contract: a QPS quoted from this output is citable as
    # "<qps> QPS (<date>, <artifact>)" without further archaeology)
    print(json.dumps({**{k: report[k] for k in
                         ("throughput_qps", "completed", "rejected",
                          "latency_ms", "tiered")},
                      "artifact": args.out, "date": report["date"]}),
          flush=True)
    print(f"wrote {args.out} (measured {report['date']})", flush=True)
    return 0


def _slo_pool(args, rng):
    """Clustered dataset + easy/hard query pool for the SLO harness.

    Rows sit in tight clusters (the regime where the coarse margin is
    informative — JUNO's observation that real embeddings are locally
    concentrated); "easy" pool queries perturb dataset rows (large
    margin, low rungs suffice), "hard" ones sit at cluster midpoints
    (ambiguous margin, the policy escapes them to the exhaustive
    rung)."""
    n_centers = max(args.n_lists, 8)
    centers = rng.uniform(-5, 5, (n_centers, args.dim)).astype(np.float32)
    dataset = (centers[rng.integers(0, n_centers, args.n)]
               + 0.2 * rng.standard_normal((args.n, args.dim))
               ).astype(np.float32)
    n_easy = int(round(args.query_pool * args.easy_frac))
    easy = (dataset[rng.integers(0, args.n, n_easy)]
            + 0.05 * rng.standard_normal((n_easy, args.dim)))
    a, b = (rng.integers(0, n_centers, args.query_pool - n_easy)
            for _ in range(2))
    hard = ((centers[a] + centers[b]) / 2
            + 0.2 * rng.standard_normal((args.query_pool - n_easy,
                                         args.dim)))
    pool = np.concatenate([easy, hard]).astype(np.float32)
    return dataset, pool, n_easy


def _drive_slo(srv, serve, pool, oracle, k, args, duration_s,
               qps, deadline_ms, seed):
    """One measurement leg against the adaptive server: closed loop
    when qps=0, paced open loop otherwise; every request carries
    ``deadline_ms`` when set. Returns latencies of COMPLETED requests,
    per-request recall, and the shed/reject/miss split."""
    stop = threading.Event()
    lock = threading.Lock()
    lat_ms, recalls = [], []
    counts = {"completed": 0, "shed_deadline": 0, "rejected_queue": 0,
              "errors": 0}
    interval = (args.concurrency / qps) if qps > 0 else 0.0

    def worker(wid):
        wrng = np.random.default_rng(seed + wid)
        next_t = time.monotonic()
        while not stop.is_set():
            if interval:
                next_t += interval
                pause = next_t - time.monotonic()
                if pause > 0:
                    time.sleep(pause)
            j = int(wrng.integers(pool.shape[0]))
            t0 = time.perf_counter()
            try:
                _, ids = srv.search(pool[j], k, timeout_s=60.0,
                                    deadline_ms=deadline_ms)
            except serve.Overloaded as e:
                with lock:
                    counts["shed_deadline" if e.reason == "deadline"
                           else "rejected_queue"] += 1
                if e.reason != "deadline":
                    time.sleep(0.002 * (1 + wrng.random()))
                continue
            except Exception:  # noqa: BLE001  # graft-lint: allow-unclassified-swallow loadgen accounting only; the server already classified the failure
                with lock:
                    counts["errors"] += 1
                continue
            ms = (time.perf_counter() - t0) * 1e3
            hit = len(set(ids[0].tolist()) & oracle[j]) / k
            with lock:
                counts["completed"] += 1
                lat_ms.append(ms)
                recalls.append(hit)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(args.concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    wall = time.perf_counter() - t0
    return {"counts": counts, "lat_ms": lat_ms, "recalls": recalls,
            "wall_s": wall,
            "qps": round(counts["completed"] / max(wall, 1e-9), 1)}


def _counter_points(obs, name):
    snap = obs.snapshot(runtime_gauges=False)["metrics"]
    return {tuple(sorted(p["labels"].items())): p["value"]
            for p in snap.get(name, {}).get("points", [])}


def _mean_probed(before, after):
    """Mean probed lists per request from the serve.probe_rung counter
    delta (labels carry the rung value)."""
    total = probes = 0.0
    for key, v in after.items():
        d = v - before.get(key, 0.0)
        if d <= 0:
            continue
        rung = int(dict(key)["rung"])
        total += d
        probes += d * rung
    return (probes / total) if total else None


def _run_plan_ab(args, ks, rng, obs, serve) -> int:
    """graft-plan A/B (ISSUE 20; docs/plans.md): the compiled-plan
    serving path vs the legacy library dispatch it replaced, measured
    at identical batch shapes on the SAME index — QPS, recall@k vs
    exact ground truth, steady-state retraces (the GL007 hook), and
    the bitwise verdict the test matrix pins; then the hybrid
    dense+sparse ``score_fuse`` plan served end-to-end through the
    batcher against a fused numpy oracle. Artifact: PLAN_r20.json."""
    from raft_tpu.neighbors import brute_force, hybrid, ivf_pq

    k = max(ks)
    out = args.out or "PLAN_r20.json"
    B = int(min(args.max_batch_rows, 32))
    window_s = max(args.duration_s / 2, 1.0)
    dataset = rng.standard_normal((args.n, args.dim)).astype(np.float32)
    reps = max(1, args.query_pool // B)
    pool = rng.standard_normal((reps * B, args.dim)).astype(np.float32)
    _, ti = brute_force.knn(pool, dataset, k, metric="sqeuclidean")
    truth = np.asarray(ti)

    def recall(ids):
        return float(np.mean([
            len(set(map(int, ids[r])) & set(map(int, truth[r]))) / k
            for r in range(ids.shape[0])]))

    # rabitq + dataset kept: the serving plan is the multi-stage
    # refined_tiered variant — the richest legacy path to A/B against
    bp = ivf_pq.IndexParams(
        n_lists=args.n_lists, pq_dim=max(args.dim // 8, 4),
        metric="sqeuclidean", cache_dtype="rabitq")
    sp = ivf_pq.SearchParams(n_probes=max(4, args.n_lists // 2))

    srv = serve.Server(serve.ServeParams(
        max_batch_rows=B, max_wait_ms=args.max_wait_ms, max_k=k))
    t_build = time.perf_counter()
    srv.create_index("default", dataset, algo="ivf_pq", build_params=bp,
                     search_params=sp, refine_ratio=16)
    build_s = time.perf_counter() - t_build
    h = srv.registry.get("default").handle
    print(f"plan-ab: ivf_pq/rabitq n={args.n} d={args.dim} "
          f"n_lists={args.n_lists} k={k} B={B} "
          f"(build+warmup {build_s:.1f}s)", flush=True)

    def timed(fn):
        # one untimed pass settles one-time shape work AND collects the
        # answer ids; the timed window then loops the pool
        parts = [np.asarray(fn(pool[b * B:(b + 1) * B])[1])
                 for b in range(reps)]
        ids = np.concatenate(parts, axis=0)
        tr0 = serve.total_trace_count()
        rows = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < window_s:
            for b in range(reps):
                fn(pool[b * B:(b + 1) * B])
                rows += B
        dt = time.perf_counter() - t0
        return {"qps": round(rows / dt, 1),
                "recall_at_k": round(recall(ids), 4),
                "retraces": serve.total_trace_count() - tr0}, ids

    plan_col, plan_ids = timed(lambda q: srv.search(q, k))
    rr = h.pipeline_rr()
    legacy_col, legacy_ids = timed(
        lambda q: ivf_pq.search_refined(sp, h.index, q, k,
                                        refine_ratio=rr,
                                        dataset=dataset))
    bitwise = bool(np.array_equal(plan_ids, legacy_ids))
    srv.close()

    # hybrid score_fuse leg: served end-to-end through the batcher,
    # recall vs the fused numpy oracle over the SAME rows
    dd = max(args.dim // 4, 8)
    vocab = args.dim
    n_h = int(min(args.n, 4096))
    hr = np.random.default_rng(args.seed + 5)
    dense = hr.standard_normal((n_h, dd)).astype(np.float32)
    spr = hr.standard_normal((n_h, vocab)).astype(np.float32)
    spr[hr.random((n_h, vocab)) > 0.15] = 0.0
    hx = np.concatenate([dense, spr], axis=1)
    m_h = min(reps * B, 4 * B)
    hq = np.concatenate([
        hr.standard_normal((m_h, dd)).astype(np.float32),
        np.where(hr.random((m_h, vocab)) < 0.2,
                 hr.standard_normal((m_h, vocab)), 0).astype(np.float32),
    ], axis=1)
    wd, ws = 0.8, 1.2
    srv2 = serve.Server(serve.ServeParams(
        max_batch_rows=B, max_wait_ms=args.max_wait_ms, max_k=k))
    fuse_expand = 16  # each leg over-fetches k*16 before the fuse
    srv2.create_index(
        "default", hx, algo="hybrid",
        build_params=hybrid.IndexParams(dense_dim=dd, w_dense=wd,
                                        w_sparse=ws),
        search_params=hybrid.SearchParams(fuse_expand=fuse_expand))
    hyb_parts = []
    for b in range(0, m_h, B):
        hyb_parts.append(np.asarray(srv2.search(hq[b:b + B], k)[1]))
    tr0 = serve.total_trace_count()
    for b in range(0, m_h, B):        # steady-state pass: zero retraces
        srv2.search(hq[b:b + B], k)
    hyb_retraces = serve.total_trace_count() - tr0
    srv2.close()
    hyb_ids = np.concatenate(hyb_parts, axis=0)
    fused = wd * (hq[:, :dd] @ dense.T) + ws * (hq[:, dd:] @ spr.T)
    oids = np.argsort(-fused, axis=1)[:, :k]
    hyb_recall = float(np.mean([
        len(set(map(int, hyb_ids[r])) & set(map(int, oids[r]))) / k
        for r in range(m_h)]))

    acceptance = {
        "bitwise_plan_vs_legacy": bitwise,
        "plan_zero_retraces": plan_col["retraces"] == 0,
        "hybrid_recall_ok": hyb_recall > 0.95,
        "hybrid_zero_retraces": hyb_retraces == 0,
    }
    ok = all(acceptance.values())
    report = {
        "config": {
            "n": args.n, "dim": args.dim, "n_lists": args.n_lists,
            "k": k, "batch_rows": B, "query_pool": reps * B,
            "n_probes": sp.n_probes, "refine_ratio": int(rr),
            "cache": "rabitq+tiered", "window_s": window_s,
            "seed": args.seed,
        },
        "arms": {"plan": plan_col, "legacy": legacy_col},
        "hybrid": {
            "rows": n_h, "dense_dim": dd, "vocab": vocab,
            "queries": m_h, "w_dense": wd, "w_sparse": ws,
            "fuse_expand": fuse_expand,
            "recall_vs_fused_numpy_oracle": round(hyb_recall, 4),
            "retraces_steady_state": hyb_retraces,
        },
        "acceptance": acceptance,
        "pass": ok,
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"arms": report["arms"],
                      "hybrid_recall": round(hyb_recall, 4),
                      "acceptance": acceptance, "pass": ok,
                      "out": out}, indent=1))
    return 0 if ok else 1


def _run_slo(args, ks, rng, obs, serve) -> int:
    """The closed-loop SLO harness (ISSUE 14; ROADMAP item 5
    acceptance): calibrate capacity, then hold a p99 target under 1x
    and 2x overload with per-request deadlines, while tracking recall
    against the exhaustive baseline and the mean probed-list
    reduction. Artifact: SLO_r14.json."""
    from raft_tpu.neighbors import brute_force, ivf_flat

    k = max(ks)
    slo = float(args.slo_p99_ms)
    dataset, pool, n_easy = _slo_pool(args, rng)
    t_build = time.perf_counter()
    index = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=args.n_lists, kmeans_n_iters=10),
        dataset)
    # the exhaustive baseline: the same resolved params serving's
    # non-adaptive default uses (n_probes = n_lists, f32, exact local
    # top-k) — the recall band is measured against THIS
    sp_exh = ivf_flat.SearchParams(n_probes=args.n_lists,
                                   compute_dtype="f32",
                                   local_recall_target=1.0)
    _, gt = brute_force.knn(pool, dataset, k)
    gt = np.asarray(gt)
    oracle = {j: set(gt[j].tolist()) for j in range(pool.shape[0])}
    _, exh_ids = ivf_flat.search(sp_exh, index, pool, k)
    exh_ids = np.asarray(exh_ids)
    recall_exh = float(np.mean([
        len(set(exh_ids[j].tolist()) & oracle[j]) / k
        for j in range(pool.shape[0])]))

    params = serve.ServeParams(
        max_batch_rows=args.max_batch_rows,
        max_wait_ms=args.max_wait_ms,
        max_queue_rows=args.max_queue_rows,
        max_k=k,
        adaptive_probes=True,
        deadline_action="downshift",
    )
    srv = serve.Server(params)
    srv.add_index("default", index, algo="ivf_flat", dataset=dataset)
    build_s = time.perf_counter() - t_build
    print(f"SLO harness up: ivf_flat n={args.n} d={args.dim} "
          f"n_lists={args.n_lists} ladder="
          f"{srv.stats()['probe_ladder']} pool={pool.shape[0]} "
          f"(easy {n_easy}) recall_exh={recall_exh:.4f} "
          f"(build+warmup {build_s:.1f}s)", flush=True)
    traces_before = serve.total_trace_count()

    # leg 0: calibration — closed loop, no deadlines, measures capacity
    cal = _drive_slo(srv, serve, pool, oracle, k, args,
                     max(args.duration_s / 2, 3.0), qps=0.0,
                     deadline_ms=None, seed=args.seed + 100)
    capacity = max(cal["qps"], 1.0)
    print(f"calibration: {capacity} QPS closed-loop "
          f"(p99 {_percentiles(cal['lat_ms']).get('p99')} ms)",
          flush=True)

    legs = {}
    for factor in (1.0, 2.0):
        before_rung = _counter_points(obs, "serve.probe_rung")
        before_miss = _counter_points(obs, "serve.deadline_miss_total")
        before_shed = _counter_points(obs, "serve.deadline_shed_total")
        leg = _drive_slo(srv, serve, pool, oracle, k, args,
                         args.duration_s, qps=capacity * factor,
                         deadline_ms=slo,
                         seed=args.seed + 1000 * int(factor * 10))
        after_rung = _counter_points(obs, "serve.probe_rung")
        lat = _percentiles(leg["lat_ms"])
        shed_d = {
            dict(kk).get("action"): vv - before_shed.get(kk, 0.0)
            for kk, vv in _counter_points(
                obs, "serve.deadline_shed_total").items()}
        miss = sum(_counter_points(
            obs, "serve.deadline_miss_total").values()) - sum(
            before_miss.values())
        mean_probed = _mean_probed(before_rung, after_rung)
        legs[f"{factor:g}x"] = {
            "offered_qps": round(capacity * factor, 1),
            "achieved_qps": leg["qps"],
            **leg["counts"],
            "latency_ms": lat,
            "p99_le_slo": (lat.get("p99") is not None
                           and lat["p99"] <= slo),
            "deadline_miss": int(miss),
            "downshifts": int(shed_d.get("downshift", 0)),
            "recall": (round(float(np.mean(leg["recalls"])), 4)
                       if leg["recalls"] else None),
            "mean_probed_lists": (round(mean_probed, 3)
                                  if mean_probed else None),
        }
        print(f"leg {factor:g}x: {legs[f'{factor:g}x']}", flush=True)

    traces_after = serve.total_trace_count()
    srv.close()
    two = legs["2x"]
    probed_1x = legs["1x"]["mean_probed_lists"]
    reduction = (round(args.n_lists / probed_1x, 2)
                 if probed_1x else None)
    report = {
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": {
            "algo": "ivf_flat", "n": args.n, "dim": args.dim,
            "n_lists": args.n_lists, "k": k,
            "query_pool": int(pool.shape[0]), "easy": n_easy,
            "easy_frac": args.easy_frac,
            "concurrency": args.concurrency,
            "max_batch_rows": args.max_batch_rows,
            "max_wait_ms": args.max_wait_ms,
            "slo_p99_ms": slo, "recall_band": args.slo_recall_band,
            "duration_s": args.duration_s, "seed": args.seed,
        },
        "exhaustive": {"recall": round(recall_exh, 4),
                       "probed_lists": args.n_lists},
        "capacity_qps": capacity,
        "legs": legs,
        "steady_state_retraces": int(traces_after - traces_before),
        "acceptance": {
            "slo_held_2x_overload": bool(two["p99_le_slo"]),
            "recall_within_band": bool(
                two["recall"] is not None
                and two["recall"] >= recall_exh - args.slo_recall_band),
            "probed_reduction_vs_exhaustive": reduction,
            "probed_reduction_ge_4x": bool(reduction is not None
                                           and reduction >= 4.0),
            "zero_retraces": traces_after == traces_before,
        },
    }
    out = args.out or "SLO_r14.json"
    with open(os.path.join(ROOT, out), "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    if args.obs_snapshot:
        obs.write_snapshot(os.path.join(ROOT, args.obs_snapshot))
    # GL005 contract: every number this prints is citable with its
    # artifact + capture date
    print(json.dumps({"acceptance": report["acceptance"],
                      "capacity_qps": capacity,
                      "p99_2x": two["latency_ms"].get("p99"),
                      "artifact": out, "date": report["date"]}),
          flush=True)
    print(f"wrote {out} (measured {report['date']})", flush=True)
    return 0


def _run_drift(args, ks, rng, obs, serve) -> int:
    """The graft-gauge closed-loop quality drill (ISSUE 19; ROADMAP
    item 9 acceptance; docs/serving.md §14): two legs over clustered
    data with hard between-cluster queries, one per actuator of the
    online recall estimator.

    * **retune leg** — the ``serve_probe_margin``/``serve_probe_floor``
      budgets are seeded DOWN to ``--drift-margin-bp`` /
      ``--drift-floor-bp``, so the adaptive policy reads ambiguous
      queries as easy and serves them at the minimum rung; the pooled
      Wilson upper bound falls below the band (a proven breach, not a
      wobble) and the monitor's bounded tighten steps must walk recall
      back inside it — no human in the loop, zero new traces.
    * **rollback leg** — fresh budgets, retune disabled; a healthy
      baseline generation is hot-swapped for one pinned to
      ``n_probes=1``; the swap-probation window must convict the swap
      against the predecessor's pinned baseline, republish the healthy
      handle as a fresh monotone generation, and recover in-band.

    Artifact: QUALITY_r19.json (per-leg estimator timelines, action
    logs with evidence, acceptance booleans)."""
    from raft_tpu import tuning
    from raft_tpu.neighbors import ivf_flat

    k = max(ks)
    band = args.quality_band
    out = args.out or "QUALITY_r19.json"

    # tight clusters + between-cluster midpoint queries: the regime
    # where a too-loose margin policy measurably under-recalls (the
    # truth set splits across lists) yet the exhaustive oracle rung
    # still scores 1.0 — recall loss is attributable, not noise
    n_centers = max(args.n_lists, 8)
    centers = (5.0 * rng.standard_normal((n_centers, args.dim))
               ).astype(np.float32)
    per = max(args.n // n_centers, 8)
    dataset = np.concatenate(
        [c + rng.standard_normal((per, args.dim)).astype(np.float32)
         for c in centers], axis=0)
    a, b = (rng.integers(0, n_centers, (args.query_pool,))
            for _ in range(2))
    hard = ((centers[a] + centers[b]) / 2
            + 0.5 * rng.standard_normal((args.query_pool, args.dim))
            ).astype(np.float32)

    def qparams(**kw):
        return serve.ServeParams(
            max_batch_rows=16, max_wait_ms=0.2, max_k=max(k, 16),
            adaptive_probes=True,
            quality_sample_rate=args.quality_rate,
            quality_band=band, quality_min_samples=8,
            quality_window=16, **kw)

    def run_leg(srv, done, deadline_s, wrng, label, timeline):
        """Drive hard-query traffic until ``done(quality_stats)`` or
        the deadline, sampling the estimator into ``timeline``."""
        t0 = time.monotonic()
        st = srv.stats("t")["quality"]
        converged = done(st)
        while not converged and time.monotonic() - t0 < deadline_s:
            for _ in range(8):
                srv.submit(hard[wrng.integers(0, hard.shape[0], (4,))],
                           k=k, index="t").result(timeout=60.0)
                time.sleep(0.002)
            st = srv.stats("t")["quality"]
            timeline.append({
                "t_s": round(time.monotonic() - t0, 2),
                "estimate": st["estimate"],
                "ci_low": st["ci_low"], "ci_high": st["ci_high"],
                "samples": st["samples"],
                "retune_steps": st["retune_steps"],
                "generation": srv.generation("t"),
            })
            converged = done(st)
        print(f"{label}: {'converged' if converged else 'DEADLINE'} "
              f"after {time.monotonic() - t0:.1f}s — est="
              f"{st['estimate']} ci=[{st['ci_low']}, {st['ci_high']}] "
              f"steps={st['retune_steps']} "
              f"actions={[x[0] for x in st['actions']]}", flush=True)
        return st, converged

    deadline_s = max(args.duration_s * 4, 120.0)
    build_params = ivf_flat.IndexParams(n_lists=args.n_lists)

    # ---- leg 1: margin drift -> bounded retune recovery --------------
    tuning.record_budget("serve_probe_margin", args.drift_margin_bp)
    tuning.record_budget("serve_probe_floor", args.drift_floor_bp)
    wrng = np.random.default_rng(args.seed + 101)
    t_build = time.perf_counter()
    srv = serve.Server(qparams(quality_rollback=False))
    srv.create_index("t", dataset, algo="ivf_flat",
                     build_params=build_params)
    print(f"retune leg up: ivf_flat n={dataset.shape[0]} d={args.dim} "
          f"n_lists={args.n_lists} margins seeded to "
          f"{args.drift_margin_bp}/{args.drift_floor_bp}bp "
          f"(build+warmup {time.perf_counter() - t_build:.1f}s)",
          flush=True)
    traces0 = serve.total_trace_count()
    tl_retune: list = []
    st_r, retune_ok = run_leg(
        srv,
        lambda s: (s["retune_steps"] > 0 and s["estimate"] is not None
                   and s["samples"] >= 8 and s["estimate"] >= band),
        deadline_s, wrng, "retune", tl_retune)
    retune_traces = int(serve.total_trace_count() - traces0)
    max_retunes = qparams().quality_max_retunes
    srv.close()
    tuning.reload()        # the next leg starts from healthy defaults
    breach_r = min((p["ci_high"] for p in tl_retune
                    if p["ci_high"] is not None), default=None)

    # ---- leg 2: crippled hot-swap -> probation rollback --------------
    wrng = np.random.default_rng(args.seed + 202)
    t_build = time.perf_counter()
    srv = serve.Server(qparams(quality_retune=False))
    srv.create_index("t", dataset, algo="ivf_flat",
                     build_params=build_params)
    print(f"rollback leg up (build+warmup "
          f"{time.perf_counter() - t_build:.1f}s)", flush=True)
    tl_roll: list = []
    base_st, base_ok = run_leg(
        srv,
        lambda s: (s["estimate"] is not None and s["samples"] >= 8
                   and s["estimate"] >= band),
        deadline_s, wrng, "rollback-baseline", tl_roll)
    gen_healthy = srv.generation("t")
    # one probe cannot cover between-cluster queries; its own pinned
    # exhaustive oracle convicts it against the predecessor's baseline
    srv.swap("t", dataset=dataset,
             search_params=ivf_flat.SearchParams(n_probes=1), wait=True)
    gen_swapped = srv.generation("t")
    t_swap = time.monotonic()
    traces1 = serve.total_trace_count()
    st_b, rolled = run_leg(
        srv, lambda s: any(x[0] == "rollback" for x in s["actions"]),
        deadline_s, wrng, "rollback", tl_roll)
    detect_s = round(time.monotonic() - t_swap, 2)
    rb_detail = None
    kinds = [x[0] for x in st_b["actions"]]
    if "rollback" in kinds:
        rb_detail = dict(st_b["actions"][kinds.index("rollback")][1])
    st_b2, recovered = run_leg(
        srv, lambda s: (s["estimate"] is not None
                        and s["estimate"] >= band),
        deadline_s, wrng, "rollback-recovery", tl_roll)
    roll_traces = int(serve.total_trace_count() - traces1)
    gen_final = srv.generation("t")
    srv.close()
    tuning.reload()

    acceptance = {
        # the retune leg's breach must be PROVEN (ci_high under the
        # band), the recovery in-band, the steps bounded, and the whole
        # episode free of new trace compilation
        "retune_drift_proven": bool(breach_r is not None
                                    and breach_r < band),
        "retune_recovered_in_band": bool(retune_ok),
        "retune_steps_bounded": bool(
            0 < st_r["retune_steps"] <= max_retunes),
        "retune_zero_retraces": retune_traces == 0,
        "rollback_convicted_swap": bool(rolled),
        "rollback_detect_s": detect_s if rolled else None,
        "rollback_versions_monotone": bool(gen_final > gen_swapped
                                           > gen_healthy),
        "rollback_recovered_in_band": bool(recovered),
        "rollback_zero_retraces": roll_traces == 0,
    }
    ok = all(v for kk, v in acceptance.items()
             if kk != "rollback_detect_s")
    report = {
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": {
            "algo": "ivf_flat", "n": int(dataset.shape[0]),
            "dim": args.dim, "n_lists": args.n_lists, "k": k,
            "query_pool": args.query_pool,
            "quality_rate": args.quality_rate, "band": band,
            "quality_window": 16, "quality_min_samples": 8,
            "drift_margin_bp": args.drift_margin_bp,
            "drift_floor_bp": args.drift_floor_bp,
            "seed": args.seed,
        },
        "retune": {
            "actions": st_r["actions"],
            "retune_steps": st_r["retune_steps"],
            "max_retunes": max_retunes,
            "min_ci_high_seen": breach_r,
            "final": {"estimate": st_r["estimate"],
                      "ci_low": st_r["ci_low"],
                      "ci_high": st_r["ci_high"]},
            "new_traces": retune_traces,
            "timeline": tl_retune,
        },
        "rollback": {
            "baseline_estimate": base_st["estimate"],
            "baseline_in_band": bool(base_ok),
            "generations": {"healthy": gen_healthy,
                            "swapped": gen_swapped,
                            "final": gen_final},
            "detect_s": detect_s if rolled else None,
            "evidence": rb_detail,
            "actions": st_b2["actions"],
            "final": {"estimate": st_b2["estimate"],
                      "ci_low": st_b2["ci_low"],
                      "ci_high": st_b2["ci_high"]},
            "new_traces": roll_traces,
            "timeline": tl_roll,
        },
        "acceptance": acceptance,
        "pass": bool(ok),
    }
    with open(os.path.join(ROOT, out), "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    if args.obs_snapshot:
        obs.write_snapshot(os.path.join(ROOT, args.obs_snapshot))
    # GL005 contract: every number this prints is citable with its
    # artifact + capture date
    print(json.dumps({"acceptance": acceptance, "pass": bool(ok),
                      "artifact": out, "date": report["date"]}),
          flush=True)
    print(f"wrote {out} (measured {report['date']})", flush=True)
    return 0 if ok else 1


def _drive_fabric(fab, args, ks, duration_s, seed_base, serve,
                  swap_mid_run=False, dataset=None):
    """One closed-loop/paced measurement leg against ``fab``; returns
    the raw counters/latencies so a leg can run twice (the --ab-obs
    off/on pair) without duplicating the loop."""
    stop = threading.Event()
    lock = threading.Lock()
    lat_ms: list = []
    per_k = {k: [] for k in ks}
    cov_sum = [0.0]
    cov_min = [1.0]
    counts = {"completed": 0, "degraded": 0, "errors": 0}
    interval = (args.concurrency / args.qps) if args.qps > 0 else 0.0

    def worker(wid: int):
        wrng = np.random.default_rng(seed_base + wid)
        next_t = time.monotonic()
        while not stop.is_set():
            if interval:
                next_t += interval
                pause = next_t - time.monotonic()
                if pause > 0:
                    time.sleep(pause)
            k = int(wrng.choice(ks))
            q = wrng.standard_normal((1, args.dim)).astype(np.float32)
            t0 = time.perf_counter()
            try:
                d, ids, cov = fab.search(q, k)
            except Exception:  # noqa: BLE001  # graft-lint: allow-unclassified-swallow loadgen accounting only; the fabric already classified the failure
                with lock:
                    counts["errors"] += 1
                continue
            ms = (time.perf_counter() - t0) * 1e3
            c = float(cov.min()) if cov.size else 1.0
            with lock:
                counts["completed"] += 1
                done = counts["completed"]
                lat_ms.append(ms)
                per_k[k].append(ms)
                cov_sum[0] += c
                cov_min[0] = min(cov_min[0], c)
                if c < 1.0:
                    counts["degraded"] += 1
                if args.requests and done >= args.requests:
                    stop.set()

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(args.concurrency)]
    t_run = time.perf_counter()
    for t in threads:
        t.start()
    swap_generation = None
    if swap_mid_run:
        time.sleep(duration_s / 2)
        print("mid-run cluster hot swap...", flush=True)
        try:
            swap_generation = fab.swap(dataset)
        except serve.FabricSwapError as e:
            print(f"swap rolled back: {e}", flush=True)
            swap_generation = "aborted"
    deadline = t_run + (max(duration_s, 60.0) if args.requests
                        else duration_s)
    while not stop.is_set():
        if time.perf_counter() >= deadline:
            break
        time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    return {
        "counts": counts, "lat_ms": lat_ms, "per_k": per_k,
        "cov_sum": cov_sum[0], "cov_min": cov_min[0],
        "wall_s": time.perf_counter() - t_run,
        "swap_generation": swap_generation,
    }


def _waterfall_columns(obs):
    """The graft-trace stage-attribution columns (ISSUE 13): per-stage
    p50/p99 + hedge wins over the run's completed waterfalls, and the
    complete-waterfall fraction — the SAME
    ``obs.trace.waterfall_complete`` predicate the chaos acceptance
    test asserts, so the artifact and the test cannot diverge. The
    ring-eviction count rides along: a run faster than the bounded
    ring's window must say so instead of presenting the tail as the
    whole run."""
    from raft_tpu.obs.trace import (ring_stats, stage_stats,
                                    waterfall_complete)

    wfs = [w for w in obs.trace_report()
           if w.get("entry") == "fabric.search"]
    answered = [w for w in wfs if w.get("status") in ("ok", "degraded")]
    complete = sum(1 for w in answered if waterfall_complete(w))
    ring = ring_stats()
    return {
        "waterfalls": len(wfs),
        "answered": len(answered),
        "complete": complete,
        "complete_fraction": (round(complete / len(answered), 5)
                              if answered else None),
        "ring_evicted": ring["evicted"],
        "window": ("ring_tail" if ring["evicted"] else "full_run"),
        "stages": stage_stats(wfs),
    }


def _run_fabric(args, ks, dataset, rng, obs, serve) -> int:
    """The --fabric leg: closed-loop/paced load against a
    :class:`raft_tpu.serve.Fabric`, FABRIC_r13.json sidecar out."""
    params = serve.FabricParams(
        n_workers=args.fabric_workers,
        replication=args.fabric_replication,
        worker_algo=args.fabric_algo,
        **({"balance": args.balance} if args.balance else {}),
    )
    obs_ab = None
    if args.ab_obs:
        # the instrumentation-overhead A/B (the <5% acceptance bar):
        # three swap-free, FAULT-FREE probe legs on fresh fabrics —
        # off, on, off — each duration_s/2. Bracketing the instrumented
        # leg between two uninstrumented ones cancels linear machine
        # drift, and the probes deliberately skip --fault: injected
        # deaths and hedge storms add per-leg randomness far above the
        # few-percent effect being measured (single chaos off/on pairs
        # measured anywhere from -15% to +13% run-to-run on this shared
        # CPU host, r13). Workers inherit each leg's mode at spawn, so
        # the whole path (router stages + worker spans + RPC trace
        # field) flips with the leg. The swap/chaos columns come from
        # the MAIN run below, which is not part of the A/B.
        on_mode = obs.mode() if obs.mode() != "off" else "on"

        def _ab_leg(idx: int, mode: str) -> float:
            obs.set_mode(mode)
            fab = serve.Fabric(dataset, params=params,
                               group=args.fabric_group)
            leg = _drive_fabric(fab, args, ks, args.duration_s / 2,
                                args.seed + 5000 + 100 * idx, serve)
            fab.close()
            qps = leg["counts"]["completed"] / max(leg["wall_s"], 1e-9)
            print(f"A/B leg {idx} ({mode}): {qps:.1f} QPS", flush=True)
            return qps

        off1 = _ab_leg(1, "off")
        on1 = _ab_leg(2, on_mode)
        off2 = _ab_leg(3, "off")
        obs.set_mode(on_mode)
        qps_off = (off1 + off2) / 2
        obs_ab = {
            "mode_off_qps": round(qps_off, 1),
            "off_leg_qps": [round(off1, 1), round(off2, 1)],
            "mode_on": on_mode,
            "mode_on_qps": round(on1, 1),
            "overhead_fraction": (round(1.0 - on1 / qps_off, 4)
                                  if qps_off else None),
        }
        print(f"A/B: off {qps_off:.1f} (bracket {off1:.1f}/{off2:.1f}) "
              f"vs {on_mode} {on1:.1f} QPS, overhead "
              f"{obs_ab['overhead_fraction']}", flush=True)

    t_build = time.perf_counter()
    fab = serve.Fabric(dataset, params=params, group=args.fabric_group,
                       fault_spec=args.fault)
    build_s = time.perf_counter() - t_build
    print(f"fabric up: {args.fabric_workers} workers x "
          f"{args.fabric_replication} replicas, {args.fabric_algo} "
          f"n={args.n} d={args.dim} (spawn+load {build_s:.1f}s)",
          flush=True)
    # FULL obs reset (metrics + spans + flight + trace): the A/B probe
    # legs and the fabric build otherwise leave their counters and
    # histograms in the router registry, and the --obs-snapshot /
    # --federate-out artifacts would report ~1.5x the main run's
    # traffic — the columns must describe the run they ship with
    if obs.enabled():
        obs.reset()

    leg = _drive_fabric(fab, args, ks, args.duration_s, args.seed + 1000,
                        serve, swap_mid_run=args.swap_mid_run,
                        dataset=dataset)
    counts, lat_ms, per_k = leg["counts"], leg["lat_ms"], leg["per_k"]
    wall_s, swap_generation = leg["wall_s"], leg["swap_generation"]
    cov_sum = [leg["cov_sum"]]
    cov_min = [leg["cov_min"]]

    waterfall = _waterfall_columns(obs) if obs.enabled() else None
    federated = None
    if args.federate_out:
        fed = fab.collect_metrics()
        fed_path = os.path.join(ROOT, args.federate_out)
        os.makedirs(os.path.dirname(fed_path) or ".", exist_ok=True)
        with open(fed_path, "w") as f:
            json.dump(fed, f, indent=1, default=str)
            f.write("\n")
        prom_path = os.path.splitext(fed_path)[0] + ".prom"
        with open(prom_path, "w") as f:
            f.write(obs.federation.render_prometheus(fed["metrics"]))
        federated = {"json": args.federate_out,
                     "prom": os.path.relpath(prom_path, ROOT),
                     "workers": fed["workers"],
                     "worker_health": fed.get("worker_health")}
        print(f"wrote federated snapshot {args.federate_out}", flush=True)

    stats = fab.stats()
    fab.close()
    done = counts["completed"]
    report = {
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": {
            "mode": "fabric", "algo": args.fabric_algo, "n": args.n,
            "dim": args.dim, "workers": args.fabric_workers,
            "replication": args.fabric_replication,
            "group": args.fabric_group, "fault": args.fault,
            "balance": params.balance,
            "concurrency": args.concurrency, "qps_target": args.qps,
            "k": ks, "duration_s": round(wall_s, 2),
            "build_s": round(build_s, 2),
        },
        "throughput_qps": round(done / max(wall_s, 1e-9), 1),
        **counts,
        "swap_generation": swap_generation,
        "latency_ms": _percentiles(lat_ms),
        "per_k": {str(k): _percentiles(v) for k, v in per_k.items()},
        "coverage": {
            "mean": round(cov_sum[0] / done, 5) if done else None,
            "min": round(cov_min[0], 5) if done else None,
        },
        "hedges": stats["counters"].get("hedges", 0),
        "retries": stats["counters"].get("retries", 0),
        "dropouts": stats["counters"].get("dropouts", 0),
        "waterfall": waterfall,
        "obs_ab": obs_ab,
        "federated": federated,
        "fabric": stats,
    }
    with open(os.path.join(ROOT, args.out), "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    if args.obs_snapshot:
        obs.write_snapshot(os.path.join(ROOT, args.obs_snapshot))
    # artifact + date ride the summary line (GL005 contract — see the
    # single-process leg)
    print(json.dumps({**{k: report[k] for k in
                         ("throughput_qps", "completed", "coverage",
                          "hedges", "dropouts", "latency_ms")},
                      "waterfall_complete_fraction":
                          (waterfall or {}).get("complete_fraction"),
                      "obs_ab": obs_ab,
                      "artifact": args.out, "date": report["date"]}),
          flush=True)
    print(f"wrote {args.out} (measured {report['date']})", flush=True)
    return 0


def _chaos_oracle(dataset, q, k, n_shards):
    """The surviving-owner oracle: the same per-shard build + merge the
    workers run, so a full-coverage fabric answer must match BITWISE
    (identical tie-breaking, identical reduction order)."""
    from raft_tpu.comms import procgroup
    from raft_tpu.serve import fabric as fabmod

    bounds = fabmod.shard_bounds(dataset.shape[0], n_shards)
    results = {}
    for s in range(n_shards):
        entry = procgroup.build_shard_entry(
            dataset[bounds[s]:bounds[s + 1]], bounds[s], "brute_force")
        d, i = procgroup.search_shard_entry(entry, q, k)
        results[s] = (0, d, i)
    d, i, _ = fabmod.merge_shard_results(n_shards, results, q.shape[0], k)
    return d, i


def _run_chaos_curve(args, ks, dataset, rng, obs, serve) -> int:
    """--chaos-curve (ISSUE 18): the self-healing acceptance drill.

    Leg 1 — balancer A/B: two fault-free fabrics at MATCHED topology,
    identical seeds, ``balance="primary"`` vs ``"p2c"`` — the p2c
    replica read balancer must win on throughput.

    Leg 2 — the chaos curve: one fabric under a scripted spawn-time
    schedule (``#after:N`` delays — one transient-slow worker, one
    flapping worker, one PERMANENTLY dead worker) with a
    :class:`~raft_tpu.serve.HelmController` closing the repair and
    autoscale loops, driven by a low/high/low closed-loop traffic ramp.
    A sampler thread records the coverage/membership timeline; after a
    bounded settle the report asserts coverage back at 1.0, replication
    restored over the survivors (dead rank evicted, flapping rank
    healed in place), zero mixed-generation answers, bitwise oracle
    agreement on full-coverage samples, and a grew-then-shrank
    autoscale trace with no thrash."""
    import copy

    from raft_tpu.serve.controller import HelmController, HelmParams
    from raft_tpu.serve.fabric import CLOSED

    W, R = args.fabric_workers, args.fabric_replication
    if W < 3:
        print("--chaos-curve needs --fabric-workers >= 3 (one slow, one "
              "flapping, one dead rank)", flush=True)
        return 2

    def _params(balance):
        return serve.FabricParams(
            n_workers=W, replication=R, worker_algo=args.fabric_algo,
            balance=balance)

    # -- leg 1: the balancer A/B at matched topology, fault-free ------------
    ab_qps = {}
    for balance in ("primary", "p2c"):
        fab = serve.Fabric(dataset, params=_params(balance),
                           group=args.fabric_group)
        leg = _drive_fabric(fab, args, ks, args.duration_s / 2,
                            args.seed + 7000, serve)
        fab.close()
        qps = leg["counts"]["completed"] / max(leg["wall_s"], 1e-9)
        ab_qps[balance] = round(qps, 1)
        print(f"balance A/B {balance}: {qps:.1f} QPS", flush=True)
    balance_ab = {
        "primary_qps": ab_qps["primary"],
        "p2c_qps": ab_qps["p2c"],
        "speedup": (round(ab_qps["p2c"] / ab_qps["primary"], 4)
                    if ab_qps["primary"] else None),
        "p2c_wins": ab_qps["p2c"] > ab_qps["primary"],
    }

    # -- leg 2: the chaos curve under the helm ------------------------------
    # early arming delays: the repair story should resolve during the
    # ramp, not after it — and the rebalance budget must exceed one
    # respawn + readmission round trip (process spawn + imports + shard
    # rebuild, seconds on a busy host), or a respawned worker is
    # evicted while it is still booting
    slow_rank, flap_rank, dead_rank = 0, W - 2, W - 1
    fault = (f"slow@proc:{slow_rank}#after:10*12,"
             f"flap@proc:{flap_rank}#after:60*2,"
             f"dead@proc:{dead_rank}#after:20")
    if obs.enabled():
        obs.reset()
    t_build = time.perf_counter()
    fab = serve.Fabric(dataset, params=_params(args.balance or "p2c"),
                       group=args.fabric_group, fault_spec=fault)
    build_s = time.perf_counter() - t_build
    helm = HelmController(fab, params=HelmParams(
        interval_s=0.05,
        rebalance_budget_ms=6000.0,
        restart_budget=2,
        # floor at the provisioned topology: the ramp's shrink releases
        # SURGE capacity only (and an eviction under the floor admits a
        # replacement, restoring both replication and capacity)
        min_workers=W,
        max_workers=W + 2,
        scale_up_inflight=2.0,
        scale_down_inflight=0.75,
        sustain_ticks=4,
        cooldown_s=1.0,
        retire_timeout_s=20.0,
    ))
    print(f"chaos fabric up: {W} workers x {R} replicas "
          f"(spawn+load {build_s:.1f}s), faults '{fault}'", flush=True)

    timeline: list = []
    t0 = time.monotonic()
    stop_sample = threading.Event()

    def sampler():
        while not stop_sample.is_set():
            now = time.monotonic()
            open_eps = fab.open_episodes(now)
            snap = fab.load_snapshot()
            active = fab.active_ranks()
            cov = fab.coverage_ewma()
            timeline.append({
                "t_s": round(now - t0, 3),
                "active": active,
                "open": sorted(r for r, e in open_eps.items() if e > 0.0),
                "coverage_ewma": (round(cov, 5) if cov is not None
                                  else None),
                "mean_inflight": round(
                    sum(snap["inflight"].get(r, 0) for r in active)
                    / max(len(active), 1), 3),
                "generation": fab.generation(),
            })
            stop_sample.wait(0.25)

    sampler_t = threading.Thread(target=sampler, daemon=True)
    helm.start()
    sampler_t.start()

    # closed-loop traffic ramp: low -> high (the scale-up window) ->
    # low (the scale-down window); each phase reuses the standard
    # measurement leg against the SAME fabric while the helm runs
    low_c = max(2, args.concurrency // 4)
    phases = [
        ("ramp_low", low_c, args.duration_s * 0.5),
        ("ramp_high", max(args.concurrency, 16), args.duration_s),
        ("ramp_cool", 1, args.duration_s * 0.5),
    ]
    ver_rng = np.random.default_rng(args.seed + 1234)
    oracle = {"checked": 0, "mismatches": 0, "degraded_skipped": 0}

    def _oracle_sample(n_queries):
        k = int(max(ks))
        for _ in range(n_queries):
            q = ver_rng.standard_normal((1, args.dim)).astype(np.float32)
            try:
                d, ids, cov = fab.search(q, k)
            except Exception:  # noqa: BLE001  # graft-lint: allow-unclassified-swallow sampling only; the fabric already classified the failure
                continue
            if float(cov.min()) < 1.0:
                oracle["degraded_skipped"] += 1
                continue
            od, oi = _chaos_oracle(dataset, q, k, fab.n_shards)
            oracle["checked"] += 1
            if not (np.array_equal(ids, oi) and np.array_equal(d, od)):
                oracle["mismatches"] += 1

    phase_rows = []
    for i, (name, conc, dur) in enumerate(phases):
        pa = copy.copy(args)
        pa.concurrency = int(conc)
        pa.requests = 0
        pa.qps = 0.0
        leg = _drive_fabric(fab, pa, ks, dur,
                            args.seed + 9000 + 100 * i, serve)
        done = leg["counts"]["completed"]
        phase_rows.append({
            "phase": name, "concurrency": int(conc),
            "qps": round(done / max(leg["wall_s"], 1e-9), 1),
            **leg["counts"],
            "cov_min": round(leg["cov_min"], 5),
            "p99_ms": _percentiles(leg["lat_ms"]).get("p99"),
        })
        _oracle_sample(8)   # between-phase spot checks, chaos included
        print(f"phase {name} (c={conc}): {phase_rows[-1]['qps']} QPS, "
              f"cov_min {phase_rows[-1]['cov_min']}", flush=True)

    # bounded settle: let the repair loop finish (respawns, eviction,
    # replacement admission) and the breakers re-close
    settle_deadline = time.monotonic() + 30.0
    while time.monotonic() < settle_deadline:
        active = fab.active_ranks()
        if active and all(fab.health[r].state == CLOSED for r in active) \
                and all(e <= 0.0 for e in fab.open_episodes().values()):
            break
        time.sleep(0.2)
    _oracle_sample(24)      # post-repair: every sample full-coverage
    waterfall = _waterfall_columns(obs) if obs.enabled() else None
    stats = fab.stats()
    helm_stats = helm.stats()
    helm.stop()
    stop_sample.set()
    sampler_t.join(timeout=5)

    actions = [{"t_s": round(a["t"] - t0, 3), "action": a["action"],
                "worker": a["worker"]} for a in helm_stats["actions"]]
    cur = fab.registry.get(fab.name)
    owners = (dict(cur.handle.owners)
              if cur is not None and cur.handle is not None else {})
    fab.close()

    active = stats["members"]
    active = [r for r in active if r not in stats["retired"]]
    want_repl = min(R, len(active))
    replication_ok = bool(owners) and all(
        len(set(o)) == want_repl
        and all(r not in stats["retired"] for r in o)
        for o in owners.values())
    first_fault_t = min(
        (s["t_s"] for s in timeline if s["open"]), default=None)
    repair_actions = [a for a in actions
                     if a["action"] in ("respawn", "evict", "admit")]
    last_repair_t = max((a["t_s"] for a in repair_actions),
                        default=first_fault_t)
    repaired_t = None
    if first_fault_t is not None:
        for s in timeline:
            if s["t_s"] >= (last_repair_t or 0.0) and not s["open"] \
                    and (s["coverage_ewma"] or 0.0) >= 0.999:
                repaired_t = s["t_s"]
                break
    ups = [a["t_s"] for a in actions if a["action"] == "scale_up"]
    downs = [a["t_s"] for a in actions if a["action"] == "scale_down"]
    respawns = helm_stats["restarts"]
    final_cov = next((s["coverage_ewma"] for s in reversed(timeline)
                      if s["coverage_ewma"] is not None), None)
    acceptance = {
        "p2c_beats_primary": balance_ab["p2c_wins"],
        "coverage_restored": repaired_t is not None,
        "final_coverage_ewma": final_cov,
        "time_to_repair_s": (round(repaired_t - first_fault_t, 3)
                             if repaired_t is not None
                             and first_fault_t is not None else None),
        "replication_restored": replication_ok,
        "evicted": helm_stats["evicted"],
        "evicted_only_dead": helm_stats["evicted"] == [dead_rank],
        "flap_healed_in_place": (flap_rank in active
                                 and respawns.get(flap_rank, 0) >= 1),
        "mixed_gen": stats["counters"].get("mixed_gen", 0),
        "oracle": oracle,
        "grew_then_shrank": (bool(ups) and bool(downs)
                             and min(ups) < max(downs)),
        "scale_actions": len(ups) + len(downs),
        "no_thrash": (len(ups) + len(downs) <= 4
                      and all(n <= 2 for n in respawns.values())),
    }
    ok = (acceptance["p2c_beats_primary"]
          and acceptance["coverage_restored"]
          and acceptance["replication_restored"]
          and acceptance["evicted_only_dead"]
          and acceptance["flap_healed_in_place"]
          and acceptance["mixed_gen"] == 0
          and oracle["checked"] > 0 and oracle["mismatches"] == 0
          and acceptance["grew_then_shrank"]
          and acceptance["no_thrash"])

    report = {
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "config": {
            "mode": "chaos_curve", "algo": args.fabric_algo,
            "n": args.n, "dim": args.dim, "workers": W,
            "replication": R, "group": args.fabric_group,
            "balance": args.balance or "p2c", "fault": fault,
            "k": ks, "duration_s": args.duration_s,
            "build_s": round(build_s, 2), "seed": args.seed,
        },
        "balance_ab": balance_ab,
        "phases": phase_rows,
        "helm": {"ticks": helm_stats["ticks"],
                 "restarts": respawns,
                 "evicted": helm_stats["evicted"],
                 "actions": actions,
                 "rebalance_budget_ms":
                     helm_stats["rebalance_budget_ms"]},
        "fabric": stats,
        "owners": {str(s): list(o) for s, o in sorted(owners.items())},
        "timeline": timeline,
        "waterfall": waterfall,
        "acceptance": acceptance,
        "pass": ok,
    }
    with open(os.path.join(ROOT, args.out), "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    if args.obs_snapshot:
        obs.write_snapshot(os.path.join(ROOT, args.obs_snapshot))
    # artifact + date ride the summary line (GL005 contract)
    print(json.dumps({"pass": ok, "balance_ab": balance_ab,
                      "acceptance": {k: acceptance[k] for k in
                                     ("time_to_repair_s", "evicted",
                                      "mixed_gen", "grew_then_shrank",
                                      "no_thrash")},
                      "oracle": oracle,
                      "artifact": args.out, "date": report["date"]}),
          flush=True)
    print(f"wrote {args.out} (measured {report['date']})", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
