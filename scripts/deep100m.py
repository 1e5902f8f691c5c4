#!/usr/bin/env python
"""DEEP-100M IVF-PQ north star (BASELINE.json config #4): 100M x 96,
pq_dim=64, n_probes=128, k=10 — run once per round on the real chip,
artifact committed as DEEP100M_r{N}.json.

The reference demonstrates this scale via mmap + batch_load_iterator
(python/raft-ann-bench/.../conf/deep-100M.json; dataset.hpp:45-128); at
f32 the dataset is 38 GB — bigger than HBM — so batches are GENERATED on device from
a fixed seed (the bench-wide synthetic manifold recipe) and streamed
through ``ivf_pq.build_streamed``'s donated-scatter encoder; ground
truth runs the same generator through a streaming brute-force merge.

Usage: python scripts/deep100m.py [out.json] [--n 100000000]

Tiered-memory acceptance (ISSUE 12, ROADMAP item 3): ``--tiered-out
TIERED_r12.json`` appends a stage that materializes the dataset to a
host memmap (the SSD/host tier), reranks through
``neighbors.tiered``'s shortlist-only fetch under a Zipf query mix,
and records recall / QPS / bytes-moved (vs the full-upload baseline)
/ hot-row hit-rate — asserting the tiered path is bitwise identical
to the device full-upload rerank on the same shortlists.
``--tiered-only`` skips the main battery (the CPU-smoke acceptance
shape; pair with --n 200000 and DEEP100M_FORCE_CPU=1).

graft-flow acceptance (ISSUE 16): ``--pipeline-out PIPE_r16.json``
(with ``--pipeline-only`` to skip the main battery) measures the
prefetch pipeline on the memmap tiered rerank leg — depth 0 (serial)
vs ``--pipeline-depth`` (default 2) wall-clock under an injected slow
fetch, with the stall/occupancy columns and the overlap fraction
``1 - stall(depth)/stall(0)`` — asserting bitwise-identical results
between the legs.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

if os.environ.get("DEEP100M_FORCE_CPU"):
    # CPU smoke only (--scan-impl pallas_interpret)
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp


def tiered_stage(out_path: str, n: int, cpu_smoke: bool) -> dict:
    """ISSUE 12 acceptance: the tiered-memory rerank measured at a
    DEEP-smoke shape — host/memmap originals, shortlist-only fetch,
    Zipf query mix, hot-row residency — vs the full-upload baseline.

    Writes ``out_path`` (TIERED_r12.json) with recall / QPS /
    bytes-moved / hit-rate, a bitwise-identity verdict, and the
    steady-state retrace count. Every number is dated and carries the
    platform (GL005: CPU-smoke QPS is CPU QPS, labeled as such)."""
    import tempfile

    from raft_tpu import obs, serve
    from raft_tpu.bench.run import _gen_device_block
    from raft_tpu.bench.harness import compute_recall
    from raft_tpu.neighbors import ivf_pq, tiered

    d, k, rr = 96, 10, 3
    bs = 50_000
    # lists capped so the CPU-smoke xla scan stays minutes-scale: the
    # bytes/bitwise/hit-rate columns are shape-independent, only the
    # QPS columns carry the smoke's reduced probe work
    n_lists = max(64, min(1024, n // 256))
    n_probes = max(16, n_lists // 16)
    pool_q, batch_q, n_batches = 1024, 256, 16
    hot_rows = 65_536
    gen = _gen_device_block(bs, d, 16)
    key0 = jax.random.PRNGKey(71)
    nb = -(-n // bs)

    res = {"date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "platform": jax.devices()[0].platform,
           "config": {"n": n, "dim": d, "n_lists": n_lists,
                      "n_probes": n_probes, "k": k, "refine_ratio": rr,
                      "cache_dtype": "i4", "zipf_s": 1.0,
                      "query_pool": pool_q, "query_batches": n_batches,
                      "batch_rows": batch_q, "hot_rows": hot_rows}}

    # ---- materialize the host tier: stream-generate -> memmap --------
    tmp = tempfile.NamedTemporaryFile(suffix=".f32", delete=False)
    mm = np.memmap(tmp.name, dtype=np.float32, mode="w+", shape=(n, d))
    for b in range(nb):
        blk = np.asarray(gen(jax.random.fold_in(key0, b)))
        rows = min(bs, n - b * bs)
        mm[b * bs:b * bs + rows] = blk[:rows]
    mm.flush()
    mm = np.memmap(tmp.name, dtype=np.float32, mode="r", shape=(n, d))
    print(f"tiered: host tier materialized ({n}x{d} f32, "
          f"{mm.nbytes / 1e6:.0f} MB memmap)", flush=True)

    # ---- build: streamed, cache-only i4 (HBM holds codes ONLY) -------
    params = ivf_pq.IndexParams(
        n_lists=n_lists, pq_dim=64, pq_bits=8, kmeans_n_iters=4,
        cache_dtype="i4",
    )
    t0 = time.time()

    def make_batches():
        for b in range(nb):
            yield jnp.asarray(np.asarray(mm[b * bs:(b + 1) * bs]))

    trainset = jnp.asarray(np.asarray(mm[:min(n, 4 * bs)]))
    index = ivf_pq.build_streamed(
        params, make_batches, n, d, trainset, keep_codes=False,
        cap_rows=int(1.4 * n / n_lists), verbose=False,
    )
    jax.block_until_ready(index.list_sizes)
    res["build_s"] = round(time.time() - t0, 1)
    print(f"tiered: build {res['build_s']}s", flush=True)

    # ---- Zipf(s=1.0) query mix over a finite pool --------------------
    qgen = _gen_device_block(pool_q, d, 16)
    pool = np.asarray(qgen(jax.random.fold_in(key0, 10_000)))
    rng = np.random.default_rng(12)
    ranks = np.arange(1, pool_q + 1, dtype=np.float64)
    p = 1.0 / ranks
    p /= p.sum()
    draws = rng.choice(pool_q, size=(n_batches, batch_q), p=p)

    # ---- ground truth on the pool (exact, streamed brute force) ------
    t0 = time.time()
    qd = jnp.asarray(pool)
    qn = jnp.sum(qd.astype(jnp.float32) ** 2, axis=1, keepdims=True)

    @jax.jit
    def partial_knn(batch, off):
        b32 = batch.astype(jnp.float32)
        dots = jnp.dot(qd, b32.T, preferred_element_type=jnp.float32)
        dist = qn + jnp.sum(b32 * b32, axis=1)[None, :] - 2.0 * dots
        valid = off + jnp.arange(batch.shape[0]) < n
        dist = jnp.where(valid[None, :], dist, jnp.inf)
        dd, ii = jax.lax.top_k(-dist, k)
        return -dd, ii + off

    from raft_tpu.neighbors.common import merge_topk

    cur_d = jnp.full((pool_q, k), jnp.inf)
    cur_i = jnp.full((pool_q, k), -1, jnp.int32)
    for b in range(nb):
        bd, bi = partial_knn(jnp.asarray(
            np.asarray(mm[b * bs:(b + 1) * bs])), jnp.int32(b * bs))
        cur_d, cur_i = merge_topk(
            jnp.concatenate([cur_d, bd], axis=1),
            jnp.concatenate([cur_i, bi], axis=1), k, True)
    gt = np.asarray(jnp.where(cur_i < n, cur_i, -1))
    res["groundtruth_s"] = round(time.time() - t0, 1)
    print(f"tiered: groundtruth {res['groundtruth_s']}s", flush=True)

    sp = ivf_pq.SearchParams(n_probes=n_probes, scan_impl="xla")
    obs.set_mode("on")
    obs.reset()

    def run(dataset, label):
        outs = []
        t0 = time.perf_counter()
        for b in range(n_batches):
            qb = jnp.asarray(pool[draws[b]])
            d_, i_ = ivf_pq.search_refined(sp, index, qb, k,
                                           refine_ratio=rr,
                                           dataset=dataset)
            outs.append((np.asarray(d_), np.asarray(i_)))
        wall = time.perf_counter() - t0
        qps = n_batches * batch_q / wall
        print(f"tiered: {label} {wall:.1f}s ({qps:.0f} qps)", flush=True)
        return outs, qps

    # ---- baseline: full-upload device rerank -------------------------
    ds_dev = jnp.asarray(np.asarray(mm))
    jax.block_until_ready(ds_dev)
    bytes_full = int(mm.nbytes)          # what the upload actually moves
    base, qps_full = run(ds_dev, "full-upload baseline")
    del ds_dev

    # ---- tiered: shortlist-only fetch + hot-row residency ------------
    src = tiered.HostArraySource(mm, hot_rows=hot_rows, promote_after=1,
                                 promote_batch=1024)
    # trace the full fetched-block rung ladder up front (what serve's
    # warmup does), so BOTH epochs below run at zero added traces
    kc = ivf_pq.refined_shortlist_width(sp, index, k, rr)
    src.warm(batch_q, kc, k, index.metric)
    tiered_out, qps_warm = run(src, "tiered (cold+warming)")
    bitwise = all(
        np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        for a, b in zip(base, tiered_out))
    # steady state: the hot set is resident, every rung traced — a
    # second epoch must add ZERO XLA traces and hit the hot tier
    st_warm = src.stats()
    traces0 = serve.total_trace_count()
    steady, qps_steady = run(src, "tiered (steady state)")
    retraces = serve.total_trace_count() - traces0
    bitwise = bitwise and all(
        np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        for a, b in zip(base, steady))

    st = src.stats()
    bytes_tiered = int(st["bytes_moved"])
    recall = compute_recall(
        np.concatenate([draw_i for _, draw_i in steady]),
        gt[draws.reshape(-1)])
    res.update({
        "bitwise_identical_to_full_upload": bool(bitwise),
        "recall_at_10": round(float(recall), 4),
        "qps_full_upload": round(qps_full, 1),
        "qps_tiered_warming": round(qps_warm, 1),
        "qps_tiered_steady": round(qps_steady, 1),
        "bytes_full_upload": bytes_full,
        "bytes_moved_tiered": bytes_tiered,
        "bytes_ratio": round(bytes_full / max(bytes_tiered, 1), 1),
        "bytes_per_query_tiered": round(
            bytes_tiered / (2 * n_batches * batch_q), 1),
        "hot_hit_rate": round(st["hit_rate_hbm"], 4),
        "hot_hit_rate_steady": round(
            (st["hbm_hits"] - st_warm["hbm_hits"])
            / max(st["lookups"] - st_warm["lookups"], 1), 4),
        "evictions": int(st["evictions"]),
        "promotions": int(st["promotions"]),
        "steady_state_retraces": int(retraces),
        "timing": "wall-clock over %d x %d Zipf(1.0) query batches"
                  % (n_batches, batch_q),
    })
    if cpu_smoke:
        res["note"] = ("CPU smoke (xla scan): QPS columns are CPU-host "
                       "numbers; bytes/bitwise/hit-rate are "
                       "platform-independent")
    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
        f.write("\n")
    os.unlink(tmp.name)
    print(json.dumps(res))
    return res


def pipeline_stage(out_path: str, n: int, cpu_smoke: bool,
                   depth: int = 2) -> dict:
    """ISSUE 16 acceptance: graft-flow prefetch on the memmap tiered
    rerank leg. Runs the SAME Zipf-free query battery through
    ``ivf_pq.search_refined_stream`` serially (depth 0) and pipelined
    (``depth``) under an injected slow fetch, and records wall-clock
    speedup, stall totals, and the overlap fraction
    ``1 - stall(depth)/stall(0)`` in a dated ``PIPE_r16.json``.

    The injection models both sides of the overlap on the CPU smoke:
    ``slow@stage:tiered.fetch`` is the host/SSD tier's fetch latency
    (producer side), ``slow@stage:tiered.score`` stands in for the
    device scan time the CPU host-loop lacks (consumer side) — on TPU
    the score side is real device time and needs no injection. The
    sleep length is calibrated to 2x the measured uninjected per-batch
    time, so the serial leg pays fetch+score stacked while the
    pipelined leg pays only the longer of the two. Results must be
    bitwise identical between the legs (GL005: every number dated and
    platform-labeled)."""
    import tempfile

    from raft_tpu import obs
    from raft_tpu.bench.run import _gen_device_block
    from raft_tpu.neighbors import ivf_pq, tiered
    from raft_tpu.resilience import faultinject

    d, k, rr = 96, 10, 3
    bs = 50_000
    n_lists = max(32, min(512, n // 512))
    # lighter probe work than tiered_stage: the overlap ratio is
    # shape-independent and the CPU-smoke xla scan is the bottleneck
    n_probes = max(8, n_lists // 32)
    batch_q, n_batches = 256, 8
    m = batch_q * n_batches
    hot_rows = 4096          # small on purpose: misses keep the gather real
    gen = _gen_device_block(bs, d, 16)
    key0 = jax.random.PRNGKey(71)
    nb = -(-n // bs)

    tmp = tempfile.NamedTemporaryFile(suffix=".f32", delete=False)
    mm = np.memmap(tmp.name, dtype=np.float32, mode="w+", shape=(n, d))
    for b in range(nb):
        blk = np.asarray(gen(jax.random.fold_in(key0, b)))
        rows = min(bs, n - b * bs)
        mm[b * bs:b * bs + rows] = blk[:rows]
    mm.flush()
    mm = np.memmap(tmp.name, dtype=np.float32, mode="r", shape=(n, d))
    print(f"pipeline: host tier materialized ({n}x{d} f32, "
          f"{mm.nbytes / 1e6:.0f} MB memmap)", flush=True)

    params = ivf_pq.IndexParams(
        n_lists=n_lists, pq_dim=64, pq_bits=8, kmeans_n_iters=4,
        cache_dtype="i4",
    )

    def make_batches():
        for b in range(nb):
            yield jnp.asarray(np.asarray(mm[b * bs:(b + 1) * bs]))

    trainset = jnp.asarray(np.asarray(mm[:min(n, 4 * bs)]))
    index = ivf_pq.build_streamed(
        params, make_batches, n, d, trainset, keep_codes=False,
        cap_rows=int(1.4 * n / n_lists), verbose=False,
        pipeline_depth=depth,
    )
    jax.block_until_ready(index.list_sizes)

    qgen = _gen_device_block(m, d, 16)
    queries = np.asarray(qgen(jax.random.fold_in(key0, 10_000)))
    sp = ivf_pq.SearchParams(n_probes=n_probes, scan_impl="xla")
    kc = ivf_pq.refined_shortlist_width(sp, index, k, rr)
    obs.set_mode("on")

    def leg(depth_leg):
        src = tiered.HostArraySource(mm, hot_rows=hot_rows,
                                     promote_after=1, promote_batch=1024)
        src.warm(batch_q, kc, k, index.metric)
        obs.reset()
        t0 = time.perf_counter()
        d_, i_ = ivf_pq.search_refined_stream(
            sp, index, queries, k, refine_ratio=rr, dataset=src,
            batch_rows=batch_q, pipeline_depth=depth_leg)
        wall = time.perf_counter() - t0
        snap = obs.snapshot()
        stall = 0.0
        occ = None
        for p in snap["metrics"].get("pipeline.stall_ms",
                                     {}).get("points", []):
            if p["labels"].get("path") == "tiered.rerank":
                stall += p.get("sum", 0.0)
        for p in snap["metrics"].get("pipeline.occupancy",
                                     {}).get("points", []):
            if p["labels"].get("path") == "tiered.rerank":
                occ = p.get("value")
        return d_, i_, wall, stall, occ

    # warmup pass (compiles every rung), THEN an uninjected serial pass
    # whose per-batch time sizes the injected sleep at 2x the real work
    # — calibrating on the warmup pass would fold the XLA compile into
    # the sleep and balloon the injected legs
    leg(0)
    _, _, wall_cal, _, _ = leg(0)
    slow_ms = max(25.0, round(2e3 * wall_cal / n_batches, 1))
    if "RAFT_TPU_FAULTS_SLOW_MS" not in os.environ:
        os.environ["RAFT_TPU_FAULTS_SLOW_MS"] = str(slow_ms)
    strikes = 1000 * n_batches
    spec = (f"slow@stage:tiered.fetch*{strikes},"
            f"slow@stage:tiered.score*{strikes}")
    with faultinject.inject(spec):
        d0, i0, wall0, stall0, _ = leg(0)
    with faultinject.inject(spec):
        dN, iN, wallN, stallN, occN = leg(depth)
    bitwise = bool(np.array_equal(d0, dN) and np.array_equal(i0, iN))
    res = {
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": jax.devices()[0].platform,
        "config": {"n": n, "dim": d, "n_lists": n_lists,
                   "n_probes": n_probes, "k": k, "refine_ratio": rr,
                   "batch_rows": batch_q, "n_batches": n_batches,
                   "hot_rows": hot_rows, "pipeline_depth": depth,
                   "slow_ms": float(os.environ["RAFT_TPU_FAULTS_SLOW_MS"]),
                   "fault_spec": spec},
        "bitwise_identical_serial_vs_pipelined": bitwise,
        "wall_serial_s": round(wall0, 3),
        "wall_pipelined_s": round(wallN, 3),
        "speedup": round(wall0 / max(wallN, 1e-9), 2),
        "stall_serial_ms": round(stall0, 1),
        "stall_pipelined_ms": round(stallN, 1),
        "overlap_fraction": round(1.0 - stallN / max(stall0, 1e-9), 3),
        "occupancy_pipelined": (round(occN, 2)
                                if occN is not None else None),
        "timing": "wall-clock over %d x %d query batches, injected "
                  "slow fetch+score" % (n_batches, batch_q),
    }
    if cpu_smoke:
        res["note"] = ("CPU smoke: tiered.score slow-injection models "
                       "the device scan the host loop lacks; on TPU the "
                       "score side is real device time")
    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
        f.write("\n")
    os.unlink(tmp.name)
    print(json.dumps(res))
    return res


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    out_path = args[0] if args else "DEEP100M.json"
    n = 100_000_000
    if "--n" in sys.argv:
        n = int(sys.argv[sys.argv.index("--n") + 1])
    tiered_out = None
    if "--tiered-out" in sys.argv:
        tiered_out = sys.argv[sys.argv.index("--tiered-out") + 1]
    pipe_out = None
    if "--pipeline-out" in sys.argv:
        pipe_out = sys.argv[sys.argv.index("--pipeline-out") + 1]
    pipe_depth = 2
    if "--pipeline-depth" in sys.argv:
        pipe_depth = int(sys.argv[sys.argv.index("--pipeline-depth") + 1])
    if "--tiered-only" in sys.argv:
        tiered_stage(tiered_out or "TIERED_r12.json", n,
                     bool(os.environ.get("DEEP100M_FORCE_CPU")))
        return
    if "--pipeline-only" in sys.argv:
        pipeline_stage(pipe_out or "PIPE_r16.json", n,
                       bool(os.environ.get("DEEP100M_FORCE_CPU")),
                       depth=pipe_depth)
        return
    scan_impl = "pallas"
    if "--scan-impl" in sys.argv:   # CPU smoke: pass pallas_interpret
        scan_impl = sys.argv[sys.argv.index("--scan-impl") + 1]
    # cache rung: i4 (0.5 B/comp, the 100M default — 6.4 GB at rot128)
    # or i8 with pq_dim=96/rot=96 (9.6 GB cache-only; the rehearsal
    # measured i8-raw ~0.95 vs i4 ~0.9 recall on IP-like data —
    # SHARDED_r05.json) for a second recall/QPS Pareto point on chip
    cache_dtype = "i4"
    if "--cache-dtype" in sys.argv:
        cache_dtype = sys.argv[sys.argv.index("--cache-dtype") + 1]
    pq_dim = 96 if cache_dtype == "i8" else 64   # i8: rot=96 keeps the
    # cache at 9.6 GB (rot128 would be 12.8 GB and miss HBM)
    d, nq, k = 96, 10_000, 10
    bs = 500_000
    n_lists = 32768 if n > 20_000_000 else 4096
    n_probes = 128

    from raft_tpu.bench.run import _gen_device_block
    from raft_tpu.bench.harness import compute_recall
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.common import merge_topk

    gen = _gen_device_block(bs, d, 16)
    key0 = jax.random.PRNGKey(71)
    nb = -(-n // bs)

    def make_batches():
        for b in range(nb):
            yield gen(jax.random.fold_in(key0, b))

    qgen = _gen_device_block(nq, d, 16)
    queries = qgen(jax.random.fold_in(key0, 10_000))
    jax.block_until_ready(queries)

    res = {"config": {"n": n, "dim": d, "n_lists": n_lists,
                      "pq_dim": pq_dim, "pq_bits": 8,
                      "cache_dtype": cache_dtype, "n_probes": n_probes,
                      "k": k, "batch_rows": bs}}

    # ---- build ---------------------------------------------------------
    # trainset: 4M rows (125 rows/list at 32k lists). Cache-only int4
    # index (keep_codes=False): the packed-int4 residual cache (~9 GB at
    # 100M x rot128) is the only storage, scanned by the fused Pallas
    # kernel with in-kernel nibble decode — the round-4 answer to the
    # round-3 195-QPS decode-gather fallback.
    params = ivf_pq.IndexParams(
        n_lists=n_lists, pq_dim=pq_dim, pq_bits=8, kmeans_n_iters=10,
        cache_dtype=cache_dtype,
    )
    t0 = time.time()

    def make_trainset():
        return jnp.concatenate(
            [gen(jax.random.fold_in(key0, b)) for b in range(8)]
        )   # 4M rows at bs=500k

    # cap lists at 1.4x the mean: the codes accumulator must fit HBM
    # beside the batch transients; outlier-list overflow rows are dropped
    # (reported in stored_rows). The trainset is passed as a temporary so
    # build_streamed can free it before the accumulators go up.
    index = ivf_pq.build_streamed(
        params, make_batches, n, d, make_trainset(),
        keep_codes=False, cap_rows=int(1.4 * n / n_lists), verbose=True,
    )
    jax.block_until_ready(index.list_sizes)
    build_s = time.time() - t0
    sizes = np.asarray(index.list_sizes)
    res["build_s"] = round(build_s, 1)
    res["cap"] = int(index.indices.shape[1])
    res["list_size_mean"] = float(sizes.mean())
    res["list_size_max"] = int(sizes.max())
    res["stored_rows"] = int(sizes.sum())
    print(f"build: {build_s:.0f} s  cap={res['cap']} "
          f"stored={res['stored_rows']}", flush=True)

    # ---- ground truth: streaming exact brute force ---------------------
    t0 = time.time()
    sub = 1000
    qs = queries[:sub]
    qn = jnp.sum(qs.astype(jnp.float32) ** 2, axis=1, keepdims=True)

    @jax.jit
    def partial_knn(batch, off):
        b32 = batch.astype(jnp.float32)
        dots = jnp.dot(qs, b32.T, preferred_element_type=jnp.float32)
        dist = qn + jnp.sum(b32 * b32, axis=1)[None, :] - 2.0 * dots
        # mask padded tail rows (global id >= n) BEFORE the merge so they
        # cannot evict real neighbors when --n isn't batch-aligned
        valid = off + jnp.arange(batch.shape[0]) < n
        dist = jnp.where(valid[None, :], dist, jnp.inf)
        dd, ii = jax.lax.top_k(-dist, k)
        return -dd, ii + off

    cur_d = jnp.full((sub, k), jnp.inf)
    cur_i = jnp.full((sub, k), -1, jnp.int32)
    for b in range(nb):
        bd, bi = partial_knn(gen(jax.random.fold_in(key0, b)),
                             jnp.int32(b * bs))
        gd = jnp.concatenate([cur_d, bd], axis=1)
        gi = jnp.concatenate([cur_i, bi], axis=1)
        cur_d, cur_i = merge_topk(gd, gi, k, True)
        if b % 8 == 7:
            np.asarray(cur_i[0, 0])    # throttle the async queue
    # mask padded tail rows (ids >= n)
    cur_i = np.asarray(jnp.where(cur_i < n, cur_i, -1))
    res["groundtruth_s"] = round(time.time() - t0, 1)
    print(f"groundtruth: {res['groundtruth_s']} s", flush=True)

    # ---- search --------------------------------------------------------
    sp = ivf_pq.SearchParams(n_probes=n_probes, scan_impl=scan_impl)
    dist, idx = ivf_pq.search(sp, index, queries, k)
    np.asarray(idx[0, 0])
    recall = compute_recall(np.asarray(idx[:sub]), cur_i)
    res["recall_at_10"] = round(float(recall), 4)
    print(f"recall={recall:.4f}", flush=True)
    # scan-chained on-device timing (the repo's standard methodology —
    # the fused int4 kernel is fast enough to fit iterations under the
    # platform watchdog, unlike round 3's decode fallback). CPU smokes
    # (interpret-mode kernel, ~minutes per search pass) skip the timing
    # blocks: their numbers would be meaningless and cost hours.
    cpu_smoke = bool(os.environ.get("DEEP100M_FORCE_CPU"))
    from raft_tpu.bench.harness import scan_qps_time

    def step(qb, ops):
        return ivf_pq.search(sp, ops, qb, k)

    if not cpu_smoke:
        s = scan_qps_time(step, queries, n1=2, n2=6, operands=index)
        res["qps"] = round(nq / s, 1)
        res["timing"] = "scan-chained (iters 2->6 slope)"
        print(f"qps={res['qps']} recall={res['recall_at_10']}", flush=True)

    # ---- cache-resident refine point (search_refined: slot-substituted
    # search + f32 re-rank decoded from the same i4 cache — removes the
    # kernel's bf16/extraction losses at no extra index bytes) ----------
    rq = queries[:sub] if cpu_smoke else queries
    _, idx_r = ivf_pq.search_refined(sp, index, rq, k, refine_ratio=3)
    np.asarray(idx_r[0, 0])
    res["refined_recall_at_10"] = round(
        float(compute_recall(np.asarray(idx_r[:sub]), cur_i)), 4)
    print(f"refined recall={res['refined_recall_at_10']}", flush=True)

    if not cpu_smoke:
        def step_r(qb, ops):
            return ivf_pq.search_refined(sp, ops, qb, k, refine_ratio=3)

        s = scan_qps_time(step_r, queries, n1=2, n2=6, operands=index)
        res["refined_qps"] = round(nq / s, 1)
        print(f"refined qps={res['refined_qps']}", flush=True)

    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    if tiered_out:
        tiered_stage(tiered_out, n, cpu_smoke)
    if pipe_out:
        pipeline_stage(pipe_out, n, cpu_smoke, depth=pipe_depth)


if __name__ == "__main__":
    main()
