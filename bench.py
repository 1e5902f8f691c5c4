#!/usr/bin/env python
"""Benchmark: prints ONE JSON line with the headline metric (IVF-Flat
SIFT-1M-class QPS @ recall) plus the other north-star configs in "extra".

Every QPS number is a **scan-chained on-device loop**: N search
iterations run inside one jitted program, each on a rolled (distinct)
query batch, all folded into a returned checksum so XLA cannot elide any
iteration. Wall time is taken at two iteration counts (N1 < N2) and the
per-iteration time is (T2-T1)/(N2-N1), cancelling the constant dispatch
and fetch overhead.

It runs only on a TPU (checked in-process) and exits non-zero when a
phase fails. The vs_baseline denominators are A100 roofline estimates,
derived per entry in ``_BASELINES``: the reference publishes no numeric
tables, only a Pareto plot.
"""

import json
import os
import sys
import time

import numpy as np

# A100/raft-24.02 reference throughput estimates for the north-star
# configs. The reference publishes NO numeric tables (only the H100
# recall-vs-QPS Pareto plot, docs/source/raft_ann_benchmarks.md:254) and
# this environment has no network to fetch public runs, so every
# denominator below is a FLOP/bandwidth roofline for an A100-80GB
# [312 TF/s fp16 tensor, 2.0 TB/s HBM] with its derivation and
# confidence documented per entry.
_BASELINES = {
    # 10k x 10k x 128 L2 + top-k = 33 GFLOP/batch; at ~50% tensor peak
    # plus selection overhead -> ~2e6 QPS. Confidence MEDIUM (pure
    # roofline; public GPU brute-force numbers at this shape are scarce).
    "bruteforce_sift10k_qps": 2.0e6,
    # nlist=1024, nprobe=64, batch 10k, r@10~0.95: scans ~1/16 of 512 MB
    # per query batch -> HBM-bound ~4e5 QPS. Confidence MEDIUM-HIGH
    # (consistent with the H100 Pareto plot's IVF-Flat band scaled to
    # A100 bandwidth).
    "ivfflat_sift1m_qps": 4.0e5,
    # pairwise 10k x 10k x 128 f32: bound by the 400 MB output write,
    # ~0.7x of 2 TB/s effective. Confidence HIGH (straight bandwidth).
    "pairwise_l2_gbps": 1400.0,
    # DEEP-10M pq48x8, nprobe=128: LUT-gather bound; scaled from the
    # reference's DEEP-100M positioning. Confidence LOW-MEDIUM (config
    # scaled down from the published 100M benchmarks).
    "ivfpq_deep10m_qps": 2.0e5,
    # CAGRA deg32 SIFT-1M r@10~0.95 batch 10k: the CAGRA paper
    # (arXiv:2308.15136, fig. batch-throughput) places A100 large-batch
    # SIFT-1M throughput in the 5e5-1e6 band at 0.95. Confidence MEDIUM
    # (anchored to the paper's published order of magnitude).
    "cagra_sift1m_qps": 6.0e5,
}


def _sift_like(n, d, seed=0, intrinsic=16):
    """SIFT-like synthetic: points near a low-intrinsic-dimension manifold
    (real SIFT has intrinsic dim ~15 in 128 ambient dims). A
    few-isolated-blobs mixture is *adversarial* for graph ANN (the KNN
    graph disconnects); this matches realistic ANN difficulty instead.
    Generated on the device (synthetic_dataset_device), in bulk. Ground
    truth is computed from these same arrays."""
    from raft_tpu.bench.run import synthetic_dataset_device

    base, _ = synthetic_dataset_device(n, d, n_queries=1, seed=seed,
                                       intrinsic_dim=intrinsic)
    return base


from raft_tpu.bench.harness import scan_qps_time  # noqa: E402


def _emit_roofline(results, stub, *, bytes_moved, flops, seconds,
                   rows=None):
    """Roofline columns next to each QPS number (ROADMAP item 1): the
    op's cost model (ideal HBM bytes + FLOPs as implemented) against
    the measured seconds, scored vs the backend peak spec
    (raft_tpu.bench.harness.PEAK_SPECS; methodology docs/kernels.md).
    ``rows`` = dataset rows scanned per timed iteration, for the
    bytes_per_row column (the quantization ladder's figure of merit)."""
    from raft_tpu.bench.harness import roofline

    r = roofline(bytes_moved, flops, seconds)
    results[f"{stub}_roofline"] = r
    results[f"{stub}_peak_fraction"] = r["peak_fraction"]
    if rows:
        results[f"{stub}_bytes_per_row"] = round(bytes_moved / rows, 2)


def _median_s(results, key_stub, timer, n_draws=5):
    """Variance-honest timing: run ``timer()`` (one scan-chained
    two-point measurement = one draw) ``n_draws`` times, record EVERY
    draw under ``{key_stub}_draws_s`` and return the median seconds.
    Round 3 saw single draws spread by up to ~20x (pairwise 41-868
    GB/s); the full list keeps the spread auditable."""
    draws = [timer() for _ in range(n_draws)]
    results[f"{key_stub}_draws_s"] = [round(s, 6) for s in draws]
    return float(np.median(draws))


def bench_bruteforce_sift10k(results):
    import jax
    from raft_tpu.neighbors import brute_force

    n, d, nq, k = 10_000, 128, 10_000, 10
    x = jax.device_put(_sift_like(n, d, seed=1))
    q = jax.device_put(_sift_like(nq, d, seed=2))
    index = brute_force.build(x, "sqeuclidean")
    s = _median_s(results, "bruteforce_sift10k", lambda: scan_qps_time(
        lambda qq, ix: brute_force.search(ix, qq, k), q, operands=index))
    results["bruteforce_sift10k_qps"] = round(nq / s, 1)
    from raft_tpu.distance.types import DistanceType, pair_flops

    # cost model: one full dataset stream + query/output traffic per
    # batch; the fused kernel's whole point is that the [nq, n] distance
    # matrix is NOT in this byte count (it never reaches HBM)
    _emit_roofline(
        results, "bruteforce_sift10k",
        bytes_moved=n * d * 4 + nq * d * 4 + nq * k * 8,
        flops=nq * n * pair_flops(DistanceType.L2Expanded, d),
        seconds=s, rows=n)


def bench_pairwise(results):
    import jax
    from raft_tpu.distance import pairwise_distance

    n, d = 10_000, 128
    x = jax.device_put(_sift_like(n, d, seed=1))
    q = jax.device_put(_sift_like(n, d, seed=2))
    s = _median_s(results, "pairwise_l2", lambda: scan_qps_time(
        lambda qq, xx: (pairwise_distance(qq, xx, "sqeuclidean"),
                        jax.numpy.zeros((1,), jax.numpy.int32)),
        q, operands=x))
    bytes_moved = n * d * 4 * 2 + n * n * 4
    results["pairwise_l2_gbps"] = round(bytes_moved / s / 1e9, 1)
    results["pairwise_l2_gflops"] = round(2 * n * n * d / s / 1e9, 1)
    # pairwise MATERIALIZES its output, so the n*n*4 write dominates the
    # byte model — the bandwidth-bound contrast to the fused search ops
    _emit_roofline(results, "pairwise_l2", bytes_moved=bytes_moved,
                   flops=2 * n * n * d, seconds=s, rows=n)


def bench_ivfflat_sift1m(results):
    import jax
    from raft_tpu.neighbors import brute_force, ivf_flat
    from raft_tpu.bench.harness import compute_recall

    n, d, nq, k = 1_000_000, 128, 10_000, 10
    x = jax.device_put(_sift_like(n, d, seed=1))
    q = jax.device_put(_sift_like(nq, d, seed=2))
    t0 = time.time()
    params = ivf_flat.IndexParams(n_lists=1024, metric="sqeuclidean")
    index = ivf_flat.build(params, x)
    np.asarray(index.list_sizes)  # sync build
    results["ivfflat_build_s"] = round(time.time() - t0, 1)

    sp = ivf_flat.SearchParams(n_probes=64)
    dist, idx = ivf_flat.search(sp, index, q, k)
    sub = 1000
    _, bf_idx = brute_force.knn(q[:sub], x, k)
    recall = compute_recall(np.asarray(idx[:sub]), np.asarray(bf_idx))
    s = _median_s(results, "ivfflat_sift1m", lambda: scan_qps_time(
        lambda qq, ix: ivf_flat.search(sp, ix, qq, k), q, operands=index))
    results["ivfflat_sift1m_qps"] = round(nq / s, 1)
    results["ivfflat_recall"] = round(float(recall), 3)
    from raft_tpu.distance.types import DistanceType, pair_flops

    # cost model: coarse centers GEMM + probed-list block streams
    # (storage row f32 + stored id + precomputed norm per row)
    cap = int(index.storage.shape[1])
    rows = nq * sp.n_probes * cap
    pf = pair_flops(DistanceType.L2Expanded, d)
    _emit_roofline(
        results, "ivfflat_sift1m",
        bytes_moved=rows * (d * 4 + 4 + 4) + nq * d * 4,
        flops=rows * pf + nq * index.n_lists * pf,
        seconds=s, rows=rows)


def bench_cagra_sift1m(results):
    import jax
    from raft_tpu.neighbors import brute_force, cagra
    from raft_tpu.bench.harness import compute_recall

    n, d, nq, k = 1_000_000, 128, 10_000, 10
    x = jax.device_put(_sift_like(n, d, seed=1))
    q = jax.device_put(_sift_like(nq, d, seed=2))
    t0 = time.time()
    index = cagra.build(
        cagra.IndexParams(graph_degree=32, intermediate_graph_degree=64), x
    )
    np.asarray(index.graph[0, 0])  # sync build
    results["cagra_build_s"] = round(time.time() - t0, 1)
    # n_seeds=64 + 15 iterations: measured 0.960 recall @ 181k QPS on the
    # fused Pallas beam path (auto-iters=17 buys 0.971 at 151k)
    sp = cagra.SearchParams(n_seeds=64, max_iterations=15)
    dist, idx = cagra.search(sp, index, q, k)
    sub = 1000
    _, bf_idx = brute_force.knn(q[:sub], x, k)
    recall = compute_recall(np.asarray(idx[:sub]), np.asarray(bf_idx))
    s = _median_s(results, "cagra_sift1m", lambda: scan_qps_time(
        lambda qq, ix: cagra.search(sp, ix, qq, k), q, operands=index))
    results["cagra_sift1m_qps"] = round(nq / s, 1)
    results["cagra_recall"] = round(float(recall), 3)
    from raft_tpu.distance.types import DistanceType, pair_flops

    # cost model: seeds + per-iteration beam expansion (graph row of 32
    # neighbor ids + each neighbor's vector) — a graph walk's traffic is
    # gather-shaped, so this is the IDEAL byte floor, not a stream
    deg = int(index.graph.shape[1])
    visited = nq * (sp.n_seeds + 15 * deg)
    _emit_roofline(
        results, "cagra_sift1m",
        bytes_moved=visited * (d * 4 + 4) + nq * 15 * deg * 4,
        flops=visited * pair_flops(DistanceType.L2Expanded, d),
        seconds=s, rows=visited)


def bench_cagra_graph_build(results):
    """Graph-build roofline (ISSUE 15, ROADMAP item 7): time the
    rebuilt nn-descent at the 1M scale and score it against the
    gather byte floor — per iteration every node gathers S+K candidate
    vectors (+ the sampled two-hop ids), so the ideal traffic is
    ``iters * n * (S+K) * (d*4 + 4)`` bytes against
    ``iters * n * (S+K) * pair_flops`` FLOPs. The old formulation
    added ``n*2K*K*4`` bytes of two-hop tensor per iteration on top —
    deleted by sample-then-gather, which is why it is not in this
    model (the cost model is the algorithm as implemented)."""
    import jax
    import jax.numpy as jnp
    from raft_tpu.neighbors import nn_descent
    from raft_tpu.distance.types import DistanceType, pair_flops

    n, d, deg, iters = 1_000_000, 128, 32, 14
    # clustered blobs, generated on the device: the sampled pull-join localizes blobs in ~10-16 rounds
    # but flat low-intrinsic-dim manifolds crawl at ~0.04
    # recall/iteration (GRAPH_r15.json sweep, 2026-08-04) — _sift_like
    # here would publish an iteration-budget artifact, not a build
    # property (ROADMAP item 7b tracks the convergence-rate work)
    kc, ka, kn = jax.random.split(jax.random.PRNGKey(5), 3)
    centers = jax.random.uniform(kc, (1024, d), jnp.float32, -5.0, 5.0)
    x = (centers[jax.random.randint(ka, (n,), 0, 1024)]
         + 0.6 * jax.random.normal(kn, (n, d), jnp.float32))
    x = jax.block_until_ready(x)
    params = nn_descent.IndexParams(
        graph_degree=deg, max_iterations=iters,
        termination_threshold=0.0)
    t0 = time.time()
    index = nn_descent.build(params, x)
    g = np.asarray(index.graph)                 # sync
    s = time.time() - t0
    results["graph_build_s"] = round(s, 1)
    from raft_tpu.neighbors import brute_force

    sub = 500
    _, want = brute_force.knn(x[:sub], x, deg + 1)
    want = np.asarray(want)[:, 1:]
    results["graph_build_recall"] = round(float(np.mean(
        [len(set(g[i]) & set(want[i])) / deg for i in range(sub)])), 3)
    K = deg * 3 // 2
    S = int(params.n_candidates)
    C = S + K
    _emit_roofline(
        results, "graph_build",
        bytes_moved=iters * n * (C * (d * 4 + 4) + S * 4),
        flops=iters * n * C * pair_flops(DistanceType.L2Expanded, d),
        seconds=s, rows=iters * n * C)


def bench_ivfpq_deep10m(results):
    import jax
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.bench.harness import compute_recall

    n, d, nq, k = 10_000_000, 96, 10_000, 10
    x = _sift_like(n, d, seed=3)
    q = jax.device_put(_sift_like(nq, d, seed=4))
    t0 = time.time()
    # streaming build: per-batch encode keeps the full-dataset rotation /
    # residual intermediates (≈12 GB at 10M x 96) out of HBM
    # trainset fraction 0.1: 1M training rows are plenty for 1024 coarse
    # centers + codebooks and cut the dominant kmeans/upload cost
    index = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=1024, pq_dim=48, pq_bits=8,
                           kmeans_trainset_fraction=0.1), x,
        batch_size=2_000_000,
    )
    np.asarray(index.list_sizes)
    results["ivfpq_build_s"] = round(time.time() - t0, 1)
    sp = ivf_pq.SearchParams(n_probes=128)
    dist, idx = ivf_pq.search(sp, index, q, k)
    np.asarray(idx[0, 0])  # first call: compile + warm
    t0 = time.time()
    # distinct queries, so that no result cache can answer the repeat
    import jax.numpy as jnp

    _, idx2 = ivf_pq.search(sp, index, jnp.roll(q, 1, axis=0), k)
    np.asarray(idx2[0, 0])
    rough_s = max(time.time() - t0, 0.1)  # warm order-of-magnitude + RTT
    # chunked exact oracle on a query subset
    sub = 500
    from raft_tpu.bench.run import generate_groundtruth

    mi = generate_groundtruth(
        x, np.asarray(q[:sub]), k, "sqeuclidean", chunk=2_000_000
    )
    recall = compute_recall(np.asarray(idx[:sub]), np.asarray(mi))
    # size the scan so one timed program stays under ~45 s
    n2 = int(np.clip(45.0 / rough_s, 2, 13))
    n1 = max(1, n2 // 3)
    s = _median_s(results, "ivfpq_deep10m", lambda: scan_qps_time(
        lambda qq, ix: ivf_pq.search(sp, ix, qq, k), q,
        n1=n1, n2=n2, operands=index), n_draws=3)
    results["ivfpq_deep10m_qps"] = round(nq / s, 1)
    results["ivfpq_recall"] = round(float(recall), 3)
    # cost model: probed lists stream pq codes (pq_dim * pq_bits/8
    # bytes) + stored id per row, plus the coarse GEMM — the
    # rows-per-HBM-byte ceiling the quantization ladder multiplies
    cap_pq = int(index.indices.shape[1])
    rows_pq = nq * sp.n_probes * cap_pq
    code_bytes = 48 * 8 // 8            # pq48x8
    _emit_roofline(
        results, "ivfpq_deep10m",
        bytes_moved=rows_pq * (code_bytes + 4) + nq * d * 4,
        flops=rows_pq * 2 * int(index.rot_dim),
        seconds=s, rows=rows_pq)

    # + exact refine (the reference's standard recall lever: its bench
    # runs IVF-PQ with refine_ratio, raft_ivf_pq_wrapper.h) — recall
    # plateaus at 0.893 on raw pq48 codes regardless of n_probes
    # (measured at 128/160/192), so the re-rank is what clears 0.90
    from raft_tpu.neighbors.refine import refine

    x_dev = jnp.asarray(x)

    def search_refined(qq, ops):
        ix, xs = ops   # dataset rides operands: closure capture would
        # bake the 3.8 GB array into the HLO as a constant (harness doc)
        _, cand = ivf_pq.search(sp, ix, qq, 3 * k)
        return refine(xs, qq, cand, k, "sqeuclidean")

    dist_r, idx_r = search_refined(q, (index, x_dev))
    recall_r = compute_recall(np.asarray(idx_r[:sub]), np.asarray(mi))
    s = _median_s(results, "ivfpq_refined", lambda: scan_qps_time(
        search_refined, q, n1=n1, n2=n2, operands=(index, x_dev)),
        n_draws=3)
    results["ivfpq_refined_qps"] = round(nq / s, 1)
    results["ivfpq_refined_recall"] = round(float(recall_r), 3)

    # + cache-resident refine: raw-residual i8 cache as both scan operand
    # and refine source — the billion-scale pattern (SHARDED_r05.json)
    # measured here as a DATASET-FREE Pareto point (the f32-refined
    # config above reads the 3.8 GB dataset per query batch; this one
    # reads only the 1 B/dim cache)
    index_raw = ivf_pq.attach_raw_residual_cache(index, x_dev,
                                                 dtype="i8")
    np.asarray(index_raw.cache_scales[0, 0])   # sync the attach

    def search_cache_refined(qq, ix):
        return ivf_pq.search_refined(sp, ix, qq, k, refine_ratio=3)

    _, idx_cr = search_cache_refined(q, index_raw)
    results["ivfpq_cache_refined_recall"] = round(float(
        compute_recall(np.asarray(idx_cr[:sub]), np.asarray(mi))), 3)
    s = _median_s(results, "ivfpq_cache_refined", lambda: scan_qps_time(
        search_cache_refined, q, n1=n1, n2=n2, operands=index_raw),
        n_draws=3)
    results["ivfpq_cache_refined_qps"] = round(nq / s, 1)
    del index_raw

    # + tiered host-tier refine (ISSUE 12, docs/serving.md §12): the
    # f32 originals stay HOST-resident — only each batch's unique
    # shortlist rows cross the link (vs the x_dev full upload the
    # f32-refined config above is built on). Wall-clock timed: the
    # host gather sits outside the jit chain, so scan_qps_time's
    # scan-chained methodology cannot carry it. Emits the
    # bytes-moved-per-query column ROADMAP item 3 budgets against.
    from raft_tpu.neighbors import tiered as _tiered

    src_t = _tiered.HostArraySource(x, hot_rows=65536)

    def search_tiered(qq):
        return ivf_pq.search_refined(sp, index, qq, k,
                                     refine_ratio=3, dataset=src_t)

    dist_t, idx_t = search_tiered(q)
    jax.block_until_ready(idx_t)
    assert np.array_equal(np.asarray(idx_t), np.asarray(idx_r)), \
        "tiered rerank diverged from the full-upload refine"
    results["ivfpq_tiered_refined_recall"] = round(float(
        compute_recall(np.asarray(idx_t[:sub]), np.asarray(mi))), 3)
    st0 = src_t.stats()
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(search_tiered(q))
    s = (time.perf_counter() - t0) / 3
    st1 = src_t.stats()
    results["ivfpq_tiered_refined_qps"] = round(nq / s, 1)
    results["ivfpq_tiered_bytes_per_query"] = round(
        (st1["bytes_moved"] - st0["bytes_moved"]) / (3 * nq), 1)
    results["ivfpq_tiered_hot_hit_rate"] = round(
        st1["hit_rate_hbm"], 4)
    results["ivfpq_tiered_timing"] = "wall-clock (host gather)"
    del src_t

    # + the rabitq rung (ISSUE 11): 1-bit sign-code first stage + exact
    # rerank from the PQ codes — the rows-per-HBM-byte ladder's bottom
    # step. Emits TWO byte columns per arm (cost model:
    # ivf_pq.scan_bytes_per_row): the roofline row carries the honest
    # total traffic (codes + estimator scalars + id/slot row), and
    # *_code_bytes_per_row carries the quantized payload alone — the
    # ladder figure where i4 → rabitq is the full 4x (rot/2 vs rot/8)
    index_rbq = ivf_pq.attach_rabitq_cache(index)
    np.asarray(index_rbq.cache_fac[0, 0])              # sync attach
    rot = int(index.rot_dim)
    kc_rb = 4 * k

    def search_rabitq(qq, ix):
        return ivf_pq.search_refined(sp, ix, qq, k, refine_ratio=4)

    _, idx_rb = search_rabitq(q, index_rbq)
    results["ivfpq_rabitq_recall"] = round(float(
        compute_recall(np.asarray(idx_rb[:sub]), np.asarray(mi))), 3)
    s = _median_s(results, "ivfpq_rabitq", lambda: scan_qps_time(
        search_rabitq, q, n1=n1, n2=n2, operands=index_rbq),
        n_draws=3)
    results["ivfpq_rabitq_qps"] = round(nq / s, 1)
    # first-stage-only roofline (the scan the compression ladder
    # multiplies): timed at the pipeline's shortlist width
    s1 = _median_s(results, "ivfpq_rabitq_stage1",
                   lambda: scan_qps_time(
                       lambda qq, ix: ivf_pq.search(sp, ix, qq, kc_rb),
                       q, n1=n1, n2=n2, operands=index_rbq),
                   n_draws=3)
    rb_code, rb_total = ivf_pq.scan_bytes_per_row("rabitq", rot)
    i4_code, i4_total = ivf_pq.scan_bytes_per_row("i4", rot)
    _emit_roofline(
        results, "ivfpq_rabitq_stage1",
        bytes_moved=rows_pq * rb_total + nq * d * 4,
        flops=rows_pq * 2 * rot,
        seconds=s1, rows=rows_pq)
    results["ivfpq_rabitq_code_bytes_per_row"] = rb_code
    results["ivfpq_i4_code_bytes_per_row"] = i4_code
    results["ivfpq_i4_scan_bytes_per_row"] = i4_total
    del index_rbq


def main():
    # --obs-snapshot [PATH]: run instrumented (graft-scope, RAFT_TPU_OBS
    # at least "on") and write the metrics-snapshot sidecar next to the
    # headline JSON line — dispatch winners, per-algo latency histograms,
    # OOM-ladder/retry counts, device memory gauges (docs/observability.md)
    obs_path = None
    if "--obs-snapshot" in sys.argv:
        i = sys.argv.index("--obs-snapshot")
        obs_path = (sys.argv[i + 1] if i + 1 < len(sys.argv)
                    and not sys.argv[i + 1].startswith("-")
                    else "BENCH_obs.json")
        from raft_tpu import obs

        if not obs.enabled():
            obs.set_mode("on")

    from raft_tpu.bench.harness import require_tpu

    require_tpu()

    results = {}
    full = os.environ.get("BENCH_FULL", "1") != "0"
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "4500"))
    t_start = time.time()
    bench_bruteforce_sift10k(results)
    bench_pairwise(results)
    bench_ivfflat_sift1m(results)
    if full:
        bench_cagra_sift1m(results)
        bench_cagra_graph_build(results)
        # the PQ bench needs ~2400s end to end (round-3 measurement);
        # only start it if that fits in what's left of the budget
        if budget_s - (time.time() - t_start) > 2400:
            bench_ivfpq_deep10m(results)
        else:
            results["ivfpq_skipped"] = "insufficient bench time budget"

    import jax

    qps = results["ivfflat_sift1m_qps"]
    out = {
        "metric": "ivfflat_sift1m_qps",
        "value": qps,
        "unit": "QPS (nlist=1024, nprobe=64, k=10, batch=10k, recall=%.3f)"
        % results.get("ivfflat_recall", -1.0),
        "vs_baseline": round(qps / _BASELINES["ivfflat_sift1m_qps"], 3),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "extra": {
            kk: {
                "value": vv,
                "vs_baseline": (
                    round(vv / _BASELINES[kk], 4) if kk in _BASELINES else None
                ),
            }
            for kk, vv in results.items()
        },
    }
    if obs_path is not None:
        from raft_tpu.bench.harness import write_obs_snapshot

        write_obs_snapshot(obs_path)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
