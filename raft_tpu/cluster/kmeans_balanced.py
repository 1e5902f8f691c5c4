"""Hierarchical balanced k-means — the ANN coarse quantizer trainer.

TPU-native analog of the reference's ``raft::cluster::kmeans_balanced``
(cpp/include/raft/cluster/kmeans_balanced.cuh:76,134,199; impl
cpp/include/raft/cluster/detail/kmeans_balanced.cuh). The reference trains
IVF coarse centroids with a two-level scheme: fit sqrt(C) "mesoclusters"
over the trainset, partition the C fine clusters among mesoclusters
proportionally to their size, fit each mesocluster's points into its share
of fine clusters, then run balancing iterations over the full set with
starved-cluster reseeding (``adjust_centers``,
detail/kmeans_balanced.cuh:524).

TPU design: predict is an MXU GEMM + argmin epilogue (the ||x||^2 term is
dropped — it never changes the argmin); center update is a one-hot-matmul
accumulation; the per-mesocluster gathers are host-orchestrated
(data-dependent shapes) while every inner loop is a single jitted program.
``adjust_centers`` is vectorized: all starved clusters blend onto sampled
large clusters in one ``where`` instead of the reference's per-warp loop.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.cluster.kmeans import _centers_and_sizes
from raft_tpu.distance.types import DistanceType
from raft_tpu.utils.precision import argmax_exact, argmin_exact


@dataclasses.dataclass
class KMeansBalancedParams:
    """Aggregate params (reference kmeans_balanced_params: n_iters, metric).

    ``compute_dtype``: matmul operand dtype for predict/update GEMMs.
    "f32" (default) runs them at HIGH precision (bf16x3 passes) — needed
    when clusters are tight relative to coordinate magnitudes; "bf16"
    single-pass is ~3x faster (r2, v5e) and fine for coarse ANN quantizers on
    natural data.
    """

    n_clusters: int = 8
    n_iters: int = 20
    metric: DistanceType = DistanceType.L2Expanded
    seed: int = 0
    compute_dtype: str = "f32"


# reference constants (detail/kmeans_balanced.cuh)
_ADJUST_CENTERS_WEIGHT = 7.0   # kAdjustCentersWeight (:61)
_BALANCING_THRESHOLD = 0.25    # build_clusters default (:755)


def _as_f32(x) -> jax.Array:
    return jnp.asarray(x).astype(jnp.float32)


def _mm_dtype(compute_dtype: str):
    return jnp.bfloat16 if compute_dtype == "bf16" else jnp.float32


def _mm_precision(compute_dtype: str):
    # f32 operands at DEFAULT precision would still run one bf16 pass on
    # the MXU; HIGH (bf16x3) recovers near-f32 distances at 1/2 the cost
    # of HIGHEST. bf16 operands: precision is moot, pass DEFAULT.
    return (
        jax.lax.Precision.DEFAULT if compute_dtype == "bf16"
        else jax.lax.Precision.HIGH
    )


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _predict_metric(
    x, centers, metric: int, batch_rows: int = 1 << 16,
    compute_dtype: str = "bf16",
):
    """Nearest-center labels under L2, InnerProduct or Cosine (reference
    detail/kmeans_balanced.cuh:371 predict). Row-batched so peak memory
    stays at batch_rows x n_clusters.

    TPU formulation: the per-row term ||x||^2 never changes the argmin, so
    L2 predict is ``argmin(||c||^2 - 2 x·c)`` — one bf16 MXU pass per batch
    plus an f32 center-norm correction. Cosine = max normalized dot (the
    query norm is constant per row, so only centers need normalizing).
    """
    from raft_tpu.cluster.kmeans import _row_batches

    mm = _mm_dtype(compute_dtype)
    c32 = centers.astype(jnp.float32)
    if metric == int(DistanceType.CosineExpanded):
        c32 = c32 / jnp.maximum(
            jnp.linalg.norm(c32, axis=1, keepdims=True), 1e-30
        )
    cT = c32.astype(mm).T
    ip_like = metric in (
        int(DistanceType.InnerProduct), int(DistanceType.CosineExpanded)
    )
    cn2 = None if ip_like else jnp.sum(c32 * c32, axis=1)

    xb, _, n = _row_batches(x.astype(mm), batch_rows)

    prec = _mm_precision(compute_dtype)

    def body(_, batch):
        dots = jnp.dot(batch, cT, preferred_element_type=jnp.float32,
                       precision=prec)
        if ip_like:
            return None, argmax_exact(dots, axis=1)
        return None, argmin_exact(cn2[None, :] - 2.0 * dots, axis=1)

    _, labels = jax.lax.scan(body, None, xb)
    return labels.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _update_centers(x, labels, n_clusters: int, batch_rows: int,
                    compute_dtype: str = "bf16"):
    """Per-cluster sums/sizes via batched one-hot MXU matmuls (the
    reference's calc_centers_and_sizes, detail/kmeans_balanced.cuh:257,
    without atomics). One-hot entries are exact in bf16; sums accumulate
    in f32."""
    from raft_tpu.cluster.kmeans import _row_batches

    mm = _mm_dtype(compute_dtype)
    xb, valid, n = _row_batches(x.astype(mm), batch_rows)
    nb, b, d = xb.shape
    lp = jnp.pad(labels, (0, nb * b - n), constant_values=-1).reshape(nb, b)

    prec = _mm_precision(compute_dtype)

    def body(carry, inp):
        sums, sizes = carry
        batch, lab = inp
        one_hot = (lab[:, None] == jnp.arange(n_clusters)[None, :]).astype(mm)
        sums = sums + jnp.dot(one_hot.T, batch,
                              preferred_element_type=jnp.float32,
                              precision=prec)
        sizes = sizes + jnp.sum(one_hot, axis=0, dtype=jnp.float32)
        return (sums, sizes), None

    (sums, sizes), _ = jax.lax.scan(
        body,
        (jnp.zeros((n_clusters, d), jnp.float32),
         jnp.zeros((n_clusters,), jnp.float32)),
        (xb, lp),
    )
    return sums, sizes


@functools.partial(jax.jit, static_argnums=(5,))
def _adjust_centers(x, labels, sizes, centers, key, n_clusters: int):
    """Vectorized adjust_centers (reference detail/kmeans_balanced.cuh:438):
    every starved cluster (size <= threshold x average) has its center
    moved to a weighted blend of a *large* cluster's center and one of that
    cluster's points — splitting oversized clusters instead of reseeding
    into random space. All starved clusters adjust in one shot (the
    reference does the same, one warp per cluster)."""
    n = x.shape[0]
    average = jnp.float32(n) / jnp.float32(n_clusters)
    starved = sizes <= _BALANCING_THRESHOLD * average
    # candidate rows: uniform row sampling is already size-biased toward
    # large clusters; take the best of 4 to match the reference's
    # "size >= average" acceptance loop
    cand = jax.random.randint(key, (n_clusters, 4), 0, n)
    cand_sizes = sizes[labels[cand]]
    pick = argmax_exact(cand_sizes, axis=1)
    i = jnp.take_along_axis(cand, pick[:, None], axis=1)[:, 0]  # [C]
    li = labels[i]
    wc = jnp.minimum(sizes, _ADJUST_CENTERS_WEIGHT)[:, None]
    blend = (wc * centers[li] + x[i].astype(jnp.float32)) / (wc + 1.0)
    centers = jnp.where(starved[:, None], blend, centers)
    return centers, starved.sum()


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _em_loop(x, centers, key, n_iters: int, n_clusters: int, metric: int,
             compute_dtype: str):
    """The whole balancing EM loop as ONE compiled program: seed iteration
    (predict + update, no adjustment — the reference's iter==0 guard),
    then ``n_iters`` adjust → normalize → predict → update rounds under
    ``lax.scan``. No host synchronization anywhere in the loop."""
    n = x.shape[0]
    br = min(n, 1 << 16)
    ip_like = metric in (
        int(DistanceType.InnerProduct), int(DistanceType.CosineExpanded)
    )

    def normalize(centers):
        if not ip_like:
            return centers
        # reference L2-normalizes centers every iteration for IP/Cosine
        # (detail/kmeans_balanced.cuh:659)
        norms = jnp.linalg.norm(centers, axis=1, keepdims=True)
        return centers / jnp.maximum(norms, 1e-30)

    def em_update(centers):
        labels = _predict_metric(x, centers, metric, br, compute_dtype)
        sums, sizes = _update_centers(x, labels, n_clusters, br, compute_dtype)
        centers = jnp.where(
            sizes[:, None] > 0, sums / jnp.maximum(sizes, 1.0)[:, None],
            centers,
        )
        return centers, labels, sizes

    centers, labels, sizes = em_update(normalize(centers))

    def body(carry, kk):
        centers, labels, sizes = carry
        centers, n_adj = _adjust_centers(
            x, labels, sizes, centers, kk, n_clusters
        )
        centers, labels, sizes = em_update(normalize(centers))
        return (centers, labels, sizes), n_adj

    (centers, labels, sizes), _ = jax.lax.scan(
        body, (centers, labels, sizes), jax.random.split(key, n_iters)
    )
    return centers, labels, sizes


def balancing_em_iters(
    x,
    centers,
    n_iters: int,
    n_clusters: int,
    key,
    metric: DistanceType = DistanceType.L2Expanded,
    compute_dtype: str = "bf16",
) -> Tuple[jax.Array, jax.Array]:
    """Run the balancing EM loop (detail/kmeans_balanced.cuh:618
    balancing_em_iters).

    The reference's pullback rule extends the budget while rebalancing
    keeps firing; that needs a per-iteration device→host readback of the
    adjustment count. Instead the loop runs a *fixed* ``n_iters + n_iters//2`` rounds
    on device (the extra half-budget plays the pullback's role of
    guaranteeing convergence iterations after the last reseed) as one
    compiled program."""
    x = jnp.asarray(x)
    rounds = max(int(n_iters) + int(n_iters) // 2, 1)
    centers, labels, sizes = _em_loop(
        x, _as_f32(centers), key, rounds, int(n_clusters), int(metric),
        compute_dtype,
    )
    return centers, sizes


def build_clusters(
    x,
    n_clusters: int,
    n_iters: int,
    key,
    metric: DistanceType = DistanceType.L2Expanded,
    init_centers=None,
    compute_dtype: str = "bf16",
) -> Tuple[jax.Array, jax.Array]:
    """EM-balanced clustering of one dataset (reference
    detail/kmeans_balanced.cuh:705 build_clusters).

    Returns (centers [C, d] f32, sizes [C] f32)."""
    x = jnp.asarray(x)
    n = x.shape[0]
    if init_centers is None:
        key, sub = jax.random.split(key)
        idx = jax.random.choice(sub, n, shape=(n_clusters,), replace=n < n_clusters)
        centers = _as_f32(x[idx])
    else:
        centers = _as_f32(init_centers)
    key, sub = jax.random.split(key)
    return balancing_em_iters(
        x, centers, n_iters, n_clusters, sub, metric, compute_dtype
    )


def _arrange_fine_clusters(
    n_clusters: int, n_mesoclusters: int, meso_sizes: np.ndarray
) -> np.ndarray:
    """Partition C fine clusters among mesoclusters proportional to size
    (reference detail/kmeans_balanced.cuh:758 arrange_fine_clusters).

    Guarantees each nonempty mesocluster gets >= 1 and the counts sum to C.
    """
    # graft-lint: allow-f64 host-side NumPy proportional split; never enters device code
    meso_sizes = meso_sizes.astype(np.float64)
    total = max(meso_sizes.sum(), 1.0)
    counts = np.zeros(n_mesoclusters, np.int64)
    remaining_c, remaining_n = n_clusters, total
    order = np.argsort(-meso_sizes)  # largest first, like the reference
    for i in order:
        if remaining_c <= 0:
            break
        c = int(round(remaining_c * meso_sizes[i] / max(remaining_n, 1.0)))
        c = max(1 if meso_sizes[i] > 0 else 0, min(c, remaining_c))
        counts[i] = c
        remaining_c -= c
        remaining_n -= meso_sizes[i]
    # dump any remainder on the largest mesocluster
    if remaining_c > 0:
        counts[order[0]] += remaining_c
    return counts


def build_hierarchical(
    x,
    n_clusters: int,
    n_iters: int = 20,
    metric: DistanceType = DistanceType.L2Expanded,
    seed: int = 0,
    compute_dtype: str = "bf16",
) -> jax.Array:
    """Two-level balanced training (reference
    detail/kmeans_balanced.cuh:955 build_hierarchical). Returns centers.

    TPU adaptation: the reference runs full per-mesocluster fine fits; here
    the hierarchy only *initializes* the centers — meso fit and per-meso
    fine fits run on fixed-size subsamples (so every fine fit shares one
    compiled shape instead of jit-recompiling per mesocluster), then the
    real work happens in full-dataset balancing EM iterations, which are a
    single compiled program. On TPU the full predict GEMM is cheap enough
    that the hierarchy's FLOP savings don't matter; compile time does.

    The dataset NEVER crosses the host boundary: only small index/label
    arrays do.
    """
    x_dev = jnp.asarray(x)
    if x_dev.dtype != jnp.float32:
        x_dev = x_dev.astype(jnp.float32)
    n, d = x_dev.shape
    key = jax.random.PRNGKey(seed)
    rng = np.random.default_rng(seed)

    n_meso = int(math.ceil(math.sqrt(n_clusters)))
    if n_clusters <= n_meso or n <= 4 * n_clusters:
        centers, _ = build_clusters(
            x_dev, n_clusters, n_iters, key, metric,
            compute_dtype=compute_dtype,
        )
        return centers

    # --- meso pass on a bounded subsample (device-side gather) -----------
    meso_sample = min(n, max(64 * n_meso, 1 << 14))
    sel = rng.choice(n, meso_sample, replace=False)
    x_meso = x_dev[jnp.asarray(sel)]
    key, k_meso = jax.random.split(key)
    meso_centers, _ = build_clusters(
        x_meso, n_meso, max(n_iters // 2, 4), k_meso, metric,
        compute_dtype=compute_dtype,
    )
    meso_labels = np.asarray(                       # [meso_sample] — small
        _predict_metric(x_meso, meso_centers, int(metric),
                        min(meso_sample, 1 << 16), compute_dtype)
    )
    meso_sizes = np.bincount(meso_labels, minlength=n_meso)
    fine_counts = _arrange_fine_clusters(n_clusters, n_meso, meso_sizes)

    # --- fine init: fixed-size subsample per mesocluster, ALL fine fits
    # batched into one compiled program (build_clusters_batched) — the
    # per-meso host loop of separate fits costs one dispatch round-trip
    # per mesocluster. Row picking
    # happens on host over the small label array; rows are gathered on
    # device in one shot. ------------------------------------------------
    c_max = int(fine_counts.max())
    S = max(32 * c_max, 256)  # one shared shape for all fine fits
    active = [m for m in range(n_meso) if fine_counts[m] > 0]
    pick = np.empty((len(active), S), np.int64)
    for bi, m in enumerate(active):
        members = np.nonzero(meso_labels == m)[0]
        if members.size == 0:
            pick[bi] = rng.choice(n, S, replace=n < S)
        else:
            pick[bi] = sel[rng.choice(members, S, replace=members.size < S)]
    rows_all = x_dev[jnp.asarray(pick.reshape(-1))].reshape(len(active), S, d)
    key, sub = jax.random.split(key)
    # few iterations — this is only an init for the balancing phase
    books = build_clusters_batched(rows_all, c_max, 4, sub, int(metric))
    # slice each book's share on device; concatenate stays on device
    centers = jnp.concatenate(
        [books[bi, : int(fine_counts[m])] for bi, m in enumerate(active)],
        axis=0,
    )
    assert centers.shape[0] == n_clusters

    # --- full-dataset balancing EM (the real training) -------------------
    key, sub = jax.random.split(key)
    centers, _ = balancing_em_iters(
        x_dev, centers, max(n_iters // 2, 2), n_clusters, sub, metric,
        compute_dtype,
    )
    return centers


# ---------------------------------------------------------------------------
# public API (reference kmeans_balanced.cuh:76,134,199)
# ---------------------------------------------------------------------------


def fit(params: KMeansBalancedParams, x) -> jax.Array:
    """Train balanced centers (kmeans_balanced.cuh:76). Returns [C, d]."""
    return build_hierarchical(
        x, params.n_clusters, params.n_iters, params.metric, params.seed,
        params.compute_dtype,
    )


def predict(params: KMeansBalancedParams, centers, x) -> jax.Array:
    """Nearest-center labels (kmeans_balanced.cuh:134)."""
    x = jnp.asarray(x)
    return _predict_metric(
        x, _as_f32(centers), int(params.metric), min(x.shape[0], 1 << 16),
        params.compute_dtype,
    )


def fit_predict(params: KMeansBalancedParams, x):
    """fit + predict (kmeans_balanced.cuh:199)."""
    centers = fit(params, x)
    return centers, predict(params, centers, x)


@functools.partial(jax.jit, static_argnums=(1, 2, 4))
def build_clusters_batched(xs, n_clusters: int, n_iters: int, key,
                           metric: int = int(DistanceType.L2Expanded)):
    """Train B independent codebooks in one compiled program — the batched
    replacement for the reference's per-subspace / per-cluster
    ``build_clusters`` loops (detail/ivf_pq_build.cuh:395 train_per_subset,
    :472 train_per_cluster, which launch one trainer per book) and for the
    hierarchical trainer's per-mesocluster fine fits.

    ``xs`` [B, n, d] -> centers [B, K, d]. Sequential scan over B (one
    compile, bounded memory); each book runs ``n_iters`` Lloyd iterations
    with starved-cluster reseeding from random rows. IP/Cosine metrics
    assign by max dot with per-iteration center normalization (matching
    build_clusters' angular geometry).
    """
    B, n, d = xs.shape
    ip_like = metric in (
        int(DistanceType.InnerProduct), int(DistanceType.CosineExpanded)
    )

    def one_book(_, inp):
        x, key = inp
        k_init, k_iters = jax.random.split(key)
        idx = jax.random.randint(k_init, (n_clusters,), 0, n)
        centers = x[idx]

        def iter_body(centers, kk):
            if ip_like:
                cnorm = jnp.linalg.norm(centers, axis=1, keepdims=True)
                centers = centers / jnp.maximum(cnorm, 1e-30)
            dots = jnp.dot(x, centers.T, preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGH)
            if ip_like:
                labels = argmax_exact(dots, axis=1)
            else:
                cn2 = jnp.sum(centers * centers, axis=1)
                labels = argmin_exact(cn2[None, :] - 2.0 * dots, axis=1)
            one_hot = (
                labels[:, None] == jnp.arange(n_clusters)[None, :]
            ).astype(jnp.float32)
            sums = jnp.dot(one_hot.T, x, preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGH)
            sizes = one_hot.sum(axis=0)
            reseed = x[jax.random.randint(kk, (n_clusters,), 0, n)]
            centers = jnp.where(
                sizes[:, None] > 0,
                sums / jnp.maximum(sizes, 1.0)[:, None],
                reseed,
            )
            return centers, None

        centers, _ = jax.lax.scan(
            iter_body, centers, jax.random.split(k_iters, n_iters)
        )
        return None, centers

    _, books = jax.lax.scan(
        one_book, None, (xs.astype(jnp.float32), jax.random.split(key, B))
    )
    return books


def calc_centers_and_sizes(x, labels, n_clusters: int):
    """Per-cluster means and sizes (reference helper
    detail/kmeans_balanced.cuh:257). Returns (centers, sizes)."""
    x = _as_f32(x)
    sums, sizes = _centers_and_sizes(
        x, jnp.asarray(labels), None, int(n_clusters), min(x.shape[0], 1 << 16)
    )
    centers = sums / jnp.maximum(sizes, 1.0)[:, None]
    return centers, sizes
