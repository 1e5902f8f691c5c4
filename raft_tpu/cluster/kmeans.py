"""Lloyd's k-means — fit / predict / transform with random and k-means++ init.

TPU-native analog of the reference's ``raft::cluster::kmeans``
(cpp/include/raft/cluster/kmeans.cuh:88,152,215 and
cpp/include/raft/cluster/detail/kmeans.cuh:64,90,361,434). The reference's
hot loop — ``minClusterAndDistanceCompute`` (fused-L2-NN based) followed by
a weighted scatter of points into centroid sums — maps to:

  * predict: ``fused_l2_nn_argmin`` (a tiled MXU GEMM + argmin epilogue),
    row-batched with ``lax.scan`` so peak memory stays at batch x n_clusters;
  * update: one-hot matmul (``one_hot.T @ X``) instead of atomics — a
    [B, C] x [B, d] MXU contraction per batch, accumulated across batches.

The whole fit loop runs under one ``jit`` with ``lax.while_loop`` on the
inertia-change tolerance, like the reference's batched ``kmeans_fit_main``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.distance.types import DistanceType
from raft_tpu.distance.fused_l2_nn import _fused_l2_nn
from raft_tpu.utils.math import round_up_to_multiple
from raft_tpu.utils.precision import argmax_exact, dist_dot


@dataclasses.dataclass
class KMeansParams:
    """Aggregate param struct (reference cluster/kmeans_types.hpp KMeansParams;
    pylibraft cluster/kmeans.pyx:368)."""

    n_clusters: int = 8
    max_iter: int = 300
    tol: float = 1e-4
    init: str = "k-means++"  # 'k-means++' | 'random' | 'array'
    n_init: int = 1
    seed: int = 0
    metric: DistanceType = DistanceType.L2Expanded
    batch_rows: int = 1 << 16
    oversampling_factor: float = 2.0  # accepted for API parity (scalable init)


# ---------------------------------------------------------------------------
# jitted primitives
# ---------------------------------------------------------------------------


def _row_batches(x: jax.Array, batch_rows: int) -> Tuple[jax.Array, jax.Array, int]:
    """Pad x to a multiple of batch_rows and reshape to [nb, B, d].

    Returns (batches, valid_mask [nb, B], n)."""
    n, d = x.shape
    b = min(batch_rows, n)
    npad = round_up_to_multiple(n, b)
    xp = jnp.pad(x, ((0, npad - n), (0, 0)))
    valid = (jnp.arange(npad) < n).reshape(npad // b, b)
    return xp.reshape(npad // b, b, d), valid, n


@functools.partial(jax.jit, static_argnums=(2,))
def _predict_labels(x, centers, batch_rows: int):
    """argmin_c ||x_i - center_c||^2 per row, batched over rows.

    Returns (labels [n] int32, min_sq_dist [n] f32)."""
    xb, valid, n = _row_batches(x.astype(jnp.float32), batch_rows)

    def body(_, batch):
        dist, idx = _fused_l2_nn(batch, centers, False, centers.shape[0])
        return None, (idx, dist)

    _, (labels, dists) = jax.lax.scan(body, None, xb)
    return labels.reshape(-1)[:n], dists.reshape(-1)[:n]


_L2_METRICS = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.L2Unexpanded,
    DistanceType.L2SqrtUnexpanded,
)


def _check_metric(metric: DistanceType) -> DistanceType:
    metric = DistanceType(metric)
    if metric not in _L2_METRICS and metric != DistanceType.CosineExpanded:
        raise ValueError(
            f"kmeans supports L2 and cosine metrics, got {metric!r} "
            "(reference kmeans has the same restriction)"
        )
    return metric


@functools.partial(jax.jit, static_argnums=(2, 3))
def _predict_metric_labels(x, centers, metric_val: int, batch_rows: int):
    """Metric-aware predict: L2 via fused-L2-NN, cosine via normalized
    argmax-dot. Returns (labels, dists) where dists is the per-row cost
    contribution (squared L2 or 1 - cos)."""
    metric = DistanceType(metric_val)
    if metric in _L2_METRICS:
        return _predict_labels(x, centers, batch_rows)
    # CosineExpanded
    x = x.astype(jnp.float32)
    cn = centers / jnp.maximum(
        jnp.linalg.norm(centers, axis=1, keepdims=True), 1e-30
    )
    xb, valid, n = _row_batches(x, batch_rows)

    def body(_, batch):
        bn = batch / jnp.maximum(jnp.linalg.norm(batch, axis=1, keepdims=True), 1e-30)
        scores = dist_dot(bn, cn.T)
        lab = argmax_exact(scores, axis=1)
        return None, (lab, 1.0 - jnp.max(scores, axis=1))

    _, (labels, dists) = jax.lax.scan(body, None, xb)
    return labels.reshape(-1)[:n], dists.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _centers_and_sizes(x, labels, weights, n_clusters: int, batch_rows: int):
    """Weighted per-cluster sums and sizes via batched one-hot MXU matmuls.

    Analog of the reference's ``calc_centers_and_sizes``
    (cluster/detail/kmeans_balanced.cuh:257) without atomics.
    Returns (sums [C, d], sizes [C])."""
    x = x.astype(jnp.float32)
    xb, valid, n = _row_batches(x, batch_rows)
    nb, b, d = xb.shape
    lp = jnp.pad(labels, (0, nb * b - n), constant_values=-1).reshape(nb, b)
    if weights is None:
        wp = valid.astype(jnp.float32)
    else:
        wp = jnp.pad(weights.astype(jnp.float32), (0, nb * b - n)).reshape(nb, b)
        wp = wp * valid

    def body(carry, inp):
        sums, sizes = carry
        batch, lab, w = inp
        one_hot = (lab[:, None] == jnp.arange(n_clusters)[None, :]).astype(
            jnp.float32
        ) * w[:, None]
        sums = sums + dist_dot(one_hot.T, batch)
        sizes = sizes + one_hot.sum(axis=0)
        return (sums, sizes), None

    init = (jnp.zeros((n_clusters, d), jnp.float32), jnp.zeros((n_clusters,), jnp.float32))
    (sums, sizes), _ = jax.lax.scan(body, init, (xb, lp, wp))
    return sums, sizes


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _fit_loop(
    x, init_centers, weights, max_iter: int, tol: float, batch_rows: int,
    metric_val: int = int(DistanceType.L2Expanded),
):
    """Full Lloyd loop under jit (reference detail/kmeans.cuh kmeans_fit_main)."""
    n_clusters = init_centers.shape[0]

    def cond(state):
        it, _, prev_inertia, inertia, _ = state
        first = it == 0
        # strict relative-improvement test; prev=inf (first real iter)
        # always passes since any finite inertia < inf * (1 - tol)
        improving = inertia < prev_inertia * (1.0 - tol)
        return (it < max_iter) & (first | improving)

    def body(state):
        it, centers, _, inertia, _ = state
        labels, dists = _predict_metric_labels(x, centers, metric_val, batch_rows)
        w = None if weights is None else weights
        sums, sizes = _centers_and_sizes(x, labels, w, n_clusters, batch_rows)
        new_centers = jnp.where(
            sizes[:, None] > 0, sums / jnp.maximum(sizes, 1.0)[:, None], centers
        )
        if weights is None:
            new_inertia = dists.sum()
        else:
            new_inertia = (dists * weights).sum()
        return it + 1, new_centers, inertia, new_inertia, labels

    n = x.shape[0]
    state = (
        jnp.int32(0),
        init_centers.astype(jnp.float32),
        jnp.float32(jnp.inf),
        jnp.float32(jnp.inf),
        jnp.zeros((n,), jnp.int32),
    )
    it, centers, _, inertia, labels = jax.lax.while_loop(cond, body, state)
    return centers, inertia, it, labels


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_random(x, n_clusters: int, key) -> jax.Array:
    """Random-sample init (reference detail/kmeans.cuh:64 initRandom)."""
    n = x.shape[0]
    idx = jax.random.choice(key, n, shape=(n_clusters,), replace=n < n_clusters)
    return jnp.asarray(x)[idx].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def _init_plus_plus(x, n_clusters: int, key):
    x = jnp.asarray(x).astype(jnp.float32)
    n, d = x.shape
    key, sub = jax.random.split(key)
    first = jax.random.randint(sub, (), 0, n)
    centers0 = jnp.zeros((n_clusters, d), jnp.float32).at[0].set(x[first])
    xn = jnp.sum(x * x, axis=1)

    def sq_dist_to(c):
        return jnp.maximum(xn - 2.0 * dist_dot(x, c) + jnp.sum(c * c), 0.0)

    def body(carry, key_c):
        centers, min_d2, c = carry
        # sample next center ~ min_d2 (D^2 weighting)
        p = min_d2 / jnp.maximum(min_d2.sum(), 1e-30)
        nxt = jax.random.choice(key_c, n, p=p)
        centers = centers.at[c].set(x[nxt])
        min_d2 = jnp.minimum(min_d2, sq_dist_to(x[nxt]))
        return (centers, min_d2, c + 1), None

    min_d2 = sq_dist_to(x[first])
    keys = jax.random.split(key, n_clusters - 1)
    (centers, _, _), _ = jax.lax.scan(body, (centers0, min_d2, jnp.int32(1)), keys)
    return centers


def init_plus_plus(x, n_clusters: int, seed: int = 0, key=None) -> jax.Array:
    """k-means++ D^2-weighted seeding (reference detail/kmeans.cuh:90
    kmeansPlusPlus; pylibraft cluster/kmeans.pyx:198 init_plus_plus)."""
    if key is None:
        key = jax.random.PRNGKey(seed)
    return _init_plus_plus(jnp.asarray(x), int(n_clusters), key)


# ---------------------------------------------------------------------------
# public API (pylibraft cluster/kmeans.pyx parity)
# ---------------------------------------------------------------------------


def fit(
    params: Union[KMeansParams, int],
    x,
    centroids=None,
    sample_weights=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fit k-means. Returns (centroids [C, d], inertia, n_iter).

    Mirrors pylibraft ``cluster.kmeans.fit`` (kmeans.pyx:482). ``params`` may
    be a KMeansParams or a bare n_clusters int.
    """
    return _fit_impl(params, x, centroids, sample_weights)[:3]


def _fit_impl(params, x, centroids=None, sample_weights=None):
    """fit() that also returns the final-iteration labels (used by find_k
    to avoid a second full predict pass)."""
    if not isinstance(params, KMeansParams):
        params = KMeansParams(n_clusters=int(params))
    metric = _check_metric(params.metric)
    x = jnp.asarray(x)
    w = None if sample_weights is None else jnp.asarray(sample_weights)
    if params.init == "array" and centroids is None:
        raise ValueError("init='array' requires explicit centroids")

    best = None
    # explicit centroids make every trial identical — run just one
    n_trials = 1 if centroids is not None else max(1, params.n_init)
    key = jax.random.PRNGKey(params.seed)
    for trial in range(n_trials):
        key, k_init = jax.random.split(key)
        if centroids is not None:
            init_c = jnp.asarray(centroids).astype(jnp.float32)
        elif params.init == "random":
            init_c = init_random(x, params.n_clusters, k_init)
        else:
            init_c = _init_plus_plus(x, params.n_clusters, k_init)
        centers, inertia, n_iter, labels = _fit_loop(
            x, init_c, w, params.max_iter, params.tol, params.batch_rows,
            int(metric),
        )
        if best is None or float(inertia) < float(best[1]):
            best = (centers, inertia, n_iter, labels)
    return best


def predict(
    params: Union[KMeansParams, int],
    centroids,
    x,
    sample_weights=None,
    normalize_weights: bool = True,
) -> jax.Array:
    """Label each row with its nearest centroid (kmeans.cuh:152)."""
    if not isinstance(params, KMeansParams):
        params = KMeansParams(n_clusters=int(params))
    metric = _check_metric(params.metric)
    labels, _ = _predict_metric_labels(
        jnp.asarray(x).astype(jnp.float32),
        jnp.asarray(centroids).astype(jnp.float32),
        int(metric),
        params.batch_rows,
    )
    return labels


def fit_predict(params, x, centroids=None, sample_weights=None):
    """fit + predict (kmeans.cuh:215)."""
    centers, inertia, n_iter = fit(params, x, centroids, sample_weights)
    labels = predict(params, centers, x)
    return labels, centers, inertia, n_iter


def transform(params, centroids, x) -> jax.Array:
    """Pairwise distance of every row to every centroid (kmeans transform)."""
    from raft_tpu.distance import pairwise_distance

    if not isinstance(params, KMeansParams):
        params = KMeansParams(n_clusters=int(params))
    return pairwise_distance(x, centroids, metric=params.metric)


def cluster_cost(x, centroids) -> jax.Array:
    """Total inertia: sum of squared distance to nearest centroid
    (pylibraft kmeans.pyx:280 cluster_cost)."""
    _, dists = _predict_labels(
        jnp.asarray(x).astype(jnp.float32),
        jnp.asarray(centroids).astype(jnp.float32),
        1 << 16,
    )
    return dists.sum()


def compute_new_centroids(x, centroids, labels=None, sample_weights=None):
    """One centroid-update step (pylibraft kmeans.pyx:54
    compute_new_centroids)."""
    x = jnp.asarray(x)
    centroids = jnp.asarray(centroids).astype(jnp.float32)
    if labels is None:
        labels, _ = _predict_labels(x.astype(jnp.float32), centroids, 1 << 16)
    w = None if sample_weights is None else jnp.asarray(sample_weights)
    sums, sizes = _centers_and_sizes(x, labels, w, centroids.shape[0], 1 << 16)
    return jnp.where(
        sizes[:, None] > 0, sums / jnp.maximum(sizes, 1.0)[:, None], centroids
    )


def find_k(
    x,
    kmax: int,
    kmin: int = 1,
    max_iter: int = 100,
    tol: float = 1e-2,
    seed: int = 0,
) -> Tuple[int, jax.Array, jax.Array]:
    """Auto-find-k by maximizing the Calinski-Harabasz-style objective
    ``(n-k)/(k-1) * cluster_dispersion(k) / inertia(k)`` with a bisection
    on its slope — the reference's dispersion-based method
    (cluster/detail/kmeans_auto_find_k.cuh: compute_dispersion + the
    objective[0/1] slope test). Returns (k, inertia, n_iter)."""
    from raft_tpu.stats.moments import cluster_dispersion

    x = jnp.asarray(x)
    n = x.shape[0]
    cache = {}

    def eval_k(k: int):
        if k not in cache:
            centers, inertia, n_iter, labels = _fit_impl(
                KMeansParams(n_clusters=k, max_iter=max_iter, tol=tol, seed=seed),
                x,
            )
            sizes = jnp.bincount(labels, length=k)
            disp = float(cluster_dispersion(centers, sizes, n))
            ch = (n - k) / max(k - 1, 1) * disp / max(float(inertia), 1e-30)
            cache[k] = (ch, float(inertia), n_iter)
        return cache[k]

    left, right = max(2, int(kmin)), int(kmax)
    if right <= left:
        _, inertia, n_iter = eval_k(max(left, 2))
        return max(left, 2), jnp.float32(inertia), n_iter
    eval_k(left)
    eval_k(right)
    while left < right - 1:
        mid = (left + right) // 2
        slope_l = (eval_k(mid)[0] - eval_k(left)[0]) / (mid - left)
        if slope_l <= 0:
            right = mid  # CH already falling: peak is at or left of mid
            continue
        slope_r = (eval_k(right)[0] - eval_k(mid)[0]) / (right - mid)
        if slope_r < 0:
            right = mid  # interior peak, left side
        else:
            left = mid
    # every evaluated k is a candidate — the bracket walk can step past
    # the peak when the curve is noisy
    best_k = max(cache, key=lambda k: cache[k][0])
    _, inertia, n_iter = eval_k(best_k)
    return best_k, jnp.float32(inertia), n_iter
