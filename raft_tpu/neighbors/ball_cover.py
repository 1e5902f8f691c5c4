"""Random ball cover — *exact* KNN via landmarks + triangle-inequality
pruning (reference neighbors/ball_cover.cuh: BallCoverIndex,
build_index, knn_query, all_knn_query, eps_nn; impl
spatial/knn/detail/ball_cover.cuh + ball_cover/registers.cuh).

Algorithm (same maths as the reference's rbc):
  build: C ≈ √n landmarks (balanced kmeans), every point stored in its
  nearest landmark's list; per-list radius = max point↔landmark distance.
  search: with true-metric distances, list i can contain a better-than-kth
  neighbor only if d(q, cᵢ) − radiusᵢ < kth. Phase 1 scans the p₀
  closest lists to bound kth; phase 2 scans exactly the per-query prefix
  of the lb-sorted list order where lb < kth — everything outside is
  *provably* prunable, so the result is exact.

TPU design: the reference's per-thread register-tiled pruning loop
becomes two batched phases — an [m, C] landmark GEMM, then a
``lax.scan`` over probe positions that gathers one [m, cap, d] list
block per step and folds it into a running top-k (no per-point
branching: pruning happens at list granularity, which is where the
batched-bound math is MXU-shaped). Probe counts are data-dependent, so
the certification loop doubles the probe prefix on the host (≤ log C
rounds) until every query's remaining lower bounds clear its kth — the
same adaptive widening the IVF search uses for recall targets, but with
an exactness certificate instead of a heuristic.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.distance.types import DistanceType, resolve_metric
from raft_tpu.distance.pairwise import pairwise_distance
from raft_tpu.neighbors.ivf_flat import _aligned_cap, _pack_lists
from raft_tpu.utils.precision import argmin_exact, dist_dot

_SUPPORTED = {
    DistanceType.L2SqrtExpanded,
    DistanceType.L2SqrtUnexpanded,
    DistanceType.Haversine,
}


@dataclasses.dataclass
class BallCoverIndex:
    """reference ball_cover_types.hpp BallCoverIndex."""

    landmarks: jax.Array     # [C, d] f32
    storage: jax.Array       # [C, cap, d]
    indices: jax.Array       # [C, cap] i32, -1 pad
    list_sizes: jax.Array    # [C] i32
    radii: jax.Array         # [C] f32 — max member distance per landmark
    metric: DistanceType

    @property
    def n_landmarks(self) -> int:
        return self.landmarks.shape[0]

    @property
    def dim(self) -> int:
        return self.landmarks.shape[1]

    @property
    def size(self) -> int:
        return int(self.list_sizes.sum())


jax.tree_util.register_dataclass(
    BallCoverIndex,
    data_fields=["landmarks", "storage", "indices", "list_sizes", "radii"],
    meta_fields=["metric"],
)


def _true_metric(metric) -> DistanceType:
    metric = resolve_metric(metric)
    if metric == DistanceType.L2Expanded:
        metric = DistanceType.L2SqrtExpanded  # triangle inequality needs √
    if metric not in _SUPPORTED:
        raise ValueError(
            f"ball_cover needs a true metric (euclidean/haversine), got {metric}"
        )
    return metric


def build(
    dataset, metric="euclidean", n_landmarks: Optional[int] = None, seed: int = 0
) -> BallCoverIndex:
    """Build the ball cover (reference ball_cover.cuh:56 build_index;
    landmark count defaults to √n as in ball_cover_types.hpp)."""
    from raft_tpu import obs
    from raft_tpu.cluster import kmeans_balanced

    metric = _true_metric(metric)
    dataset = jnp.asarray(dataset, jnp.float32)
    n, d = dataset.shape
    C = int(n_landmarks or max(1, int(math.sqrt(n))))

    with obs.entry_span("build", "ball_cover", rows=n, landmarks=C):
        # L2 kmeans for every metric — for Haversine, kmeans in lat/lon
        # radians approximates well for local extents, and landmark
        # geometry only affects pruning efficiency, not exactness
        landmarks = kmeans_balanced.build_hierarchical(
            dataset, C, metric=DistanceType.L2Expanded, seed=seed
        )
        d_pl = pairwise_distance(dataset, landmarks, metric)  # [n, C] true
        labels = argmin_exact(d_pl, axis=1)
        dist_to_lm = jnp.min(d_pl, axis=1)

        # graft-lint: allow-host-sync build list capacity must be concrete to allocate
        counts = np.asarray(jnp.bincount(labels, length=C))
        cap = _aligned_cap(int(counts.max()) if n else 1)
        storage, indices, list_sizes = _pack_lists(
            dataset, labels, jnp.arange(n, dtype=jnp.int32), C, cap
        )
        radii = jnp.zeros((C,), jnp.float32).at[labels].max(dist_to_lm)
        return BallCoverIndex(landmarks, storage, indices, list_sizes,
                              radii, metric)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _scan_lists(
    queries, storage, indices, probe_lists, init, k: int, metric_val: int
):
    """Fold the per-query probe lists into a running top-k.

    queries [m, d]; probe_lists [m, P]; init (dists [m, k], ids [m, k])
    carried from a previous phase (±inf/-1 for a fresh start).
    """
    metric = DistanceType(metric_val)
    m, d = queries.shape
    cap = storage.shape[1]

    def step(carry, p):
        top_d, top_i = carry
        lists = probe_lists[:, p]                      # [m]
        block = storage[lists]                         # [m, cap, d]
        ids = indices[lists]                           # [m, cap]
        if metric == DistanceType.Haversine:
            lat1, lon1 = queries[:, 0:1], queries[:, 1:2]
            lat2, lon2 = block[..., 0], block[..., 1]
            sdlat = jnp.sin(0.5 * (lat1 - lat2))
            sdlon = jnp.sin(0.5 * (lon1 - lon2))
            a = sdlat**2 + jnp.cos(lat1) * jnp.cos(lat2) * sdlon**2
            dist = 2.0 * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))
        else:
            # batched L2: ||q||² − 2 q·x + ||x||², einsum rides the MXU
            qn = jnp.sum(queries * queries, axis=1, keepdims=True)
            xn = jnp.sum(block * block, axis=2)
            qx = jnp.einsum(
                "md,mcd->mc", queries, block,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            dist = jnp.sqrt(jnp.maximum(qn - 2.0 * qx + xn, 0.0))
        dist = jnp.where(ids >= 0, dist, jnp.inf)      # mask list padding
        # de-dup vs already-kept ids (lists can repeat across phases)
        seen = jnp.any(ids[:, :, None] == top_i[:, None, :], axis=2)
        dist = jnp.where(seen, jnp.inf, dist)
        cat_d = jnp.concatenate([top_d, dist], axis=1)
        cat_i = jnp.concatenate([top_i, ids], axis=1)
        nd, sel = jax.lax.top_k(-cat_d, k)
        return (-nd, jnp.take_along_axis(cat_i, sel, axis=1)), None

    (top_d, top_i), _ = jax.lax.scan(
        step, init, jnp.arange(probe_lists.shape[1])
    )
    return top_d, top_i


def knn_query(
    index: BallCoverIndex,
    queries,
    k: int,
    query_block: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """Exact KNN (reference ball_cover.cuh:150 knn_query). Certified by the
    triangle inequality — results match brute force bit-for-bit up to ties."""
    queries = jnp.asarray(queries, jnp.float32)
    m = queries.shape[0]
    out = [
        _knn_block(index, queries[r0 : min(r0 + query_block, m)], k)
        for r0 in range(0, m, query_block)
    ]
    return (
        jnp.concatenate([o[0] for o in out]),
        jnp.concatenate([o[1] for o in out]),
    )


def _knn_block(index: BallCoverIndex, queries, k: int):
    C = index.n_landmarks
    m = queries.shape[0]
    dql = pairwise_distance(queries, index.landmarks, index.metric)  # [m, C]
    lb = jnp.maximum(dql - index.radii[None, :], 0.0)
    order = jnp.argsort(lb, axis=1).astype(jnp.int32)                # [m, C]
    lb_sorted = jnp.take_along_axis(lb, order, axis=1)

    k_eff = min(k, max(index.size, 1))
    init = (
        jnp.full((m, k), jnp.inf, jnp.float32),
        jnp.full((m, k), -1, jnp.int32),
    )
    p0 = min(C, max(2, int(math.ceil(math.sqrt(C)))))
    scanned = 0
    top_d, top_i = init
    while scanned < C:
        p1 = min(C, max(p0, 2 * scanned))
        top_d, top_i = _scan_lists(
            queries, index.storage, index.indices,
            order[:, scanned:p1], (top_d, top_i), k, int(index.metric),
        )
        scanned = p1
        if scanned >= C:
            break
        kth = top_d[:, k_eff - 1]
        # certified once no remaining list can beat the kth distance
        # graft-lint: allow-host-sync host-driven certification loop is the algorithm (<= log C syncs)
        need_more = bool(jnp.any(lb_sorted[:, scanned] < kth))
        if not need_more:
            break
    return top_d, top_i


def _reconstruct_dataset(index: BallCoverIndex) -> jax.Array:
    """Stored rows back in source-id order, entirely ON DEVICE: one
    scatter instead of the former numpy round trip (GL001 flagged the
    ``np.asarray`` pair on this query path — two full-index host
    transfers per call)."""
    n = index.size
    flat_i = index.indices.reshape(-1)
    rows = index.storage.reshape(-1, index.dim)
    # padding slots target row n, which mode="drop" discards
    tgt = jnp.where(flat_i >= 0, flat_i, n)
    return jnp.zeros((n, index.dim), rows.dtype).at[tgt].set(rows, mode="drop")


def all_knn_query(
    index: BallCoverIndex, k: int, query_block: int = 4096
) -> Tuple[jax.Array, jax.Array]:
    """Self-KNN over the indexed dataset (ball_cover.cuh:100
    all_knn_query): queries are the stored points in id order."""
    return knn_query(index, _reconstruct_dataset(index), k, query_block)


def eps_nn(
    index: BallCoverIndex, queries, eps: float, query_block: int = 4096
) -> Tuple[jax.Array, jax.Array]:
    """Epsilon neighborhood via the ball cover (ball_cover.cuh:219 eps_nn):
    returns (adj [m, n] bool, vertex degrees [m]).

    List-level pruning bounds the work, then exact distances fill a dense
    adjacency (the reference writes a dense boolean adjacency too).
    """
    from raft_tpu.neighbors.epsilon_neighborhood import eps_neighbors

    queries = jnp.asarray(queries, jnp.float32)
    return eps_neighbors(queries, _reconstruct_dataset(index), eps, index.metric)
