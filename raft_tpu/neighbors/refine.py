"""Exact re-ranking of ANN candidate lists.

Analog of the reference's ``refine`` (cpp/include/raft/neighbors/refine.cuh;
device impl detail/refine_device.cuh, host OpenMP impl
detail/refine_host-inl.hpp). Given candidate neighbor ids per query, compute
exact distances to those candidates and keep the best k. Used by CAGRA's
graph build and by benchmarks to boost IVF-PQ recall.

The TPU formulation is a batched gather + einsum: candidates [m, c] gather
to [m, c, d]; distances per (query, candidate) via the expanded form on the
MXU; then top-k. Works on device arrays or numpy (the "host" variant is the
same code on CPU backend).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from raft_tpu.distance.types import DistanceType, is_min_close, resolve_metric
from raft_tpu.neighbors.common import merge_topk, sentinel_for


def refine(
    dataset,
    queries,
    candidates,
    k: int,
    metric="sqeuclidean",
) -> Tuple[jax.Array, jax.Array]:
    """Re-rank ``candidates`` [n_queries, n_cand] exactly; return top-k.

    Negative candidate ids are treated as invalid (the reference uses them
    the same way for ragged candidate lists).
    """
    metric = resolve_metric(metric)
    dataset = jnp.asarray(dataset)
    queries = jnp.asarray(queries)
    candidates = jnp.asarray(candidates)
    if k > candidates.shape[1]:
        raise ValueError(f"k={k} > n_candidates={candidates.shape[1]}")
    return _refine(dataset, queries, candidates, int(k), int(metric))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _refine(dataset, queries, candidates, k: int, metric_val: int):
    metric = DistanceType(metric_val)
    compute = jnp.promote_types(queries.dtype, jnp.float32)
    q = queries.astype(compute)  # [m, d]
    valid = candidates >= 0
    safe = jnp.where(valid, candidates, 0)
    cand_vecs = dataset[safe].astype(compute)  # [m, c, d]
    return score_gathered(q, cand_vecs, candidates, k, metric)


def score_gathered(q, cand_vecs, candidates, k: int,
                   metric: DistanceType):
    """Exact-scoring tail shared by every gathered-candidate rerank:
    ``q`` [m, d] and ``cand_vecs`` [m, c, d] already at the compute
    dtype, ``candidates`` [m, c] with < 0 marking invalid slots. ONE
    home on purpose — :mod:`raft_tpu.neighbors.tiered` gathers the same
    rows from its fetched/hot blocks instead of a resident dataset, and
    the bitwise-identity acceptance (tiered vs full-upload rerank on
    the same shortlist) holds exactly because both paths run THIS
    arithmetic on value-identical operands. Called inside jit."""
    return select_scored(exact_scores(q, cand_vecs, metric), candidates,
                         k, metric, q.dtype)


def exact_scores(q, cand_vecs, metric: DistanceType):
    """The distance of every (query, candidate) pair, [m, c]:
    :func:`score_gathered` before invalid slots sink and the top-k. An
    entry depends on its own query and row alone, so scores of the rows
    one chip holds, zero elsewhere, join the other chips' by a sum
    (``tiered.RowShardedSource``)."""
    compute = q.dtype
    if metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded):
        # ||q - v||^2 via einsum (MXU): q·v per (query, cand)
        dots = jnp.einsum("md,mcd->mc", q, cand_vecs, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        vn = jnp.sum(cand_vecs * cand_vecs, axis=2)
        d = jnp.maximum(qn + vn - 2.0 * dots, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            d = jnp.sqrt(d)
    elif metric == DistanceType.InnerProduct:
        d = jnp.einsum("md,mcd->mc", q, cand_vecs, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    elif metric == DistanceType.CosineExpanded:
        dots = jnp.einsum("md,mcd->mc", q, cand_vecs, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        qn = jnp.sqrt(jnp.sum(q * q, axis=1, keepdims=True))
        vn = jnp.sqrt(jnp.sum(cand_vecs * cand_vecs, axis=2))
        d = 1.0 - dots / jnp.maximum(qn * vn, jnp.finfo(compute).tiny)
    else:
        # generic elementwise fallback
        diff = q[:, None, :] - cand_vecs
        d = jnp.sum(jnp.abs(diff) if metric == DistanceType.L1 else diff * diff, axis=2)
    return d


def select_scored(d, candidates, k: int, metric: DistanceType, compute):
    """Invalid slots (``candidates < 0``) sink to the sentinel of the
    ``compute`` dtype, then the best ``k`` of the scored candidates."""
    d = jnp.where(candidates >= 0, d, sentinel_for(metric, compute))
    return merge_topk(d, candidates.astype(jnp.int32), k,
                      is_min_close(metric))


def refine_host(
    dataset,
    queries,
    candidates,
    k: int,
    metric="sqeuclidean",
    n_threads: int = 0,
) -> Tuple["np.ndarray", "np.ndarray"]:
    """Host-side exact re-ranking over numpy (or ``np.memmap``) data —
    the analog of the reference's OpenMP ``refine_host``
    (cpp/include/raft/neighbors/detail/refine_host-inl.hpp), used when
    the dataset lives on the host (e.g. file-backed / larger than HBM).

    ``dataset`` is indexed row-wise only (memmap-friendly); work is
    split over ``n_threads`` Python threads (numpy releases the GIL in
    the BLAS/reduction kernels, mirroring the reference's OpenMP loop).
    """
    import concurrent.futures as _cf
    import os as _os

    import numpy as np

    metric = resolve_metric(metric)
    if metric not in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded,
                      DistanceType.InnerProduct):
        raise ValueError(f"refine_host supports L2/IP metrics, got {metric!r}")
    q = np.asarray(queries, dtype=np.float32)
    cand = np.asarray(candidates)
    m, c = cand.shape
    if k > c:
        raise ValueError(f"k={k} > n_candidates={c}")
    if n_threads <= 0:
        n_threads = min(32, _os.cpu_count() or 1)
    out_d = np.empty((m, k), np.float32)
    out_i = np.empty((m, k), np.int32)
    minimize = metric != DistanceType.InnerProduct

    def work(lo, hi):
        for i in range(lo, hi):
            ids = cand[i]
            valid = ids >= 0
            rows = np.asarray(dataset[ids[valid].astype(np.int64)],
                              dtype=np.float32)
            dots = rows @ q[i]
            if metric == DistanceType.InnerProduct:
                d = dots
            else:
                d = (rows * rows).sum(1) - 2.0 * dots + q[i] @ q[i]
                np.maximum(d, 0.0, out=d)
                if metric == DistanceType.L2SqrtExpanded:
                    np.sqrt(d, out=d)
            full = np.full(c, np.inf if minimize else -np.inf, np.float32)
            full[valid] = d
            order = np.argsort(full if minimize else -full, kind="stable")[:k]
            out_d[i] = full[order]
            out_i[i] = np.where(np.isfinite(full[order]), ids[order], -1)

    chunk = max(1, -(-m // n_threads))
    with _cf.ThreadPoolExecutor(max_workers=n_threads) as ex:
        futs = [ex.submit(work, lo, min(lo + chunk, m))
                for lo in range(0, m, chunk)]
        for f in futs:
            f.result()
    return out_d, out_i
