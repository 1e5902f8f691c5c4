"""Numpy reference oracles for tests.

Analog of the reference's naive-KNN oracle + recall-bound evaluation
(cpp/internal/raft_internal/neighbors/naive_knn.cuh:31-90,
cpp/test/neighbors/ann_utils.cuh:155,218 eval_neighbours/eval_recall).
"""

from __future__ import annotations

import numpy as np


def naive_pairwise(x: np.ndarray, y: np.ndarray, metric: str, p: float = 2.0) -> np.ndarray:
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    xi = x[:, None, :]
    yi = y[None, :, :]
    if metric == "sqeuclidean":
        return ((xi - yi) ** 2).sum(-1)
    if metric in ("euclidean", "l2"):
        return np.sqrt(((xi - yi) ** 2).sum(-1))
    if metric in ("l1", "cityblock"):
        return np.abs(xi - yi).sum(-1)
    if metric in ("chebyshev", "linf"):
        return np.abs(xi - yi).max(-1)
    if metric == "inner_product":
        return x @ y.T
    if metric == "cosine":
        xn = np.linalg.norm(x, axis=1)
        yn = np.linalg.norm(y, axis=1)
        return 1.0 - (x @ y.T) / np.maximum(np.outer(xn, yn), 1e-300)
    if metric == "correlation":
        xc = x - x.mean(1, keepdims=True)
        yc = y - y.mean(1, keepdims=True)
        return 1.0 - (xc @ yc.T) / np.maximum(
            np.outer(np.linalg.norm(xc, axis=1), np.linalg.norm(yc, axis=1)), 1e-300
        )
    if metric == "canberra":
        num = np.abs(xi - yi)
        den = np.abs(xi) + np.abs(yi)
        return np.where(den == 0, 0.0, num / np.where(den == 0, 1, den)).sum(-1)
    if metric == "minkowski":
        return (np.abs(xi - yi) ** p).sum(-1) ** (1.0 / p)
    if metric == "braycurtis":
        num = np.abs(xi - yi).sum(-1)
        den = np.abs(xi + yi).sum(-1)
        return np.where(den == 0, 0.0, num / np.where(den == 0, 1, den))
    if metric == "hamming":
        return (xi != yi).mean(-1)
    if metric == "jensenshannon":
        m = 0.5 * (xi + yi)
        def xlogx(a, b):
            with np.errstate(divide="ignore", invalid="ignore"):
                r = a * (np.log(a) - np.log(b))
            return np.where((a == 0) | (b == 0), 0.0, r)
        return np.sqrt(np.maximum(0.5 * (xlogx(xi, m) + xlogx(yi, m)).sum(-1), 0))
    if metric == "kl_divergence":
        with np.errstate(divide="ignore", invalid="ignore"):
            r = xi * (np.log(xi) - np.log(yi))
        return 0.5 * np.where(xi == 0, 0.0, r).sum(-1)
    if metric == "hellinger":
        dot = np.sqrt(xi * yi).sum(-1)
        return np.sqrt(np.maximum(1.0 - dot, 0.0))
    if metric == "russellrao":
        d = x.shape[1]
        return (d - x @ y.T) / d
    if metric == "jaccard":
        dot = x @ y.T
        union = x.sum(1)[:, None] + y.sum(1)[None, :] - dot
        return 1.0 - dot / np.where(union == 0, 1.0, union)
    if metric == "dice":
        dot = x @ y.T
        den = x.sum(1)[:, None] + y.sum(1)[None, :]
        return 1.0 - 2 * dot / np.where(den == 0, 1.0, den)
    if metric == "haversine":
        lat1, lon1 = xi[..., 0], xi[..., 1]
        lat2, lon2 = yi[..., 0], yi[..., 1]
        a = np.sin(0.5 * (lat1 - lat2)) ** 2 + np.cos(lat1) * np.cos(lat2) * np.sin(0.5 * (lon1 - lon2)) ** 2
        return 2 * np.arcsin(np.sqrt(np.clip(a, 0, 1)))
    raise ValueError(metric)


def naive_knn(x: np.ndarray, y: np.ndarray, k: int, metric: str = "sqeuclidean"):
    """Exact KNN oracle: returns (dist [m,k], idx [m,k])."""
    d = naive_pairwise(x, y, metric)
    if metric == "inner_product":
        idx = np.argsort(-d, axis=1, kind="stable")[:, :k]
    else:
        idx = np.argsort(d, axis=1, kind="stable")[:, :k]
    dist = np.take_along_axis(d, idx, axis=1)
    return dist, idx


def eval_recall(found_idx: np.ndarray, true_idx: np.ndarray) -> float:
    """Set-intersection recall@k (reference ann_utils.cuh:218 eval_recall)."""
    n, k = true_idx.shape
    hits = 0
    for i in range(n):
        hits += len(set(found_idx[i, :k].tolist()) & set(true_idx[i].tolist()))
    return hits / (n * k)


def eval_neighbours(found_idx, true_idx, found_dist, true_dist, eps: float = 1e-3) -> float:
    """Distance-aware recall: a found neighbor also counts if its distance
    ties the true k-th distance (reference ann_utils.cuh:155)."""
    n, k = true_idx.shape
    hits = 0
    for i in range(n):
        true_set = set(true_idx[i].tolist())
        kth = true_dist[i, -1]
        for j in range(k):
            if found_idx[i, j] in true_set or found_dist[i, j] <= kth + eps:
                hits += 1
    return hits / (n * k)


def exact_knn_blocked(queries, base, k: int, metric: str = "sqeuclidean",
                      block: int = 1 << 18):
    """Exact KNN oracle for large ``base``: float64, in row blocks, so a
    1M–40M-row dataset never needs the [m, n, d] tensor of
    :func:`naive_pairwise`. ``base`` is anything that slices to an array
    (numpy, memmap, or a device array, copied block by block).
    ``metric`` is "sqeuclidean" or "inner_product". Returns
    (dist [m,k] float64, idx [m,k] int64), best first. Each block keeps
    its k best by partition, so an exact tie at a block's k-th place may
    keep either id."""
    q = np.asarray(queries, np.float64)
    qn = (q * q).sum(1)[:, None]
    best_d = np.full((q.shape[0], 0), np.inf)
    best_i = np.zeros((q.shape[0], 0), np.int64)
    for a in range(0, base.shape[0], block):
        x = np.asarray(base[a:a + block], np.float64)
        dots = q @ x.T
        if metric == "inner_product":
            d = -dots
        elif metric == "sqeuclidean":
            d = qn + (x * x).sum(1)[None, :] - 2.0 * dots
        else:
            raise ValueError(metric)
        kk = min(k, d.shape[1])
        part = np.sort(np.argpartition(d, kk - 1, axis=1)[:, :kk], axis=1)
        cd = np.concatenate([best_d, np.take_along_axis(d, part, 1)], 1)
        ci = np.concatenate([best_i, part + a], axis=1)
        # ids ascend left to right, so a stable sort keeps the lower id
        # first among equal distances
        o = np.argsort(cd, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(cd, o, axis=1)
        best_i = np.take_along_axis(ci, o, axis=1)
    if metric == "inner_product":
        best_d = -best_d
    return best_d, best_i
