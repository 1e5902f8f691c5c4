"""The plain reference and the comparison that decides ``correct``.

Nothing here imports raft_tpu or reads anything it made. The exact
k-nearest neighbours of each query (squared L2) come from a plain-JAX
shortlist at ``Precision.HIGHEST``, rescored in float64 on the host, with
a guard that no row left off the shortlist could reach the top k (the
recipe of the repository's chip smoke, ``shortlist_oracle``). The control
(:func:`lowp_search`) is the same exact search with its operands rounded to
a lower precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _chunk(rows: int, cap: int) -> int:
    """The largest divisor of ``rows`` up to ``cap``, a multiple of 8
    where one exists (a slice the chip's tiling takes without a copy)."""
    divs = [c for c in range(min(cap, rows), 0, -1) if rows % c == 0]
    return next((c for c in divs if c % 8 == 0), divs[0])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _shortlist(q, x, width: int, chunk: int, operand_dtype=None):
    """Per query, the ``width`` smallest ``||x||^2 - 2 q.x`` over all rows
    (``||q||^2`` is the same for every row), scanning ``chunk`` rows at a
    time with a running top-``width``. Returns (partial distances, ids).
    ``operand_dtype`` rounds both operands first (the control)."""
    n, d = x.shape
    if operand_dtype is not None:
        q = q.astype(operand_dtype).astype(jnp.float32)
    m = q.shape[0]
    kc = min(width, chunk)

    def step(c, carry):
        best_d, best_i = carry
        blk = jax.lax.dynamic_slice_in_dim(x, c * chunk, chunk, 0)
        if operand_dtype is not None:
            blk = blk.astype(operand_dtype).astype(jnp.float32)
        dots = jnp.dot(q, blk.T, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
        part = jnp.sum(blk * blk, axis=1)[None, :] - 2.0 * dots
        v, i = jax.lax.top_k(-part, kc)
        cd = jnp.concatenate([best_d, -v], axis=1)
        ci = jnp.concatenate([best_i, i.astype(jnp.int32) + c * chunk],
                             axis=1)
        v2, j = jax.lax.top_k(-cd, width)
        return -v2, jnp.take_along_axis(ci, j, axis=1)

    init = (jnp.full((m, width), jnp.inf, jnp.float32),
            jnp.full((m, width), -1, jnp.int32))
    return jax.lax.fori_loop(0, n // chunk, step, init)


def exact_knn(q, x, k: int, width: int = 32, chunk: int = 1 << 16,
              query_block: int = 2500):
    """Exact top-``k`` by squared L2 of ``q`` [m, d] over ``x`` [n, d]
    (device arrays). Returns (dist [m, k] float64, ids [m, k] int64), best
    first, ties to the lower id, as ``tests/oracles.exact_knn_blocked``.

    Each query keeps its ``width`` best f32 partial distances; the host
    rescores them in float64. That is the whole set's top ``k`` when the
    k-th float64 distance lies below the best excluded f32 distance by more
    than the f32 error. A query where that is not shown is searched again
    over every row in the direct form ``sum((x - q)^2)``, whose f32 error
    is relative to the distance itself, and rescored in float64. Returns
    also the number of such queries."""
    m, d = q.shape
    n = x.shape[0]
    width = min(width, n)
    chunk = _chunk(n, chunk)
    out_d = np.empty((m, k), np.float64)
    out_i = np.empty((m, k), np.int64)
    xn_max = float(jnp.max(jnp.sum(x * x, axis=1)))
    redone = 0
    for a in range(0, m, query_block):
        qb = q[a:a + query_block]
        part, ids = _shortlist(qb, x, width, chunk)
        part, ids = np.asarray(part), np.asarray(ids)
        q64 = np.asarray(qb, np.float64)
        dk, ik = _rescore(q64, x, ids, k)
        # every excluded row's f32 partial distance is >= the width-th kept
        # one; its squared distance is that plus ||q||^2, up to the f32
        # error of the expanded form (a d-term dot and the norms)
        qn = (q64 * q64).sum(1)
        err = 4 * (np.log2(d) + 4) * np.finfo(np.float32).eps * (
            qn + xn_max + 2 * np.sqrt(qn * xn_max))
        ok = (dk[:, -1] + err < part[:, -1] + qn) | (width == n)
        for r in np.flatnonzero(~ok):
            _, cand = _direct_topk(qb[r], x, min(4 * k, n))
            dk[r], ik[r] = _rescore(q64[r:r + 1], x,
                                    np.asarray(cand)[None, :], k)
            redone += 1
        out_d[a:a + len(qb)], out_i[a:a + len(qb)] = dk, ik
    return out_d, out_i, redone


def _rescore(q64: np.ndarray, x, ids: np.ndarray, k: int):
    rows = np.asarray(x[jnp.asarray(np.maximum(ids, 0))], np.float64)
    diff = rows - q64[:, None, :]
    exact = np.einsum("mwd,mwd->mw", diff, diff)
    order = np.lexsort((ids, exact), axis=1)[:, :k]
    return (np.take_along_axis(exact, order, 1),
            np.take_along_axis(ids, order, 1).astype(np.int64))


@functools.partial(jax.jit, static_argnums=(2,))
def _direct_topk(qr, x, width: int):
    diff = x - qr[None, :]
    return jax.lax.top_k(-jnp.sum(diff * diff, axis=1), width)


def lowp_search(q, x, k: int, dtype, chunk: int = 1 << 16,
                query_block: int = 2500):
    """The control: exact search with both operands rounded to ``dtype``
    (float32 accumulation), in the program's place. Returns device-side
    (distances [m, k] as that arithmetic gives them, ids [m, k])."""
    n = x.shape[0]
    dt = jnp.dtype(dtype)
    out_d, out_i = [], []
    for a in range(0, q.shape[0], query_block):
        qb = q[a:a + query_block]
        part, ids = _shortlist(qb, x, min(k, n), _chunk(n, chunk), dt)
        qr = qb.astype(dt).astype(jnp.float32)
        out_d.append(part + jnp.sum(qr * qr, axis=1, keepdims=True))
        out_i.append(ids)
    return jnp.concatenate(out_d), jnp.concatenate(out_i)


@jax.jit
def _rel_dist_err(q, x, qidx, ids, dist):
    """|returned distance - true squared L2 of the returned id| over the
    scale of the expanded form, ||q||^2 + ||x||^2 (f32, direct form)."""
    qq = q[qidx]                                        # [r, d]
    rows = x[jnp.maximum(ids, 0)]                       # [r, k, d]
    diff = rows - qq[:, None, :]
    true = jnp.sum(diff * diff, axis=2)
    scale = jnp.sum(qq * qq, axis=1)[:, None] + jnp.sum(rows * rows, axis=2)
    err = jnp.abs(dist - true) / jnp.maximum(scale, 1e-30)
    err = jnp.where(ids < 0, jnp.inf, err)
    return jnp.max(err)


def judge(q, x, gt_ids: np.ndarray, qidx: np.ndarray, ids: np.ndarray,
          dist: np.ndarray, block: int = 1 << 17) -> dict:
    """Score every answer row (pool query ``qidx[r]`` answered with
    ``ids[r]``, ``dist[r]``): ``recall`` is recall@k against ``gt_ids``
    over all rows; ``dist_err`` the worst relative error of a returned
    distance against the true distance of the returned id."""
    k = gt_ids.shape[1]
    if len(qidx) == 0:
        return {"recall": 0.0, "dist_err": float("inf")}
    ids = np.asarray(ids)[:, :k]
    truth = gt_ids[qidx]                                # [r, k]
    # each true neighbour counts once, however often it is returned
    hits = (truth[:, :, None] == ids[:, None, :]).any(2)
    dist = np.asarray(dist)[:, :k]
    # one block shape (the tail repeats row 0), so one program
    pad = -len(qidx) % block
    qidx, ids, dist = (np.concatenate([a, np.repeat(a[:1], pad, 0)])
                       for a in (np.asarray(qidx), ids, dist))
    worst = 0.0
    for a in range(0, len(qidx), block):
        sl = slice(a, a + block)
        e = _rel_dist_err(q, x, jnp.asarray(qidx[sl], jnp.int32),
                          jnp.asarray(ids[sl], jnp.int32),
                          jnp.asarray(dist[sl], jnp.float32))
        worst = max(worst, float(e))
    return {"recall": float(hits.sum()) / float(hits.size),
            "dist_err": worst}
