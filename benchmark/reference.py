"""The plain reference and the comparison that decides ``correct``.

Nothing here imports raft_tpu or reads anything it made. The exact
k-nearest neighbours of each query (squared L2) come from a plain-JAX
shortlist at ``Precision.HIGHEST``, rescored in float64 on the host, with
a guard that no row left off the shortlist could reach the top k (the
recipe of the repository's chip smoke, ``shortlist_oracle``). The control
(:func:`lowp_search`) is the same exact search with its operands rounded to
a lower precision.

Rows sharded by row across a one-axis mesh (a cell on several chips) are
never gathered whole onto one chip: each chip scans its own rows under
``jax.shard_map`` with global ids, the host merges the chips' candidates,
and each row fetched by id is read on the chip that holds it. Rows on one
device take the one-device programs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
# rows a chip relays at a time where it gathers from its own rows
_GATHER_CHUNK = 1 << 18
# answer rows judged at a time on a chip that holds a shard of the rows:
# the gathered rows stay near 1 GB beside the shard
_SHARDED_JUDGE_BLOCK = 1 << 15


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


def _row_mesh(x):
    """The one-axis mesh across which ``x``'s rows are sharded, or None
    where ``x`` lies on one device."""
    sharding = x.sharding
    if len(sharding.device_set) == 1:
        return None
    if not (isinstance(sharding, NamedSharding)
            and len(sharding.mesh.axis_names) == 1
            and sharding.spec[0] == sharding.mesh.axis_names[0]):
        raise ValueError(f"rows must lie on one device or be sharded by "
                         f"row across a one-axis mesh, not {sharding}")
    return sharding.mesh


def _replicated(a, mesh):
    return jax.device_put(a, NamedSharding(mesh, P()))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _shortlist_sharded(q, x, width: int, chunk: int, operand_dtype, mesh):
    """:func:`_shortlist` on each chip over its own rows, with global ids:
    (partial distances, ids), each [m, chips * width]."""
    axis = mesh.axis_names[0]

    def local(qq, xs):
        part, ids = _shortlist(qq, xs, width, chunk, operand_dtype)
        return part, ids + jax.lax.axis_index(axis) * xs.shape[0]

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axis, None)),
                         out_specs=(P(None, axis), P(None, axis)),
                         check_vma=False)(q, x)


def _best(part: np.ndarray, ids: np.ndarray, width: int):
    """Per row, the ``width`` smallest of ``part``, ties to the lower id."""
    order = np.lexsort((ids, part), axis=1)[:, :width]
    return (np.take_along_axis(part, order, 1),
            np.take_along_axis(ids, order, 1))


def _shortlist_on(q, x, width: int, chunk: int, mesh, operand_dtype=None):
    """:func:`_shortlist` over the rows of every chip of ``mesh``, the
    chips' candidates merged on the host: (partial distances, ids) as
    host arrays [m, width]. No chip's ``width``-th lies below the merged
    ``width``-th, so a row left out is at or above it."""
    rows = x.shape[0] // mesh.size
    part, ids = _shortlist_sharded(q, x, min(width, rows), _chunk(rows, chunk),
                                   operand_dtype, mesh)
    return _best(np.asarray(part), np.asarray(ids), width)


@jax.jit
def _max_sq_norm(x):
    return jnp.max(jnp.sum(x * x, axis=1))


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
    also the number of such queries.

    Over rows sharded across chips, each chip keeps its own ``width``
    best and the host keeps the best ``width`` of theirs
    (:func:`_shortlist_on`); the guard stands as it is."""
    m, d = q.shape
    n = x.shape[0]
    width = min(width, n)
    mesh = _row_mesh(x)
    out_d = np.empty((m, k), np.float64)
    out_i = np.empty((m, k), np.int64)
    if mesh is None:
        chunk = _chunk(n, chunk)
        xn_max = float(jnp.max(jnp.sum(x * x, axis=1)))
    else:
        q = _replicated(q, mesh)
        xn_max = float(_max_sq_norm(x))
    redone = 0
    for a in range(0, m, query_block):
        qb = q[a:a + query_block]
        if mesh is None:
            part, ids = _shortlist(qb, x, width, chunk)
            part, ids = np.asarray(part), np.asarray(ids)
        else:
            part, ids = _shortlist_on(qb, x, width, chunk, mesh)
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
            cand = _direct_candidates(qb[r], x, min(4 * k, n), mesh)
            dk[r], ik[r] = _rescore(q64[r:r + 1], x, cand[None, :], k)
            redone += 1
        out_d[a:a + len(qb)], out_i[a:a + len(qb)] = dk, ik
    return out_d, out_i, redone


@functools.partial(jax.jit, static_argnums=(2,))
def _gather_rows(xs, ids, chunk: int):
    """``xs[ids]`` for ids in range, read ``chunk`` rows at a time. A
    gather straight from the chip's rows would first copy all of them to
    the layout it reads (narrow rows lie column-major on the TPU); this
    relays one chunk at a time."""

    def step(c, acc):
        blk = jax.lax.dynamic_slice_in_dim(xs, c * chunk, chunk, 0)
        loc = ids - c * chunk
        inb = (loc >= 0) & (loc < chunk)
        return jnp.where(inb[..., None], blk[jnp.clip(loc, 0, chunk - 1)],
                         acc)

    return jax.lax.fori_loop(0, xs.shape[0] // chunk, step,
                             jnp.zeros(ids.shape + xs.shape[1:], xs.dtype))


def _owned(ids, xs, axis: str, n: int):
    """Per global id, clamped into [0, n) as a one-device gather clamps
    it: its row on this chip (0 where another chip holds it), and
    whether this chip holds it."""
    own = (jnp.minimum(jnp.maximum(ids, 0), n - 1)
           - jax.lax.axis_index(axis) * xs.shape[0])
    mine = (own >= 0) & (own < xs.shape[0])
    return jnp.where(mine, own, 0), mine


@functools.partial(jax.jit, static_argnums=(2,))
def _take_rows_sharded(x, ids, mesh):
    """``x[ids]``, each row read on the chip that holds it; the result on
    every chip."""
    axis, n = mesh.axis_names[0], x.shape[0]
    chunk = _chunk(n // mesh.size, _GATHER_CHUNK)

    def local(xs, ids):
        own, mine = _owned(ids, xs, axis, n)
        rows = _gather_rows(xs, own, chunk)
        return jax.lax.psum(jnp.where(mine[..., None], rows, 0.0), axis)

    return jax.shard_map(local, mesh=mesh, in_specs=(P(axis, None), P()),
                         out_specs=P(), check_vma=False)(x, ids)


def _take_rows(x, ids: np.ndarray) -> np.ndarray:
    """``x[ids]`` in float64 on the host, an id past either end read as
    the end row (as a device gather clamps it)."""
    ids = jnp.asarray(np.maximum(ids, 0))
    mesh = _row_mesh(x)
    rows = x[ids] if mesh is None else _take_rows_sharded(x, ids, mesh)
    return np.asarray(rows, np.float64)


def _rescore(q64: np.ndarray, x, ids: np.ndarray, k: int):
    rows = _take_rows(x, ids)
    diff = rows - q64[:, None, :]
    exact = np.einsum("mwd,mwd->mw", diff, diff)
    order = np.lexsort((ids, exact), axis=1)[:, :k]
    return (np.take_along_axis(exact, order, 1),
            np.take_along_axis(ids, order, 1).astype(np.int64))


@functools.partial(jax.jit, static_argnums=(2,))
def _direct_topk(qr, x, width: int):
    diff = x - qr[None, :]
    return jax.lax.top_k(-jnp.sum(diff * diff, axis=1), width)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _direct_topk_sharded(qr, x, width: int, mesh):
    """:func:`_direct_topk` on each chip over its own rows, with global
    ids: (-distances, ids), each [chips * width]."""
    axis = mesh.axis_names[0]

    def local(qq, xs):
        v, i = _direct_topk(qq, xs, width)
        return v, i + jax.lax.axis_index(axis) * xs.shape[0]

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axis, None)),
                         out_specs=(P(axis), P(axis)), check_vma=False)(qr, x)


def _direct_candidates(qr, x, width: int, mesh) -> np.ndarray:
    """The ids of the ``width`` rows nearest ``qr`` in the direct form,
    over every chip's rows where ``x`` is sharded."""
    if mesh is None:
        return np.asarray(_direct_topk(qr, x, width)[1])
    rows = x.shape[0] // mesh.size
    v, i = _direct_topk_sharded(qr, x, min(width, rows), mesh)
    return _best(-np.asarray(v)[None], np.asarray(i)[None], width)[1][0]


def lowp_search(q, x, k: int, dtype, chunk: int = 1 << 16,
                query_block: int = 2500):
    """The control: exact search with both operands rounded to ``dtype``
    (float32 accumulation), in the program's place. Returns device-side
    (distances [m, k] as that arithmetic gives them, ids [m, k]). Over
    rows sharded across chips each chip searches its own rows and the
    host keeps the best ``k`` of theirs."""
    n = x.shape[0]
    dt = jnp.dtype(dtype)
    mesh = _row_mesh(x)
    if mesh is not None:
        q = _replicated(q, mesh)
    out_d, out_i = [], []
    for a in range(0, q.shape[0], query_block):
        qb = q[a:a + query_block]
        if mesh is None:
            part, ids = _shortlist(qb, x, min(k, n), _chunk(n, chunk), dt)
        else:
            part, ids = _shortlist_on(qb, x, min(k, n), chunk, mesh, dt)
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
    err = jnp.where(ids < 0, jnp.inf, _rel_errs(qq, rows, dist))
    return jnp.max(err)


def _rel_errs(qq, rows, dist):
    diff = rows - qq[:, None, :]
    true = jnp.sum(diff * diff, axis=2)
    scale = jnp.sum(qq * qq, axis=1)[:, None] + jnp.sum(rows * rows, axis=2)
    return jnp.abs(dist - true) / jnp.maximum(scale, 1e-30)


@functools.partial(jax.jit, static_argnums=(5,))
def _rel_dist_err_sharded(q, x, qidx, ids, dist, mesh):
    """:func:`_rel_dist_err` with each returned id read on the chip that
    holds its row, the chips' worst taken by ``pmax``."""
    axis, n = mesh.axis_names[0], x.shape[0]
    chunk = _chunk(n // mesh.size, _GATHER_CHUNK)

    def local(q, xs, qidx, ids, dist):
        own, mine = _owned(ids, xs, axis, n)
        err = _rel_errs(q[qidx], _gather_rows(xs, own, chunk), dist)
        err = jnp.where(mine, err, 0.0)
        # a NaN is past any limit; the chips' pmax may pass over one
        err = jnp.where((ids < 0) | jnp.isnan(err), jnp.inf, err)
        return jax.lax.pmax(jnp.max(err), axis)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(), P(axis, None), P(), P(), P()),
                         out_specs=P(), check_vma=False)(q, x, qidx, ids,
                                                         dist)


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
    mesh = _row_mesh(x)
    if mesh is not None:
        q = _replicated(q, mesh)
        block = min(block, _SHARDED_JUDGE_BLOCK)
    # one block shape (the tail repeats row 0), so one program
    pad = -len(qidx) % block
    qidx, ids, dist = (np.concatenate([a, np.repeat(a[:1], pad, 0)])
                       for a in (np.asarray(qidx), ids, dist))
    worst = 0.0
    for a in range(0, len(qidx), block):
        sl = slice(a, a + block)
        args = (q, x, jnp.asarray(qidx[sl], jnp.int32),
                jnp.asarray(ids[sl], jnp.int32),
                jnp.asarray(dist[sl], jnp.float32))
        e = (_rel_dist_err(*args) if mesh is None
             else _rel_dist_err_sharded(*args, mesh))
        # a NaN distance is past any limit (max() would pass over it)
        worst = max(worst, float(e) if e == e else float("inf"))
    return {"recall": float(hits.sum()) / float(hits.size),
            "dist_err": worst}
