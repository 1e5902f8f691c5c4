"""Fused L2 distance + argmin 1-nearest-neighbor.

TPU-native analog of the reference's ``fused_l2_nn`` / ``fusedL2NNMinReduce``
(cpp/include/raft/distance/fused_l2_nn-inl.cuh:76-181) — the key primitive
under k-means predict and 1-NN queries. Instead of a custom CUDA kernel with
atomics, we scan over tiles of ``y`` keeping a running (min, argmin): each
tile is a GEMM on the MXU plus an elementwise epilogue, and the running
reduction keeps peak memory at m×tile instead of m×n.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.utils.math import round_up_to_multiple
from raft_tpu.utils.precision import argmin_exact, dist_dot


def fused_l2_nn_argmin(
    x,
    y,
    sqrt: bool = False,
    tile_n: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """For each row of x, the L2 distance and index of its nearest row of y.

    Returns ``(min_dist [m], argmin [m])`` — the reference's KVP output
    (fused_l2_nn-inl.cuh:76 with MinAndDistanceReduceOp).

    ``sqrt=True`` applies the square root in the epilogue
    (fused_l2_nn-inl.cuh Sqrt template param).
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    n = y.shape[0]
    if tile_n is None:
        # whole-y fast path for modest n (e.g. kmeans centers)
        tile_n = n if n * x.shape[0] <= (256 * 1024 * 1024) // 4 else 4096
    return _fused_l2_nn(x, y, bool(sqrt), int(min(tile_n, n)))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _fused_l2_nn(x, y, sqrt: bool, tile_n: int):
    compute = jnp.promote_types(x.dtype, jnp.float32)
    x = x.astype(compute)
    y = y.astype(compute)
    m, d = x.shape
    n, _ = y.shape
    xn = jnp.sum(x * x, axis=1)

    if tile_n >= n:
        dot = dist_dot(x, y.T)
        yn = jnp.sum(y * y, axis=1)
        d2 = jnp.maximum(xn[:, None] + yn[None, :] - 2.0 * dot, 0.0)
        idx = argmin_exact(d2, axis=1)
        val = jnp.take_along_axis(d2, idx[:, None], axis=1)[:, 0]
        return (jnp.sqrt(val) if sqrt else val), idx

    npad = round_up_to_multiple(n, tile_n)
    ypad = jnp.pad(y, ((0, npad - n), (0, 0)))
    y_tiles = ypad.reshape(npad // tile_n, tile_n, d)
    n_tiles = npad // tile_n

    def body(carry, inp):
        best_val, best_idx = carry
        t, yt = inp
        dot = dist_dot(x, yt.T)
        yn = jnp.sum(yt * yt, axis=1)
        d2 = jnp.maximum(xn[:, None] + yn[None, :] - 2.0 * dot, 0.0)
        col = jnp.arange(tile_n) + t * tile_n
        d2 = jnp.where(col[None, :] < n, d2, jnp.inf)
        tile_idx = argmin_exact(d2, axis=1)
        tile_val = jnp.take_along_axis(d2, tile_idx[:, None], axis=1)[:, 0]
        take = tile_val < best_val
        best_val = jnp.where(take, tile_val, best_val)
        best_idx = jnp.where(take, (tile_idx + t * tile_n).astype(jnp.int32), best_idx)
        return (best_val, best_idx), None

    init = (jnp.full((m,), jnp.inf, compute), jnp.zeros((m,), jnp.int32))
    (best_val, best_idx), _ = jax.lax.scan(
        body, init, (jnp.arange(n_tiles), y_tiles)
    )
    return (jnp.sqrt(best_val) if sqrt else best_val), best_idx


def fused_l2_nn_min_reduce(x, y, sqrt: bool = False):
    """Reference-named alias (fused_l2_nn-inl.cuh:163 fusedL2NNMinReduce)."""
    return fused_l2_nn_argmin(x, y, sqrt=sqrt)


def masked_l2_nn_argmin(
    x,
    y,
    adj,
    group_idxs=None,
    sqrt: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Masked fused NN (reference distance/masked_nn.cuh).

    ``adj``: bool [m, n_groups] adjacency — row i may match group g only if
    adj[i, g]. ``group_idxs``: [n_groups] *end* offsets partitioning y's rows
    into contiguous groups (reference masked_l2_nn semantics); None = one
    group per y row (adj is [m, n]).
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    adj = jnp.asarray(adj).astype(jnp.bool_)
    n = y.shape[0]
    if group_idxs is None:
        mask = adj
    else:
        group_idxs = jnp.asarray(group_idxs)
        # map each y row to its group: group g covers [prev_end, end)
        row = jnp.arange(n)
        grp = jnp.searchsorted(group_idxs, row, side="right")
        mask = adj[:, grp]  # [m, n]
    compute = jnp.promote_types(x.dtype, jnp.float32)
    xw = x.astype(compute)
    yw = y.astype(compute)
    dot = dist_dot(xw, yw.T)
    xn = jnp.sum(xw * xw, axis=1)
    yn = jnp.sum(yw * yw, axis=1)
    d2 = jnp.maximum(xn[:, None] + yn[None, :] - 2.0 * dot, 0.0)
    d2 = jnp.where(mask, d2, jnp.inf)
    idx = argmin_exact(d2, axis=1)
    val = jnp.take_along_axis(d2, idx[:, None], axis=1)[:, 0]
    if sqrt:
        val = jnp.sqrt(val)
    return val, idx
