"""Matrix utilities.

Analog of the reference's ``cpp/include/raft/matrix`` toolbox (SURVEY.md
§2.4): gather/scatter/slice/argmax/argmin, columnwise sort, linewise ops,
norms, init, reverse, triangular. On TPU these are thin jit-compatible
wrappers over XLA ops — the value is the stable API surface for consumers.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from raft_tpu.utils.precision import argmax_exact, argmin_exact


def gather(matrix, row_indices) -> jax.Array:
    """Select rows (reference matrix/gather.cuh)."""
    return jnp.take(jnp.asarray(matrix), jnp.asarray(row_indices), axis=0)


def gather_if(matrix, row_indices, mask, fill_value=0):
    m = jnp.asarray(matrix)
    out = gather(m, row_indices)
    return jnp.where(jnp.asarray(mask)[:, None], out, fill_value)


def scatter(matrix, row_indices, rows) -> jax.Array:
    """Write rows at row_indices (reference matrix/scatter.cuh)."""
    return jnp.asarray(matrix).at[jnp.asarray(row_indices)].set(jnp.asarray(rows))


def slice_matrix(matrix, row_start: int, row_end: int, col_start: int = 0, col_end: Optional[int] = None):
    """Static sub-block (reference matrix/slice.cuh)."""
    m = jnp.asarray(matrix)
    col_end = m.shape[1] if col_end is None else col_end
    return m[row_start:row_end, col_start:col_end]


def argmax(matrix) -> jax.Array:
    """Per-row argmax (reference matrix/argmax.cuh)."""
    return argmax_exact(matrix, axis=1)


def argmin(matrix) -> jax.Array:
    return argmin_exact(matrix, axis=1)


def col_wise_sort(matrix, ascending: bool = True):
    """Sort each row's values (reference matrix/col_wise_sort.cuh sorts keys
    per row returning sorted keys + source indices)."""
    m = jnp.asarray(matrix)
    order = jnp.argsort(m if ascending else -m, axis=1)
    return jnp.take_along_axis(m, order, axis=1), order.astype(jnp.int32)


def linewise_op(matrix, vec, along_rows: bool, op) -> jax.Array:
    """Broadcast a vector op along rows or columns
    (reference matrix/linewise_op.cuh / linalg matrix_vector_op)."""
    m = jnp.asarray(matrix)
    v = jnp.asarray(vec)
    return op(m, v[None, :] if along_rows else v[:, None])


def norm(matrix, norm_type: str = "l2", axis: int = 1) -> jax.Array:
    m = jnp.asarray(matrix)
    if norm_type in ("l2", "l2sqrt"):
        out = jnp.sum(m * m, axis=axis)
        return jnp.sqrt(out) if norm_type == "l2sqrt" else out
    if norm_type == "l1":
        return jnp.sum(jnp.abs(m), axis=axis)
    if norm_type == "linf":
        return jnp.max(jnp.abs(m), axis=axis)
    raise ValueError(norm_type)


def init(shape, value, dtype=jnp.float32) -> jax.Array:
    return jnp.full(shape, value, dtype=dtype)


def reverse(matrix, axis: int = 0) -> jax.Array:
    return jnp.flip(jnp.asarray(matrix), axis=axis)


def eye(n: int, dtype=jnp.float32) -> jax.Array:
    return jnp.eye(n, dtype=dtype)


def triangular_upper(matrix) -> jax.Array:
    return jnp.triu(jnp.asarray(matrix))


def triangular_lower(matrix) -> jax.Array:
    return jnp.tril(jnp.asarray(matrix))
