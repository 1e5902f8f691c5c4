"""Matmul precision policy for distance math.

On TPU, the MXU's default fp32 matmul uses bf16 passes (~1e-2 relative
error) — unacceptable for distance computations that feed k-selection.
Distance GEMMs therefore default to ``Precision.HIGHEST`` (full fp32 via
multi-pass). The intended fast path is to feed bf16 *inputs* (the TPU-KNN
recipe): HIGHEST on bf16 operands is a single MXU pass with fp32
accumulation, which is both fast and accurate enough for recall targets.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_precision = jax.lax.Precision.HIGHEST


def set_dist_precision(p) -> None:
    global _precision
    _precision = p


def get_dist_precision():
    return _precision


def dist_dot(a, b):
    """a @ b with fp32 accumulation at the distance-math precision policy."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=_precision)


def argmin_exact(x, axis: int = -1):
    """First position of the minimum of ``x`` along ``axis``, exact.
    ``jnp.argmin`` of f32 compiles for the TPU to a reduce that carries
    the compared values in bf16: on a v5e it picked the wrong PQ code for
    ~87% of subvectors. Floats map to int32 keys in the same order (flip
    the magnitude bits of negatives; -0.0 joins +0.0 first), and an
    int32 argmin is exact. Every arg-min/max of floats in the library
    goes through here or :func:`argmax_exact`."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.argmin(x, axis=axis).astype(jnp.int32)
    x = x.astype(jnp.float32)
    i = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    key = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))
    return jnp.argmin(key, axis=axis).astype(jnp.int32)


def argmax_exact(x, axis: int = -1):
    """First position of the maximum along ``axis`` (see argmin_exact)."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.argmax(x, axis=axis).astype(jnp.int32)
    return argmin_exact(-x, axis)
