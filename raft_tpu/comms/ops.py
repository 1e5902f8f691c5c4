"""Collective operations over a mesh axis.

Each function mirrors one virtual of the reference's ``comms_iface``
(core/comms.hpp:123-230) and must be called inside ``shard_map`` (or pmap)
with the named axis bound. XLA lowers these to ICI/DCN collectives — the
NCCL ring the reference manages by hand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size


def allreduce(x, axis_name: str, op: str = "sum"):
    """comms_iface::allreduce (core/comms.hpp)."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    raise ValueError(f"unsupported reduce op {op!r}")


def reduce(x, axis_name: str, root: int = 0, op: str = "sum"):
    """comms_iface::reduce — result valid on root, zeros elsewhere."""
    full = allreduce(x, axis_name, op)
    rank = lax.axis_index(axis_name)
    return jnp.where(rank == root, full, jnp.zeros_like(full))

def bcast(x, axis_name: str, root: int = 0):
    """comms_iface::bcast — every rank gets root's value."""
    rank = lax.axis_index(axis_name)
    masked = jnp.where(rank == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def allgather(x, axis_name: str, axis: int = 0, tiled: bool = False):
    """comms_iface::allgather(v)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def gather(x, axis_name: str, root: int = 0, axis: int = 0):
    """comms_iface::gather — gathered result on root (others get zeros)."""
    full = lax.all_gather(x, axis_name, axis=axis, tiled=True)
    rank = lax.axis_index(axis_name)
    return jnp.where(rank == root, full, jnp.zeros_like(full))


def reducescatter(x, axis_name: str, scatter_axis: int = 0):
    """comms_iface::reducescatter."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis, tiled=True)


def alltoall(x, axis_name: str, split_axis: int = 0, concat_axis: int = 0):
    """Dense all-to-all (no direct reference virtual; std_comms implements
    p2p equivalents). Used by IVF multi-shard query routing."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def device_sendrecv(x, axis_name: str, shift: int = 1):
    """comms_iface::device_sendrecv — ring permute by ``shift``
    (ppermute rides ICI neighbor links)."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def device_multicast_sendrecv(x, axis_name: str, shifts):
    """comms_iface::device_multicast_sendrecv — sum of several ring shifts."""
    out = jnp.zeros_like(x)
    for s in shifts:
        out = out + device_sendrecv(x, axis_name, s)
    return out


def barrier(axis_name: str):
    """comms_iface::barrier — a collective no-op that forces rendezvous."""
    return lax.psum(jnp.ones((), jnp.int32), axis_name)
