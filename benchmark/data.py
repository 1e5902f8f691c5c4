"""Data at published shapes, made on the device from seeds.

The recipe is raft_tpu's bench-wide manifold generator
(``raft_tpu.bench.run._gen_device_block``), copied here so that no change
to the program can move it: rows lie near a random ``intrinsic_dim``-d
linear manifold in ``dim`` dimensions, offset by 64, scaled like SIFT's
[0, 255] byte range, with isotropic noise, clipped to [0, 255]. DEEP-shaped
data is the same recipe normalised to unit length (DEEP's CNN features are
L2-normalised).

For a cell on several chips the rows are made row-sharded in place: each
chip writes the blocks of its own rows, so that no chip ever holds more
than its shard plus one block's temporaries, and the rows are the same
bits the one-device recipe gives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# the fixed projection key of the recipe: every seed shares the manifold's
# orientation, as one published dataset has one
_PROJ_KEY = 12345


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number, wider than 32 bits included."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _block(key, count: int, dim: int, intr: int, unit: bool):
    proj = jax.random.normal(jax.random.PRNGKey(_PROJ_KEY), (intr, dim),
                             jnp.float32) / jnp.sqrt(jnp.float32(intr))
    kz, kn = jax.random.split(key)
    z = 24.0 * jax.random.normal(kz, (count, intr), jnp.float32)
    blk = 64.0 + z @ proj + 2.0 * jax.random.normal(kn, (count, dim),
                                                    jnp.float32)
    blk = jnp.clip(blk, 0, 255)
    if unit:
        blk = blk / jnp.linalg.norm(blk, axis=1, keepdims=True)
    return blk


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _rows(key, rows: int, dim: int, intr: int, unit: bool, block: int):
    # row blocks written in place: one program, temporaries of one block
    def fill(b, x):
        blk = _block(jax.random.fold_in(key, b), block, dim, intr, unit)
        return jax.lax.dynamic_update_slice_in_dim(x, blk, b * block, 0)

    return jax.lax.fori_loop(0, rows // block, fill,
                             jnp.zeros((rows, dim), jnp.float32))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _rows_sharded(key, rows: int, dim: int, intr: int, unit: bool,
                  block: int, mesh):
    # the blocks of :func:`_rows`, each written on the chip that owns its
    # rows: chip r makes blocks r * per .. (r + 1) * per - 1
    axis = mesh.axis_names[0]
    shard = rows // mesh.size
    per = shard // block

    def local(key):
        first = jax.lax.axis_index(axis) * per

        def fill(j, x):
            blk = _block(jax.random.fold_in(key, first + j), block, dim,
                         intr, unit)
            return jax.lax.dynamic_update_slice_in_dim(x, blk, j * block, 0)

        return jax.lax.fori_loop(0, per, fill,
                                 jnp.zeros((shard, dim), jnp.float32))

    return jax.shard_map(local, mesh=mesh, in_specs=P(),
                         out_specs=P(axis, None), check_vma=False)(key)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _queries(key, count: int, dim: int, intr: int, unit: bool):
    return _block(key, count, dim, intr, unit)


def row_block(rows: int, cap: int = 1 << 20) -> int:
    """The largest divisor of ``rows`` not above ``cap``."""
    return next(c for c in range(min(cap, rows), 0, -1) if rows % c == 0)


def generate(cfg: dict, seed: int, mesh=None):
    """(rows [n, dim] f32, queries [m, dim] f32) on the default device,
    or, given a one-axis ``mesh``, the rows sharded by row across it
    (``NamedSharding(mesh, P(axis, None))``) and the queries replicated
    on each of its chips.

    The rows are the deployment's dataset: one fixed set per
    configuration, made from its ``data_seed``, as a published dataset is
    one set. ``seed`` draws the query pool. So every seed indexes the same
    rows, and the index's shapes (padded list capacity and with it every
    compiled program) do not change with the seed; the queries do."""
    rows, dim = int(cfg["rows"]), int(cfg["dim"])
    intr, unit = int(cfg["intrinsic_dim"]), bool(cfg.get("unit_norm"))
    key, block = seed_key(int(cfg["data_seed"])), row_block(rows)
    if mesh is None:
        x = _rows(key, rows, dim, intr, unit, block)
    else:
        if rows % mesh.size or (rows // mesh.size) % block:
            raise ValueError(
                f"{rows} rows on {mesh.size} chips: the recipe's block of "
                f"{block} rows does not divide a chip's share, so the "
                f"rows cannot be made in place shard by shard")
        x = _rows_sharded(key, rows, dim, intr, unit, block, mesh)
    q = _queries(jax.random.fold_in(seed_key(seed), 1), int(cfg["queries"]),
                 dim, intr, unit)
    if mesh is not None:
        q = jax.device_put(q, NamedSharding(mesh, P()))
    return x, q
