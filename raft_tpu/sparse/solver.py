"""Sparse solvers: minimum spanning tree + connected components + Lanczos
(reference sparse/solver/{mst,mst_solver}.cuh and
sparse/neighbors/cross_component_nn.cuh).

MST is Borůvka's algorithm, which is the natural TPU formulation: every
round each component picks its lightest outgoing edge with two
segment-min passes (weight, then edge-id among ties), merges via
pointer-jumping — all fixed-shape, all vectorized across components, at
most ⌈log₂ n⌉ rounds. The reference's GPU MST (detail/mst_solver_inl.cuh)
is Borůvka too, built on per-supervertex atomic min-reduction; the
segment-min is the collective analog of that atomic.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.linalg.lanczos import lanczos_eigsh  # re-export (sparse/solver/lanczos.cuh)
from raft_tpu.sparse.types import COO, CSR, csr_to_coo
from raft_tpu.utils.precision import argmin_exact

__all__ = ["mst", "connected_components", "lanczos_eigsh", "connect_components"]


def _pointer_jump(parent):
    """Collapse a parent forest to its roots (log-step path doubling)."""
    def cond_fn(state):
        p, changed = state
        return changed

    def while_body(state):
        p, _ = state
        p2 = p[p]
        return p2, jnp.any(p2 != p)

    p, _ = jax.lax.while_loop(cond_fn, while_body, (parent, jnp.bool_(True)))
    return p


@functools.partial(jax.jit, static_argnums=(3,))
def _boruvka(rows, cols, w, n: int):
    """Fixed-shape Borůvka. Edges must contain both directions of every
    undirected edge. Returns (mst_edge_mask [E] bool, colors [n] i32)."""
    E = rows.shape[0]
    inf = jnp.float32(jnp.inf)
    colors0 = jnp.arange(n, dtype=jnp.int32)
    mask0 = jnp.zeros((E,), bool)

    def cond_fn(state):
        _, _, again, it = state
        return again & (it < n)

    def body(state):
        colors, mask, _, it = state
        cr = colors[rows]
        cc = colors[cols]
        cross = cr != cc
        w_eff = jnp.where(cross, w, inf)
        # pass 1: lightest outgoing weight per component
        minw = jnp.full((n,), inf).at[cr].min(w_eff)
        # passes 2-3: tie-break among the lightest by the *symmetric* key
        # (w, min(u,v), max(u,v)) — both directions of an undirected edge
        # share it, so merge cycles longer than 2 cannot form (the
        # reference's alteration step, detail/mst_solver_inl.cuh
        # min_edge_per_supervertex, alters weights for the same reason)
        is_w = cross & (w_eff <= minw[cr])
        lo = jnp.minimum(rows, cols)
        hi = jnp.maximum(rows, cols)
        minlo = jnp.full((n,), n, jnp.int32).at[cr].min(
            jnp.where(is_w, lo, n)
        )
        is_wl = is_w & (lo == minlo[cr])
        minhi = jnp.full((n,), n, jnp.int32).at[cr].min(
            jnp.where(is_wl, hi, n)
        )
        is_whl = is_wl & (hi == minhi[cr])
        eid = jnp.where(is_whl, jnp.arange(E, dtype=jnp.int32), E)
        pick = jnp.full((n,), E, jnp.int32).at[cr].min(eid)  # [n] edge ids
        valid = pick < E
        # mark picked edges in the MST (pad slot E absorbs invalid picks)
        mask = (
            jnp.zeros((E + 1,), bool)
            .at[jnp.where(valid, pick, E)]
            .set(True)[:E]
            | mask
        )
        # build the merge forest: component c -> color of its pick's far end
        parent = jnp.where(valid, colors[cols[jnp.clip(pick, 0, E - 1)]],
                           jnp.arange(n, dtype=jnp.int32))
        # break 2-cycles (a<->b both picked each other): keep the smaller id
        two_cycle = parent[parent] == jnp.arange(n, dtype=jnp.int32)
        parent = jnp.where(
            two_cycle & (parent > jnp.arange(n, dtype=jnp.int32)),
            jnp.arange(n, dtype=jnp.int32),
            parent,
        )
        roots = _pointer_jump(parent)
        new_colors = roots[colors]
        return new_colors, mask, jnp.any(valid), it + 1

    colors, mask, _, _ = jax.lax.while_loop(
        cond_fn, body, (colors0, mask0, jnp.bool_(True), jnp.int32(0))
    )
    return mask, colors


def mst(
    coo: COO, symmetrize_input: bool = True
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, jax.Array]:
    """Minimum spanning forest of a weighted undirected graph
    (reference sparse/solver/mst.cuh mst: colors + MST edge list out).

    Parameters: ``coo`` — edge list; if ``symmetrize_input``, the mirror
    of every edge is appended (Borůvka needs both directions).

    Returns ``(src, dst, weight, colors)``: host-compressed MST edge
    arrays (n-1 edges per connected component tree) and the final
    per-vertex component color (connected components for free).
    """
    n = coo.shape[0]
    rows, cols, vals = coo.rows, coo.cols, coo.vals.astype(jnp.float32)
    if symmetrize_input:
        rows, cols, vals = (
            jnp.concatenate([rows, cols]),
            jnp.concatenate([cols, rows]),
            jnp.concatenate([vals, vals]),
        )
    mask, colors = _boruvka(rows, cols, vals, n)
    keep = np.asarray(mask)
    src = np.asarray(rows)[keep]
    dst = np.asarray(cols)[keep]
    w = np.asarray(vals)[keep]
    # canonicalize + dedupe edges picked from both directions
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    _, uniq = np.unique(lo.astype(np.int64) * n + hi, return_index=True)
    return src[uniq], dst[uniq], w[uniq], colors


def connected_components(coo: COO) -> Tuple[int, jax.Array]:
    """Weakly connected components via label propagation + pointer jumping
    (the reference reaches this through MST colors / cuGraph).

    Returns (n_components, labels [n] with labels in [0, n_components)).
    """
    n = coo.shape[0]
    # run Borůvka on unit weights: final colors are the components
    _, colors = _boruvka(
        jnp.concatenate([coo.rows, coo.cols]),
        jnp.concatenate([coo.cols, coo.rows]),
        jnp.ones((2 * coo.rows.shape[0],), jnp.float32),
        n,
    )
    c = np.asarray(colors)
    uniq, labels = np.unique(c, return_inverse=True)
    return int(uniq.size), jnp.asarray(labels.astype(np.int32))


def connect_components(
    x, colors, metric="sqeuclidean"
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Minimum cross-component connecting edges
    (reference sparse/neighbors/cross_component_nn.cuh: for each vertex
    find its nearest neighbor in a *different* component, then keep each
    component's lightest such edge — the FixConnectivitiesRedOp pattern
    that repairs a disconnected KNN graph before single-linkage).

    Returns host arrays (src, dst, weight) of candidate bridging edges
    (at most one per component).
    """
    from raft_tpu.distance.pairwise import pairwise_distance

    x = jnp.asarray(x)
    n = x.shape[0]
    colors = jnp.asarray(colors)
    # tiled cross-component 1-NN: mask same-component pairs to +inf
    block = max(1, min(n, (64 << 20) // max(4 * n, 1)))
    best_d = []
    best_j = []
    for r0 in range(0, n, block):
        r1 = min(r0 + block, n)
        d = pairwise_distance(x[r0:r1], x, metric)
        same = colors[r0:r1, None] == colors[None, :]
        d = jnp.where(same, jnp.inf, d)
        best_d.append(jnp.min(d, axis=1))
        best_j.append(argmin_exact(d, axis=1))
    bd = jnp.concatenate(best_d)
    bj = jnp.concatenate(best_j)
    # lightest outgoing edge per component (segment-min, like Borůvka pass)
    cr = colors
    minw = jnp.full((n,), jnp.inf).at[cr].min(bd)
    is_min = bd <= minw[cr]
    vid = jnp.where(is_min, jnp.arange(n), n)
    pick = jnp.full((n,), n, jnp.int32).at[cr].min(vid.astype(jnp.int32))
    valid = np.asarray(pick < n) & np.isfinite(np.asarray(minw))
    pick_h = np.asarray(pick)[valid]
    return (
        pick_h,
        np.asarray(bj)[pick_h],
        np.asarray(bd)[pick_h],
    )
