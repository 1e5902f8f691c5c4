"""Fused nn-descent local-join Pallas kernel: score + unique-merge top-K.

TPU-native analog of the reference's GNND local join
(cpp/include/raft/neighbors/detail/nn_descent.cuh:342-358,700): the
reference scores each node's sampled 2-hop candidates in CTA shared
memory and pushes winners into neighbor lists with atomics. The pull
formulation here (see neighbors/nn_descent.py) makes the join row-wise —
each node scores its own candidate set and merges it into its current
list — which XLA serves with three HBM round trips per iteration: the
``[B, C]`` distance matrix, the ``[B, K+C]`` concat/sort buffers of the
unique-merge, and the top-K extraction transients. This kernel is the
TPU-KNN treatment (PAPERS.md, arxiv 2206.14286) applied to that join:

* **scoring** — per node-tile, the gathered candidate slab
  ``[tile_b*C, d]`` and the query rows sit in VMEM; each node's
  candidate dots are one MXU ``[1, d] x [d, C]`` contraction (the
  per-slab partials), with the L2 epilogue (norms, clamp) fused on the
  VPU. The ``[B, C]`` distance matrix lives only in registers/VMEM.
* **unique-merge top-K in-register** — the current list rides in as a
  ``[tile_b, K]`` block and the merged output is produced by a K-pass
  min extraction that masks BY ID after each pass, so the output is
  deduplicated by construction (the sort-based dedup + top-K of the XLA
  path collapses into the extraction itself). Duplicate ids keep their
  smallest distance with distance ties resolved to the smallest id —
  which coincides with the XLA fallback
  (``nn_descent._merge_topk_unique``: keep-first in id-stable order,
  lowest-id tie-break) because duplicate copies carry bitwise-equal
  distances in this pipeline (the same deterministic scoring produces
  them), so the two paths agree bitwise on ids over tie-free keys.

Only the ``[B, K]`` merged lists ever leave the chip; HBM traffic per
node drops from ``O(C·d + (K+C)·sort)`` transient round trips to the
candidate-vector gather XLA performs anyway (row gathers are XLA's
strength — the same split ops/beam_step.py uses for its packed rows).

The candidate gather itself stays OUTSIDE the kernel on purpose: it is
the op's byte floor (``C·d·4`` bytes per node against ``~2·C·d`` FLOPs,
arithmetic intensity ~0.5 FLOP/byte — deeply bandwidth-bound), so the
kernel's job is to add zero traffic on top of it, not to feed the MXU at
peak. ``tile_b`` therefore stays small (the f32 sublane floor up to 32)
and is table-dispatched under the ``graph_join`` op key
(docs/dispatch_tuning.md) like ``fused_topk_tile``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INVALID = -1
_NO_ID = 2147483647          # min-id tie-break sentinel (int32 max)

# mirror of analysis/lint.py's _VMEM_BUDGET_BYTES (pallas guide:
# ~16 MB/core), spent at ~50% so double-buffering has somewhere to live
_VMEM_BYTES = 16 * 1024 * 1024


def _a128(v: int) -> int:
    return -(-int(v) // 128) * 128


def join_vmem_bytes(tile_b: int, C: int, K: int, d: int,
                    ip: bool = False) -> int:
    """Per-grid-step VMEM bytes of the join kernel's blocks plus its
    live intermediates (the pooled [tile_b, Kp+Cp] extraction buffers) —
    the budget rule ``tile_geometry`` and the dispatch candidates apply
    (docs/kernels.md §graph)."""
    Cp = _a128(C)
    Kp = _a128(K)
    blocks = (
        tile_b * d * 4                    # q rows
        + tile_b * Cp * d * 4             # candidate vector slab
        + tile_b * Cp * 4                 # candidate ids
        + 2 * tile_b * Kp * 4             # current list (d + i)
        + 2 * tile_b * Kp * 4             # output list (d + i)
    )
    if not ip:
        blocks += tile_b * 4 + tile_b * Cp * 4    # q norms + cand norms
    live = 2 * tile_b * (Kp + Cp) * 4             # pooled extraction pair
    return blocks + live


def tile_geometry(C: int, K: int, d: int, ip: bool = False) -> dict:
    """Expression-derived node-tile size: the largest of the canonical
    tiles (``tuning.GRAPH_JOIN_TILES`` — the ONE home; a tile added
    there is raced, dispatched, audited, and reachable here) whose
    blocks + extraction pool fit ~half of per-core VMEM; floor = the
    smallest canonical tile (8, the f32 sublane multiple). The analytic
    default — the dispatch table overrides it per backend (op key
    ``graph_join``, winner strings ``pallas:<tile_b>``)."""
    from raft_tpu.tuning import GRAPH_JOIN_TILES

    budget = _VMEM_BYTES // 2
    tiles = sorted(GRAPH_JOIN_TILES)
    tile_b = tiles[0]
    for t in reversed(tiles):
        if join_vmem_bytes(t, C, K, d, ip) <= budget:
            tile_b = t
            break
    return {"tile_b": int(tile_b)}


def _join_kernel(*refs, K: int, Kp: int, Cp: int, tile_b: int, ip: bool,
                 n_rows: int):
    refs = list(refs)
    q_ref = refs.pop(0)          # [TB, d] f32
    cid_ref = refs.pop(0)        # [TB, Cp] i32
    cv_ref = refs.pop(0)         # [TB*Cp, d] f32 candidate slab
    curd_ref = refs.pop(0)       # [TB, Kp] f32
    curi_ref = refs.pop(0)       # [TB, Kp] i32
    if not ip:
        qn_ref = refs.pop(0)     # [TB, 1] f32
        cn_ref = refs.pop(0)     # [TB, Cp] f32
    outd_ref, outi_ref = refs

    # ---- per-node scoring: one [1, d] x [d, Cp] MXU contraction per
    # node row, statically unrolled over the tile (dynamic sublane
    # offsets into the slab are unsupported in Mosaic; tile_b is small
    # by design — the op is gather-bound, see module docstring)
    rows = []
    for b in range(tile_b):
        cb = cv_ref[b * Cp:(b + 1) * Cp, :]            # [Cp, d]
        qb = q_ref[b:b + 1, :]                         # [1, d]
        # full f32 contraction: the TPU's default f32 dot is one bf16
        # pass (~1e-3 relative), and the L2 form qn + cn - 2 q.c cancels
        # that error into the near-neighbor distances the merge ranks
        dots = jax.lax.dot_general(
            qb, cb,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                              # [1, Cp]
        if ip:
            rows.append(-dots)
        else:
            rows.append(jnp.maximum(
                qn_ref[b:b + 1, :] + cn_ref[b:b + 1, :] - 2.0 * dots, 0.0))
    dist = jnp.concatenate(rows, axis=0)               # [TB, Cp]

    ids = cid_ref[...]
    # tail mask: rows past the live count (the padded node tile) are
    # inert regardless of what the pad gather produced — belt to the
    # wrapper's (-1, +inf) sentinel suspenders
    row = (pl.program_id(0) * tile_b
           + jax.lax.broadcasted_iota(jnp.int32, (tile_b, Cp), 0))
    dist = jnp.where((ids < 0) | (row >= n_rows), jnp.inf, dist)

    # ---- unique-merge top-K: pool the current list with the fresh
    # candidates and run a K-pass min extraction that masks BY ID after
    # each pass — uniqueness by construction, duplicate ids keep their
    # smallest distance (ties resolved to the smallest id, matching the
    # XLA fallback's (id, distance)-sorted dedup + top_k)
    pool_d = jnp.concatenate([curd_ref[...], dist], axis=1)
    pool_i = jnp.concatenate([curi_ref[...], ids], axis=1)
    pool_d = jnp.where(pool_i < 0, jnp.inf, pool_d)

    outd_ref[...] = jnp.full((tile_b, Kp), jnp.inf, jnp.float32)
    outi_ref[...] = jnp.full((tile_b, Kp), _INVALID, jnp.int32)
    for j in range(K):
        m = jnp.min(pool_d, axis=1)                    # [TB]
        eq = pool_d == m[:, None]
        win = jnp.min(jnp.where(eq, pool_i, _NO_ID), axis=1)
        win = jnp.where(jnp.isinf(m), _INVALID, win)
        outd_ref[:, j] = m
        outi_ref[:, j] = win
        if j + 1 < K:
            pool_d = jnp.where(pool_i == win[:, None], jnp.inf, pool_d)


def graph_local_join(
    q,                # [B, d] f32 node vectors
    cand_ids,         # [B, C] i32 candidate ids (-1 = invalid slot)
    cand_vecs,        # [B, C, d] f32 gathered candidate vectors
    cur_d,            # [B, K] f32 current list distances (min-space)
    cur_i,            # [B, K] i32 current list ids (unique per row)
    qn=None,          # [B] f32 ||q||^2 (L2); None for IP
    cand_norms=None,  # [B, C] f32 ||cand||^2 (L2); None for IP
    *,
    ip: bool = False,
    tile_b: int = None,
    interpret: bool = False,
):
    """One fused local-join step: merge the scored candidates into each
    row's unique top-K (K = the current list width). Returns
    (new_d [B, K], new_i [B, K]), best-first, unique ids per row, the
    library-wide (+inf, -1) convention in unfilled slots. Distances are
    min-space (L2: ``||q||^2 + ||c||^2 - 2 q.c`` clamped at 0; IP:
    negated scores).

    Bitwise contract vs the XLA fallback
    (``nn_descent._merge_topk_unique`` over the same scores): duplicate
    ids collapse to one copy (bitwise-equal distances in this pipeline,
    so keep-min here == keep-first there), distance ties resolve to the
    smallest id. K caps at 128 (the K-pass extraction budget — the
    dispatch fallback serves larger K).
    """
    B, C = cand_ids.shape
    K = cur_d.shape[1]
    if K > 128:
        raise ValueError(
            f"graph_local_join caps at K=128 (K-pass extraction), got {K}")
    geo = tile_geometry(C, K, q.shape[1], ip)
    tb = int(tile_b or geo["tile_b"])
    return _graph_join_tiles(
        q, cand_ids, cand_vecs, cur_d, cur_i, qn, cand_norms,
        ip=bool(ip), tile_b=tb, interpret=bool(interpret),
    )


@functools.partial(
    jax.jit, static_argnames=("ip", "tile_b", "interpret"),
)
def _graph_join_tiles(q, cand_ids, cand_vecs, cur_d, cur_i, qn=None,
                      cand_norms=None, *, ip: bool, tile_b: int,
                      interpret: bool):
    B, C = cand_ids.shape
    d = q.shape[1]
    K = cur_d.shape[1]
    nt = -(-B // tile_b)
    Bp = nt * tile_b
    Cp = _a128(C)
    Kp = _a128(K)

    rpad = Bp - B
    cpad = Cp - C
    kpad = Kp - K
    qp = jnp.pad(q, ((0, rpad), (0, 0))) if rpad else q
    cid = jnp.pad(cand_ids, ((0, rpad), (0, cpad)), constant_values=-1) \
        if rpad or cpad else cand_ids
    cv = jnp.pad(cand_vecs, ((0, rpad), (0, cpad), (0, 0))) \
        if rpad or cpad else cand_vecs
    curd = jnp.pad(cur_d, ((0, rpad), (0, kpad)),
                   constant_values=jnp.inf) if rpad or kpad else cur_d
    curi = jnp.pad(cur_i, ((0, rpad), (0, kpad)), constant_values=-1) \
        if rpad or kpad else cur_i

    row = lambda i: (i, 0)
    inputs = [qp, cid, cv.reshape(Bp * Cp, d), curd, curi]
    in_specs = [
        pl.BlockSpec((tile_b, d), row),
        pl.BlockSpec((tile_b, Cp), row),
        pl.BlockSpec((tile_b * Cp, d), row),
        pl.BlockSpec((tile_b, Kp), row),
        pl.BlockSpec((tile_b, Kp), row),
    ]
    if not ip:
        qnp = jnp.pad(qn, (0, rpad)) if rpad else qn
        cn = jnp.pad(cand_norms, ((0, rpad), (0, cpad))) \
            if rpad or cpad else cand_norms
        inputs += [qnp.reshape(Bp, 1), cn]
        in_specs += [
            pl.BlockSpec((tile_b, 1), row),
            pl.BlockSpec((tile_b, Cp), row),
        ]
    kernel = functools.partial(
        _join_kernel, K=K, Kp=Kp, Cp=Cp, tile_b=tile_b, ip=ip, n_rows=B,
    )
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((tile_b, Kp), row),
            pl.BlockSpec((tile_b, Kp), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Kp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, Kp), jnp.int32),
        ],
        interpret=interpret,
    )(*inputs)
    return out_d[:B, :K], out_i[:B, :K]


# ---------------------------------------------------------------------------
# kernel contract (graft-kern; docs/static_analysis.md §engine-4)
# ---------------------------------------------------------------------------

from raft_tpu.analysis.contracts import kernel_contract  # noqa: E402
from raft_tpu.tuning import GRAPH_JOIN_TILES  # noqa: E402


def _join_case_ok(case: dict) -> bool:
    return 0 < case.get("K", 1) <= 128 and case.get("C", 1) >= 1


def _join_case_derive(case: dict) -> dict:
    case.setdefault("ip", False)
    case.setdefault(
        "tile_b",
        tile_geometry(case["C"], case["K"], case["d"],
                      case["ip"])["tile_b"])
    if case["ip"]:
        case["qn"] = case["cand_norms"] = False
    else:
        case["qn"] = case["cand_norms"] = True
    return case


kernel_contract(
    "graph_join",
    module=__name__,
    entry="graph_local_join",
    driver="raft_tpu.analysis.contract_drivers:drive_graph_join",
    tail_rows="masked",          # B/C/K pads carry (-1, +inf) sentinels
    k_range=(1, 128),
    k_key="K",
    dtypes=("float32",),
    exactness="bitwise",
    base={"B": 24, "C": 37, "d": 32, "K": 8},
    rows_key="C", batch_key="B",
    arrays={"q": ("B", "d"), "cand_ids": ("B", "C"),
            "cand_vecs": ("B", "C", "d"),
            "cur_d": ("B", "K"), "cur_i": ("B", "K"),
            "qn": ("B",), "cand_norms": ("B", "C")},
    case_filter=_join_case_ok,
    derive=_join_case_derive,
    extra_cases=tuple(
        [
            # IP metric: no norm operands, negated-dot scores
            {"K": 8, "B": 24, "C": 37, "d": 32, "ip": True,
             "dtype": "float32"},
            # fewer candidates than K: rows must tail out as (+inf, -1)
            {"K": 32, "B": 9, "C": 5, "d": 16, "dtype": "float32"},
            # non-word-multiple dim (d binds block dim == array dim)
            {"K": 8, "B": 24, "C": 37, "d": 30, "dtype": "float32"},
        ]
        + [
            # every dispatchable node tile (the graph_join winner
            # strings carry tile_b — audit each injectable value)
            {"K": 64, "B": 70, "C": 150, "d": 64, "tile_b": t,
             "dtype": "float32"}
            for t in GRAPH_JOIN_TILES
        ]
    ),
    notes="duplicate ids keep their smallest distance (== the XLA "
          "fallback's keep-first: copies tie bitwise under the shared "
          "deterministic scoring), distance ties resolve to the "
          "smallest id on both paths, so ids agree bitwise over "
          "tie-free keys; the candidate-vector gather stays in XLA "
          "(the op's byte floor), the kernel adds zero HBM transients.",
)
