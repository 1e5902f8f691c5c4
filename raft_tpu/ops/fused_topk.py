"""Fused brute-force distance + partial-top-k Pallas kernel family.

The peak-FLOP/s recipe from TPU-KNN (PAPERS.md, arxiv 2206.14286): at
full MXU utilization the [queries x rows] distance matrix is never
materialized to HBM — each (query-tile x row-tile) grid step computes
its distance block in VMEM straight off the MXU and PARTIALLY REDUCES
it in-register down to a small per-tile candidate buffer. Only the
candidate buffers (k or R*128 entries per tile instead of tile_n) ever
leave the chip, so HBM traffic drops from O(m*n) to
O(m * n/tile_n * C), and the MXU stays busy streaming row tiles while
the VPU folds candidates. The final selection over the concatenated
per-tile buffers is one hierarchical ``select_k`` / ``merge_topk`` —
RAFT's two-level select (per-block select then cross-block merge,
matrix/detail/select_k-inl.cuh layer 4) with the block level fused into
the distance kernel.

Two in-kernel reduction variants (the candidate-buffer sizing math is
docs/kernels.md §candidate-buffers):

``exact``
    k-pass min extraction (the warp-queue analog) — emits the tile's
    EXACT top-k of its own distances, so the downstream merge is exact
    end to end: each rank's id carries the oracle's distance at that
    rank (ids may swap only between f32 near-ties, which the tile
    matmul sums in another order; exact ties go to the lower id).
    Extraction cost grows with k: eligible
    for k <= 128.
``fold``
    R-deep per-lane partial reduction (TPU-KNN's approximate-then-exact
    PartialReduce): each of the 128 lanes keeps its R smallest
    candidates as a sorted stack, emitting R*128 survivors per tile with
    no extraction loop at all. A true top-k entry is lost only when > R
    of the tile's top-k share a lane (expected C(k, R+1)/128^R per
    tile); the exact cross-tile merge recovers everything that
    survives. The throughput arm for the k <= R*128 regime.

Both variants run under ``interpret=True`` on CPU — tier-1 parity-tests
every arm against the XLA oracle (tests/test_pallas_parity.py) before a
chip ever answers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# metric_kind values (static kernel variants) — shared convention with
# ops.ivf_scan
L2 = 0        # dist = ||q||^2 + ||x||^2 - 2 q.x
IP = 1        # dist = -q.x (min-space; caller negates back)
COSINE = 2    # dist = 1 - q.x / (||q|| ||x||)

_INVALID = -1

# mirror of analysis/lint.py's _VMEM_BUDGET_BYTES: the per-core VMEM the
# tile geometry must fit (pallas guide: ~16 MB/core), spent at ~50% so
# double-buffered pipelining has somewhere to live
_VMEM_BYTES = 16 * 1024 * 1024


def _extract_exact(dist, col, k: int, outd_ref, outi_ref):
    """k-pass min extraction over [G, T]; emits [G, k] dists + global
    column ids (same sweep as ivf_scan._extract_topk, with the id row
    replaced by the tile's global column iota)."""
    G, T = dist.shape
    for j in range(k):
        m = jnp.min(dist, axis=1)                              # [G]
        eq = dist == m[:, None]
        pos = jnp.min(jnp.where(eq, col, jnp.int32(2**31 - 1)), axis=1)
        outd_ref[:, j] = m
        outi_ref[:, j] = jnp.where(jnp.isinf(m), _INVALID, pos)
        if j + 1 < k:
            dist = jnp.where(col == pos[:, None], jnp.inf, dist)


def fold_lane_stacks(dist, chunk_ids, R: int):
    """The shared R-deep per-lane fold (TPU-KNN's PartialReduce core):
    lane b keeps its R smallest (value, id) pairs as a sorted
    compare-swap cascade over the T//128 lane chunks of ``dist`` [G, T].
    ``chunk_ids(c)`` gives chunk c's ids, broadcastable to [G, 128]: the
    ids are built per chunk because Mosaic refuses a lane slice of a
    [G, T] iota or broadcast (a compiler abort, found compiling for a
    described v5e). Returns (stack_d, stack_i) — R arrays of [G, 128]
    each, sorted per lane, +inf/-1 in unfilled slots. Used by both
    fused kernels (this module's brute-force tiles and
    ops.ivf_scan's fold extraction) so the fold semantics and any
    future retuning stay in ONE place."""
    G, T = dist.shape
    nch = T // 128
    stack_d = [jnp.full((G, 128), jnp.inf, jnp.float32) for _ in range(R)]
    stack_i = [jnp.full((G, 128), _INVALID, jnp.int32) for _ in range(R)]
    for c in range(nch):
        nd = dist[:, c * 128:(c + 1) * 128]
        ni = chunk_ids(c)
        for r in range(R):
            swap = nd < stack_d[r]
            sd, si = stack_d[r], stack_i[r]
            stack_d[r] = jnp.where(swap, nd, sd)
            stack_i[r] = jnp.where(swap, ni, si)
            nd = jnp.where(swap, sd, nd)
            ni = jnp.where(swap, si, ni)
    return stack_d, stack_i


def _extract_fold(dist, col0, R: int, outd_ref, outi_ref):
    """R-deep per-lane fold over [G, T]: the R*128 survivors are
    written out UNEXTRACTED — selection happens in the cross-tile
    merge (TPU-KNN's approximate-then-exact partial reduction).
    ``col0`` is the tile's first global column."""
    G = dist.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (G, 128), 1)
    stack_d, stack_i = fold_lane_stacks(
        dist, lambda c: lane + (col0 + c * 128), R)
    for r in range(R):
        outd_ref[:, r * 128:(r + 1) * 128] = stack_d[r]
        outi_ref[:, r * 128:(r + 1) * 128] = jnp.where(
            jnp.isinf(stack_d[r]), _INVALID, stack_i[r])


def _fused_kernel(q_ref, x_ref, *refs, k: int, metric_kind: int,
                  variant: str, fold_r: int, n: int, tile_n: int,
                  has_norms: bool):
    refs = list(refs)
    xn_ref = refs.pop(0) if has_norms else None
    qa_ref = refs.pop(0) if metric_kind != IP else None
    outd_ref, outi_ref = refs
    j = pl.program_id(1)
    q = q_ref[...]                                      # [TQ, d] mm dtype
    x = x_ref[...]                                      # [TN, d] mm dtype
    # f32 operands ask for full f32 contraction: the exact arm's ids
    # must match an f32 oracle, which single-pass bf16 MXU rounding
    # (~1e-3 relative) would not
    dots = jax.lax.dot_general(
        q, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=(jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                   else None),
        preferred_element_type=jnp.float32,
    )                                                   # [TQ, TN] f32
    G, T = dots.shape
    if metric_kind == L2:
        dist = jnp.maximum(
            qa_ref[0][:, None] + xn_ref[0][None, :] - 2.0 * dots, 0.0)
    elif metric_kind == IP:
        dist = -dots
    else:  # COSINE
        xlen = jnp.sqrt(jnp.maximum(xn_ref[0], 1e-30))
        dist = 1.0 - dots / jnp.maximum(
            qa_ref[0][:, None] * xlen[None, :], 1e-30)
    col = jax.lax.broadcasted_iota(jnp.int32, (G, T), 1) + j * tile_n
    dist = jnp.where(col < n, dist, jnp.inf)            # mask pad rows
    if variant == "fold":
        _extract_fold(dist, j * tile_n, fold_r, outd_ref, outi_ref)
    else:
        _extract_exact(dist, col, k, outd_ref, outi_ref)


def tile_geometry(m: int, n: int, d: int, k: int, variant: str,
                  itemsize: int = 2) -> dict:
    """Expression-derived tile geometry for the fused kernel (the VMEM
    budget math is docs/kernels.md §tile-geometry): block bytes =
    q[TQ, d] + x[TN, d] + f32 dist[TQ, TN] + candidate buffers must fit
    ~half of per-core VMEM. The analytic default; the dispatch table
    overrides it per backend (op key ``fused_topk_tile``).

    The query-tile floor is the operand dtype's SUBLANE multiple (8 for
    4-byte, 16 for 2-byte, 32 for 1-byte operands — the (s, 128) tile
    rule analysis/contracts.py codifies): the old flat floor of 8 put
    the bf16 fast path's q-block off the (16, 128) tile at m <= 8 —
    found by graft-kern's computed alignment audit (GL016, r6)."""
    floor = {1: 32, 2: 16}.get(int(itemsize), 8)
    tile_q = 128 if m >= 128 else max(
        floor, 1 << (max(m - 1, 1)).bit_length())
    cand = candidate_width(k, variant)
    budget = _VMEM_BYTES // 2
    tile_n = 2048
    while tile_n > 256:
        used = (tile_q * d * itemsize + tile_n * d * itemsize
                + 4 * tile_q * tile_n + 8 * tile_q * cand)
        if used <= budget:
            break
        tile_n //= 2
    return {"tile_q": int(tile_q), "tile_n": int(tile_n)}


def candidate_width(k: int, variant: str) -> int:
    """Per-tile candidate-buffer width C: ``exact`` emits exactly k,
    ``fold`` emits R*128 with R from :func:`fold_depth` (ceil(k/64),
    floor 2 — sized to the per-lane occupancy tail; rationale there and
    docs/kernels.md §candidate-buffers)."""
    if variant == "fold":
        return 128 * fold_depth(k)
    return int(k)


def fold_depth(k: int) -> int:
    """Lane-stack depth R: at k candidates over 128 lanes the per-lane
    occupancy is Binomial(k, 1/128) — R must clear its tail, not just
    its mean, or lanes overflow and drop true top-k entries (measured:
    R = ceil(k/128) lost ~8% at k=200). R = ceil(k/64) keeps the
    expected overflow under ~1% of k through k=256; floor 2."""
    return max(2, -(-int(k) // 64))


def fused_topk(
    queries,          # [m, d] mm dtype (bf16 for the TPU fast path)
    dataset,          # [n, d] mm dtype
    k: int,
    *,
    metric_kind: int,
    norms=None,       # [n] f32 ||x||^2 (L2/cosine); None for IP
    qaux=None,        # [m] f32 ||q||^2 (L2) or ||q|| (cosine); None for IP
    variant: str = "exact",
    tile_q: int = None,
    tile_n: int = None,
    interpret: bool = False,
):
    """Fused-tile exact KNN in min-space: returns
    (dist [m, k] f32, idx [m, k] int32) best-first. For IP the distances
    are negated scores — negate back after. Rows short of k valid
    candidates come back (+inf, -1).

    ``variant``: "exact" (exact top-k up to f32 near-ties, k <= 128) | "fold"
    (R-deep lane fold, k <= 256; bounded per-tile loss recovered by the
    exact cross-tile merge). Tile geometry defaults to the
    expression-derived :func:`tile_geometry`; callers resolving through
    the dispatch table pass explicit tiles.
    """
    from raft_tpu import obs

    m, d = queries.shape
    n = dataset.shape[0]
    if variant not in ("exact", "fold"):
        raise ValueError(f"variant must be 'exact'|'fold', got {variant!r}")
    if variant == "exact" and k > 128:
        raise ValueError(
            f"variant='exact' caps at k=128 (k-pass extraction), got {k}")
    if variant == "fold" and k > 256:
        raise ValueError(
            f"variant='fold' caps at k=256 (the R=ceil(k/64) lane-stack "
            f"sizing's validated loss band, docs/kernels.md), got {k}")
    geo = tile_geometry(m, n, d, k, variant,
                        jnp.dtype(queries.dtype).itemsize)
    tq = int(tile_q or geo["tile_q"])
    tn = int(tile_n or geo["tile_n"])
    if variant == "fold" and tn % 128:
        # fold_lane_stacks folds T//128 lane chunks: a non-lane-multiple
        # row tile would silently DROP the tail columns from the
        # reduction (the tail-masking class the kernel contracts exist
        # for) — tile_geometry and the dispatch candidates only produce
        # lane multiples, so only an explicit tile_n can get here
        raise ValueError(
            f"variant='fold' needs tile_n % 128 == 0 (the per-lane "
            f"fold covers tile_n//128 chunks; a remainder is silently "
            f"dropped), got tile_n={tn}")
    # trace-time span: attributes compile cost per (variant, tiles);
    # steady-state cached dispatch is silent
    with obs.span("fused_topk", variant=variant, m=m, n=n, k=int(k),
                  tile_q=tq, tile_n=tn):
        cand_d, cand_i = _fused_topk_tiles(
            queries, dataset, norms, qaux, k=int(k),
            metric_kind=int(metric_kind), variant=variant, tile_q=tq,
            tile_n=tn, interpret=bool(interpret),
        )
        # exact hierarchical merge over the concatenated per-tile
        # buffers (layer-4 select; the per-tile select was in-kernel)
        from raft_tpu.neighbors.common import merge_topk

        out_d, out_i = merge_topk(cand_d[:m], cand_i[:m], int(k),
                                  select_min=True)
    return out_d, out_i


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric_kind", "variant", "tile_q", "tile_n",
                     "interpret"),
)
def _fused_topk_tiles(queries, dataset, norms=None, qaux=None, *, k: int,
                      metric_kind: int, variant: str, tile_q: int,
                      tile_n: int, interpret: bool):
    m, d = queries.shape
    n = dataset.shape[0]
    mq = -(-m // tile_q)
    nt = -(-n // tile_n)
    C = candidate_width(k, variant)
    has_norms = metric_kind != IP

    qpad = mq * tile_q - m
    npad = nt * tile_n - n
    q = jnp.pad(queries, ((0, qpad), (0, 0))) if qpad else queries
    x = jnp.pad(dataset, ((0, npad), (0, 0))) if npad else dataset
    inputs = [q, x]
    in_specs = [
        pl.BlockSpec((tile_q, d), lambda i, j: (i, 0)),
        pl.BlockSpec((tile_n, d), lambda i, j: (j, 0)),
    ]
    if has_norms:
        xn = jnp.sum(x.astype(jnp.float32) ** 2, axis=1) if norms is None \
            else (jnp.pad(norms, (0, npad)) if npad else norms)
        inputs.append(xn.reshape(1, nt * tile_n))
        in_specs.append(pl.BlockSpec((1, tile_n), lambda i, j: (0, j)))
        if qaux is None:
            q32 = q.astype(jnp.float32)
            qa = (jnp.sum(q32 * q32, axis=1) if metric_kind == L2
                  else jnp.linalg.norm(q32, axis=1))
        else:
            qa = jnp.pad(qaux, (0, qpad)) if qpad else qaux
        inputs.append(qa.reshape(1, mq * tile_q))
        in_specs.append(pl.BlockSpec((1, tile_q), lambda i, j: (0, i)))

    kernel = functools.partial(
        _fused_kernel, k=k, metric_kind=metric_kind, variant=variant,
        fold_r=fold_depth(k), n=n, tile_n=tile_n, has_norms=has_norms,
    )
    # candidates land as [nt, M, C] with block (None, tile_q, C): the
    # block's last dim equals the array's, so the narrow exact-arm width
    # C = k needs no lane padding (Mosaic's (8, 128)-or-full block rule)
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=(mq, nt),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, tile_q, C), lambda i, j: (j, i, 0)),
            pl.BlockSpec((None, tile_q, C), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nt, mq * tile_q, C), jnp.float32),
            jax.ShapeDtypeStruct((nt, mq * tile_q, C), jnp.int32),
        ],
        interpret=interpret,
    )(*inputs)
    # [nt, M, C] -> [M, nt*C], tile-major per row (the merge's layout)
    flat = lambda a: jnp.transpose(a, (1, 0, 2)).reshape(mq * tile_q, nt * C)
    return flat(out_d), flat(out_i)


# ---------------------------------------------------------------------------
# kernel contract (graft-kern: static geometry bindings + the dynamic
# adversarial interpret-mode sweep share these declarations —
# docs/static_analysis.md §engine-4)
# ---------------------------------------------------------------------------

from raft_tpu.analysis.contracts import kernel_contract  # noqa: E402


def _contract_case_ok(case: dict) -> bool:
    k, n = case.get("k", 1), case.get("n", 1)
    if not 0 < k <= n:
        return False
    if case.get("variant") == "exact" and k > 128:
        return False
    if case.get("variant") == "fold" and k > 256:
        return False
    return True


def _contract_case_derive(case: dict) -> dict:
    # tile_q is ALWAYS the analytic choice (dispatch winners carry only
    # the row tile) — bind the real coupling so the static engine does
    # not audit (m, tile_q) pairs the resolver can never produce
    itemsize = 2 if case.get("dtype") == "bfloat16" else 4
    case.setdefault(
        "tile_q",
        tile_geometry(case["m"], case["n"], case["d"], case.get("k", 1),
                      case.get("variant", "exact"), itemsize)["tile_q"])
    return case


kernel_contract(
    "fused_topk",
    module=__name__,
    entry="fused_topk",
    driver="raft_tpu.analysis.contract_drivers:drive_fused_topk",
    tail_rows="masked",           # pad rows masked to +inf in-kernel
    k_range=(1, 256),
    dtypes=("float32", "bfloat16"),
    exactness="bitwise",          # exact arm; fold judged in its band
    recall_floor=0.95,
    base={"m": 16, "n": 403, "d": 32, "metric_kind": L2},
    rows_key="n", batch_key="m",
    arms=({"variant": "exact", "k_max": 128},
          {"variant": "fold", "k_max": 256}),
    arrays={"queries": ("m", "d"), "dataset": ("n", "d"),
            "norms": ("n",), "qaux": ("m",)},
    case_filter=_contract_case_ok,
    derive=_contract_case_derive,
    extra_cases=(
        {"variant": "exact", "k": 10, "m": 16, "n": 403, "d": 32,
         "metric_kind": IP, "dtype": "float32"},
        {"variant": "exact", "k": 10, "m": 16, "n": 403, "d": 32,
         "metric_kind": COSINE, "dtype": "float32"},
        # multi-tile query grid (m >= 128: tile_q=128, mq > 1)
        {"variant": "exact", "k": 10, "m": 256, "n": 403, "d": 32,
         "metric_kind": L2, "dtype": "float32"},
        # the bf16 fast path's smallest batch: the dtype-aware tile_q
        # floor (16 for 2-byte operands) pinned by the GL016 audit
        {"variant": "fold", "k": 10, "m": 4, "n": 403, "d": 32,
         "metric_kind": L2, "dtype": "bfloat16"},
    ),
    notes="fold loses a true top-k entry only when > R share a lane "
          "(R = ceil(k/64), docs/kernels.md §candidate-buffers); the "
          "exact cross-tile merge recovers everything that survives.",
)
