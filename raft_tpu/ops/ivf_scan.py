"""Fused IVF list-scan + top-k Pallas kernel.

TPU-native analog of the reference's fused interleaved-scan kernel
(cpp/include/raft/neighbors/detail/ivf_flat_interleaved_scan-inl.cuh:663):
one grid step scans one bucketized (query-group x list) pair — the list
block is DMA'd from HBM by a scalar-prefetch index map (no gather
materialization), distances come off the MXU into VMEM, and the per-list
top-k is extracted on-chip, so the [group x cap] distance tile never
touches HBM. The reference's warp-queue (select_warpsort.cuh:100) becomes
a k-pass vectorized min-extraction; its approx mode mirrors
lax.approx_min_k's lane-binning (one candidate per 128-lane bin, then
extract from bins — collision loss ~C(k,2)/128 per list).

The kernel resolves stored ids in-kernel: the list's id row is DMA'd
alongside the block and the extraction emits global ids directly (the
argmin's position-select runs on the id row instead of a column iota).
Returning positions instead and mapping them outside costs a
[nb, G, k]-element take_along_axis — per-element gathers that measured
~10x the whole kernel's runtime at SIFT-1M scale.

Inputs are produced by ``ivf_flat.bucketize_pairs``: ``bucket_list`` maps
grid step -> list id, ``qv`` holds the pre-gathered query group per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# metric_kind values (static kernel variants)
L2 = 0        # dist = ||q||^2 + ||x||^2 - 2 q.x   (needs norms + qaux=||q||^2)
IP = 1        # dist = -q.x  (caller negates back; select-min internally)
COSINE = 2    # dist = 1 - q.x / (||q|| ||x||)     (needs norms=||x||^2, qaux=||q||)

# id emitted for invalid (inf-distance) slots; matches the library-wide
# "-1 = no neighbor" contract
_INVALID = -1

# the per-list recall budget binned eligibility is judged against when
# the caller does not say (ivf_flat/ivf_pq SearchParams default)
DEFAULT_RECALL_TARGET = 0.95


def binned_loss_fits(k: int,
                     recall_target: float = DEFAULT_RECALL_TARGET) -> bool:
    """THE single home for the single-slot binning loss model: one
    candidate per lane-bin loses a true top-k entry whenever a better
    one shares its bin — expected lost FRACTION ~ (k-1)/256
    (C(k,2)/128 colliding pairs over k entries). Consumed by the entry
    point's eligibility, the kernel contract's sweep filter, and the
    microbench candidate set, so the three can never drift apart
    (review fix, r6). ``recall_target <= 0`` always fits (forcing)."""
    rt = float(recall_target)
    return rt <= 0.0 or (k - 1) / 256.0 <= max(0.0, 1.0 - rt)


def binned_k_cap(recall_target: float = DEFAULT_RECALL_TARGET) -> int:
    """Largest k the loss model admits at ``recall_target`` (<= the
    structural 64-candidate extraction cap)."""
    k = 64
    while k > 1 and not binned_loss_fits(k, recall_target):
        k -= 1
    return k


def _extract_topk(dist, ids_row, k: int, outd_ref, outi_ref):
    """k-pass min extraction over [G, cap]; emits [G, k] dists + ids."""
    G, cap = dist.shape
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    col = jax.lax.broadcasted_iota(jnp.int32, (G, cap), 1)
    # one output column per pass — accumulating all k vectors and stacking
    # at the end measured 145 MB of register spill slots at k=130
    for j in range(k):
        m = jnp.min(dist, axis=1)                              # [G]
        eq = dist == m[:, None]
        pos = jnp.min(jnp.where(eq, col, cap), axis=1)         # [G]
        sel = jnp.where(col == pos[:, None], ids_row[None, :], big)
        idv = jnp.min(sel, axis=1)
        outd_ref[0, :, j] = m
        outi_ref[0, :, j] = jnp.where(jnp.isinf(m), _INVALID, idv)
        if j + 1 < k:
            dist = jnp.where(col == pos[:, None], jnp.inf, dist)


def _extract_topk_binned(dist, ids_row, k: int, cap: int, outd_ref, outi_ref):
    """Lane-binned approximate extraction: fold [G, cap] into 128 bins
    (bin b holds min over columns == b mod 128), then extract k from the
    bins. One top-k candidate is lost per same-bin collision among the
    true top-k (expected C(k,2)/128 per list)."""
    G = dist.shape[0]
    nch = cap // 128
    lane = jax.lax.broadcasted_iota(jnp.int32, (G, 128), 1)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    binmin = jnp.full((G, 128), jnp.inf, jnp.float32)
    binid = jnp.full((G, 128), _INVALID, jnp.int32)
    binpos = jnp.zeros((G, 128), jnp.int32)
    for c in range(nch):
        chunk = dist[:, c * 128:(c + 1) * 128]
        ids_c = ids_row[c * 128:(c + 1) * 128]
        better = chunk < binmin
        binmin = jnp.where(better, chunk, binmin)
        binid = jnp.where(better, ids_c[None, :], binid)
        binpos = jnp.where(better, lane + c * 128, binpos)
    for j in range(k):
        m = jnp.min(binmin, axis=1)
        eq = binmin == m[:, None]
        pos = jnp.min(jnp.where(eq, binpos, cap), axis=1)
        # eq guard: untouched bins share binpos=0, so a bare binpos==pos
        # match would sweep them in (emitting their -1 id) whenever the
        # winner sits at column 0
        hit = eq & (binpos == pos[:, None])
        idv = jnp.min(jnp.where(hit, binid, big), axis=1)
        outd_ref[0, :, j] = m
        outi_ref[0, :, j] = jnp.where(jnp.isinf(m), _INVALID, idv)
        if j + 1 < k:
            binmin = jnp.where(hit, jnp.inf, binmin)


def _extract_topk_binned_deep(dist, ids_row, k: int, cap: int,
                              outd_ref, outi_ref, R: int = 4):
    """R-deep lane binning for 64 < k <= 256 (the warpsort-analog large-k
    path, select_warpsort.cuh:100): each of the 128 lanes keeps its R
    smallest candidates as a sorted per-lane stack (a compare-swap
    cascade per chunk), giving R*128 survivors; k are then extracted.
    A true top-k entry is lost only when > R of the top-k share a lane:
    expected C(k, R+1)/128^R items (k=130, R=4: ~1% of the list's
    contribution, recovered by the cross-probe merge)."""
    G = dist.shape[0]
    nch = cap // 128
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    lane = jax.lax.broadcasted_iota(jnp.int32, (G, 128), 1)
    stack_d = [jnp.full((G, 128), jnp.inf, jnp.float32) for _ in range(R)]
    stack_i = [jnp.full((G, 128), _INVALID, jnp.int32) for _ in range(R)]
    for c in range(nch):
        nd = dist[:, c * 128:(c + 1) * 128]
        ids_c = ids_row[c * 128:(c + 1) * 128]      # basic slice, then
        ni = jnp.broadcast_to(ids_c[None, :], (G, 128))  # expand (no gather)
        for r in range(R):
            swap = nd < stack_d[r]
            sd, si = stack_d[r], stack_i[r]
            stack_d[r] = jnp.where(swap, nd, sd)
            stack_i[r] = jnp.where(swap, ni, si)
            nd = jnp.where(swap, sd, nd)
            ni = jnp.where(swap, si, ni)
    for j in range(k):
        m4 = stack_d[0]
        for r in range(1, R):
            m4 = jnp.minimum(m4, stack_d[r])
        m = jnp.min(m4, axis=1)                            # [G]
        pos = jnp.min(jnp.where(m4 == m[:, None], lane, 128), axis=1)
        taken = jnp.zeros((G, 128), jnp.bool_)
        idv = jnp.full((G,), big, jnp.int32)
        for r in range(R):
            hit = ((stack_d[r] == m[:, None]) & (lane == pos[:, None])
                   & (~taken))
            idv = jnp.minimum(
                idv, jnp.min(jnp.where(hit, stack_i[r], big), axis=1)
            )
            stack_d[r] = jnp.where(hit, jnp.inf, stack_d[r])
            taken = taken | hit
        outd_ref[0, :, j] = m
        outi_ref[0, :, j] = jnp.where(jnp.isinf(m), _INVALID, idv)


def _extract_fold(dist, ids_row, cap: int, outd_ref, outi_ref, R: int):
    """Fused-reduction variant (TPU-KNN's PartialReduce): R-deep
    per-lane stacks like ``_extract_topk_binned_deep``'s fold phase, but
    the R*128 survivors are emitted UNEXTRACTED — no k-pass loop at all;
    the final selection happens in the caller's exact cross-probe merge
    (the hierarchical select_k rung's home turf). The fold core and the
    R sizing live in ops.fused_topk (one home for both kernels). Loss
    profile matches binned_deep's fold: a true top-k entry is lost only
    when > R of the list's top-k share a lane."""
    from raft_tpu.ops.fused_topk import fold_lane_stacks

    stack_d, stack_i = fold_lane_stacks(
        dist, lambda c: ids_row[c * 128:(c + 1) * 128][None, :], R)
    for r in range(R):
        outd_ref[0, :, r * 128:(r + 1) * 128] = stack_d[r]
        outi_ref[0, :, r * 128:(r + 1) * 128] = jnp.where(
            jnp.isinf(stack_d[r]), _INVALID, stack_i[r])


def _fold_depth(k: int) -> int:
    """Lane-stack depth R for the fold arm — delegates to the single
    sizing rule in ops.fused_topk.fold_depth (R = ceil(k/64), floor 2;
    rationale there)."""
    from raft_tpu.ops.fused_topk import fold_depth

    return fold_depth(k)


def _scan_kernel(
    bl_ref, ls_ref, *refs,
    k: int, metric_kind: int, extract: str, has_norms: bool,
    has_filter: bool, packed_i4: bool = False, packed_pq4: bool = False,
    packed_bits: bool = False, has_row_scale: bool = False,
):
    refs = list(refs)
    storage_ref = refs.pop(0)
    ids_ref = refs.pop(0)
    norms_ref = refs.pop(0) if has_norms else None
    keep_ref = refs.pop(0) if has_filter else None
    rs_ref = refs.pop(0) if has_row_scale else None
    qv_ref = refs.pop(0)
    w_ref = refs.pop(0) if packed_pq4 else None
    qaux_ref = refs.pop(0) if metric_kind != IP else None
    if packed_i4 or packed_pq4 or packed_bits:
        outd_ref, outi_ref, recon_ref = refs
    else:
        outd_ref, outi_ref = refs

    i = pl.program_id(0)
    size = ls_ref[bl_ref[i]]
    qv = qv_ref[0]                                      # [G, d] mm dtype
    if packed_pq4:
        # packed 4-bit PQ CODES [nw, cap] u32 (8 codes/word, transposed
        # like the i4 cache) scored as a 16-pass one-hot MXU contraction —
        # the TPU answer to the reference's in-kernel shm-LUT code scoring
        # (ivf_pq_compute_similarity-inl.cuh:164-185): TPUs have no
        # per-lane LUT gather, but "which codes equal v" is a VPU compare
        # and "sum LUT[s, v] over matching (s, x)" is a matmul. Pass v:
        #   lut_v[G, s] = qv[G, rot] @ W[v][rot, s]   (block-diag codebook)
        #   dots      += lut_v @ (codes == v)         ([G,p] x [p,cap])
        # Exact PQ distances (no quantization beyond the codes), at 2x
        # fewer HBM bytes than the i8 cache and 16x its MXU work — the
        # high-compression regime trade (see tuning.md ladder).
        blk_w = storage_ref[0].astype(jnp.int32)        # [nw, cap]
        nw = blk_w.shape[0]
        p = w_ref.shape[2]
        for wi in range(nw):
            word = blk_w[wi, :]                          # [cap] i32
            for j in range(8):
                recon_ref[wi * 8 + j, :] = (word >> (4 * j)) & 0xF
        codes_blk = recon_ref[0:p, :]                    # [p, cap] i32
        G = qv.shape[0]
        cap = codes_blk.shape[1]
        dots = jnp.zeros((G, cap), jnp.float32)
        for v in range(16):
            lut_v = jax.lax.dot_general(
                qv, w_ref[v],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # [G, p]
            mask_v = (codes_blk == v).astype(qv.dtype)   # [p, cap]
            dots = dots + jax.lax.dot_general(
                lut_v.astype(qv.dtype), mask_v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    elif packed_bits:
        # RaBitQ sign-bit block [nw, cap] uint32 (32 sign bits per lane
        # word, transposed like the i4 cache: components on sublanes,
        # rows on lanes). The asymmetric estimator's hot loop is
        # XOR+popcount-shaped — <x̄, q> over ±1 codes — phrased for the
        # MXU: a 2-op VPU decode ((w >> j) & 1 -> 2b-1 ∈ {-1, +1}) into
        # the [d, cap] scratch, then ONE matmul S = qv @ signs. The
        # per-row correction scalar fac = ||r||²/||r||₁ (row_scale) is
        # applied AFTER the matmul (per stored row — it cannot fold into
        # the query side), giving the unbiased dot estimate fac·S; the
        # norm term reads the TRUE ||r||² from ``norms``
        # (docs/kernels.md §rabitq). Pad dims (d -> nw*32) decode to -1
        # but the caller zero-pads qv there, so they contribute nothing.
        blk_w = storage_ref[0].astype(jnp.int32)        # [nw, cap]
        nw = blk_w.shape[0]
        for wi in range(nw):
            word = blk_w[wi, :]                          # [cap] i32
            for j in range(32):
                bit = (word >> j) & 1
                recon_ref[wi * 32 + j, :] = (2 * bit - 1).astype(qv.dtype)
        dots = jax.lax.dot_general(
            qv, recon_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                               # [G, cap]
    elif packed_i4:
        # packed int4 block [nw, cap] uint32 (transposed: components on
        # sublanes, rows on lanes — the Mosaic-dense layout for narrow
        # per-row payloads). Unpack 8 signed nibbles per word with the
        # 2-op sign-extending decode ((w << s) >> 28) and write component
        # rows into the [d, cap] VMEM scratch; one MXU matmul then scores
        # the whole block. Per-component dequant scales are folded into
        # ``qv`` by the caller, so decoded values stay the raw [-8, 7]
        # integers (exact in bf16).
        blk_w = storage_ref[0].astype(jnp.int32)        # [nw, cap]
        nw = blk_w.shape[0]
        for wi in range(nw):
            word = blk_w[wi, :]                          # [cap] i32
            for j in range(8):
                vals = (word << (28 - 4 * j)) >> 28      # [-8, 7]
                recon_ref[wi * 8 + j, :] = vals.astype(qv.dtype)
        dots = jax.lax.dot_general(
            qv, recon_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                               # [G, cap]
    else:
        blk = storage_ref[0].astype(qv.dtype)           # [cap, d]
        dots = jax.lax.dot_general(
            qv, blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                               # [G, cap]
    G, cap = dots.shape
    if has_row_scale:
        # per-row estimator correction (rabitq): dots -> fac * dots
        dots = dots * rs_ref[0, 0][None, :]
    if metric_kind == L2:
        dist = jnp.maximum(
            qaux_ref[0, 0][:, None] + norms_ref[0, 0][None, :] - 2.0 * dots,
            0.0,
        )
    elif metric_kind == IP:
        dist = -dots
    else:  # COSINE
        plen = jnp.sqrt(jnp.maximum(norms_ref[0, 0], 1e-30))
        dist = 1.0 - dots / jnp.maximum(
            qaux_ref[0, 0][:, None] * plen[None, :], 1e-30
        )
    col = jax.lax.broadcasted_iota(jnp.int32, (G, cap), 1)
    valid = col < size
    if has_filter:
        valid = valid & (keep_ref[0, 0][None, :] > 0)
    dist = jnp.where(valid, dist, jnp.inf)
    ids_row = ids_ref[0, 0]                             # [cap] int32
    if extract == "binned":
        _extract_topk_binned(dist, ids_row, k, cap, outd_ref, outi_ref)
    elif extract == "binned_deep":
        _extract_topk_binned_deep(dist, ids_row, k, cap, outd_ref, outi_ref)
    elif extract == "fold":
        _extract_fold(dist, ids_row, cap, outd_ref, outi_ref,
                      _fold_depth(k))
    else:
        _extract_topk(dist, ids_row, k, outd_ref, outi_ref)


def fused_list_scan_topk(
    storage,        # [C, cap, d] source dtype | [C, d//8, cap] u32 (packed_i4)
    indices,        # [C, cap] int32 stored global ids
    list_sizes,     # [C] int32
    bucket_list,    # [nb] int32
    qv,             # [nb, G, d] bf16 (pre-gathered query groups)
    qaux=None,      # [nb, G] f32: ||q||^2 (L2) or ||q|| (cosine); None for IP
    norms=None,     # [C, cap] f32: ||x||^2; None for IP
    keep=None,      # [C, cap] int32 filter keep-mask; None = no filter
    lut_weights=None,  # [16, rot, p] block-diag codebook (pq4 code scan)
    row_scale=None,    # [C, cap] f32 per-row dot scale (rabitq fac)
    *,
    k: int,
    metric_kind: int,
    approx: bool = True,
    recall_target: float = 0.95,
    interpret: bool = False,
    packed_i4: bool = False,
    packed_bits: bool = False,
    extract: str = None,
):
    """Scan each bucket's list block against its query group and return the
    per-pair top-k in min-space.

    Returns (out_d [nb, G, kc] f32, out_i [nb, G, kc] int32) where out_i
    holds the stored *global ids* (resolved in-kernel). ``kc == k`` for
    the extracting arms; the ``fold`` arm (fused partial reduction —
    per-lane R-deep stacks emitted unextracted, TPU-KNN's PartialReduce)
    returns the WIDER ``kc = R*128`` candidate buffer and defers
    selection to the caller's exact cross-probe merge — callers must
    read the candidate width off the returned shape. For IP the
    distances are negated scores — negate back after the merge. Invalid
    tail entries (list shorter than k after filtering) come back as
    (+inf, -1) — mask on either.

    ``packed_i4``: storage holds signed int4 components packed 8-per-u32,
    TRANSPOSED to [C, d//8, cap] so blocks are Mosaic-dense (components on
    sublanes, rows on lanes) — the in-kernel-decode PQ scan (reference
    ivf_pq_compute_similarity-inl.cuh scores compressed codes in-registers;
    here the compressed form is the int4 reconstruction and the decode is
    a shift/mask VPU prologue feeding one MXU matmul). Per-component
    dequant scales must be pre-folded into ``qv`` (and ``norms`` hold the
    dequantized-vector norms), so the kernel itself is scale-free.

    ``packed_bits`` (the rabitq arm): storage holds 1-bit sign codes of
    the rotated residuals packed 32-per-u32, TRANSPOSED to
    [C, ceil(d/32), cap]; ``row_scale`` must carry the per-row RaBitQ
    correction fac = ||r||²/||r||₁ (applied to the dots after the MXU
    pass — the unbiased estimator <q, r> ≈ fac·Σ±q_j) and ``norms`` the
    TRUE residual norms ||r||². Queries must be zero-padded to the
    word-padded width ceil(d/32)*32 so pad bits score nothing. ~32×
    compressed vs f32 — the cheap first stage of the multi-stage rerank
    pipeline (ivf_pq.search_refined).

    ``lut_weights`` (mutually exclusive with ``packed_i4``): storage holds
    packed 4-bit PQ CODES [C, p//8, cap] u32 and scoring runs the 16-pass
    one-hot contraction against the block-diagonal codebook weights
    W[v][s*pq_len + l, s] = pq_centers[s, v, l]; ``qv`` is the raw rotated
    query (residual) group [nb, G, rot] and ``norms`` the exact
    reconstruction norms. Distances equal the decode-then-matmul path's
    exactly (same codes, same codebook).
    """
    # Extraction variant: the exact k-pass min sweep vs the lane-binned
    # approximations (k <= 64 single-slot, k <= 256 R-deep) vs the fold
    # arm (k <= 256, no in-kernel extraction at all — the R*128-wide
    # candidate buffer goes to the caller's merge). Eligibility
    # is structural (approx opt-in, lane-aligned cap); within the
    # eligible set the winner comes from the per-backend dispatch table
    # ("ivf_scan_extract", captured by microbench.bench_scan_extract),
    # analytic fallback = binned whenever legal (the k-pass sweep's
    # unrolled extraction is the known slow arm). Resolved HERE, outside
    # the jit boundary, so the choice participates in the jit cache key
    # and mode/table changes take effect per call. An explicit
    # ``extract`` bypasses the table (the microbench forcing each arm).
    from raft_tpu import obs, tuning

    cap = (storage.shape[2]
           if (packed_i4 or packed_bits or lut_weights is not None)
           else storage.shape[1])
    binned_ok = approx and cap % 128 == 0 and cap > 128
    # single-slot binning is only eligible when its collision-loss
    # model fits the caller's per-list recall budget (binned_loss_fits
    # above) — the old flat k <= 64 cap admitted ~25% loss at k=64,
    # caught by the kernel-contract sweep's lane-boundary cases (r6,
    # tests/test_kernel_contracts.py)
    eligible = ["exact"]
    if binned_ok and k <= 64 and binned_loss_fits(k, recall_target):
        eligible.append("binned")
    if binned_ok and k <= 256:
        eligible.append("binned_deep")
        eligible.append("fold")
    if extract is None:
        analytic = ("binned" if "binned" in eligible
                    else "binned_deep" if binned_ok and k <= 256
                    else "exact")
        extract = tuning.choose(
            "ivf_scan_extract",
            {"cap": int(cap), "k": int(k), "g": int(qv.shape[1])},
            eligible, analytic,
        )
    elif extract not in eligible:
        raise ValueError(
            f"extract={extract!r} not eligible here (allowed: {eligible})")
    # trace-time span (the kernel runs under the callers' jits):
    # attributes compile cost per extraction arm, silent when cached
    with obs.span("fused_list_scan_topk", extract=extract, cap=int(cap),
                  k=int(k), nb=int(bucket_list.shape[0])):
        return _fused_list_scan_topk(
            storage, indices, list_sizes, bucket_list, qv, qaux, norms,
            keep, lut_weights, row_scale, k=k, metric_kind=metric_kind,
            interpret=interpret, packed_i4=packed_i4,
            packed_bits=packed_bits, extract=extract,
        )


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric_kind", "interpret", "packed_i4",
                     "packed_bits", "extract"),
)
def _fused_list_scan_topk(
    storage, indices, list_sizes, bucket_list, qv, qaux=None, norms=None,
    keep=None, lut_weights=None, row_scale=None, *,
    k: int, metric_kind: int, interpret: bool = False,
    packed_i4: bool = False, packed_bits: bool = False,
    extract: str = "exact",
):
    packed_pq4 = lut_weights is not None
    if packed_pq4 and packed_i4:
        raise ValueError("packed_i4 and lut_weights are mutually exclusive")
    if packed_bits and (packed_i4 or packed_pq4):
        raise ValueError(
            "packed_bits is mutually exclusive with packed_i4/lut_weights")
    if packed_i4:
        C, nw_c, cap = storage.shape
        d = nw_c * 8
    elif packed_bits:
        C, nw_c, cap = storage.shape
        d = nw_c * 32
    elif packed_pq4:
        C, nw_c, cap = storage.shape
        d = lut_weights.shape[1]                       # rot_dim
        p_sub = lut_weights.shape[2]
        if p_sub > nw_c * 8:
            raise ValueError(
                f"lut_weights pq_dim {p_sub} exceeds packed capacity "
                f"{nw_c * 8}")
    else:
        C, cap, d = storage.shape
    nb, G, _ = qv.shape
    has_norms = norms is not None
    has_filter = keep is not None
    has_row_scale = row_scale is not None

    # 2-D per-row arrays are lifted to [*, 1, n] so each block equals the
    # full trailing dims (the Mosaic block rule: last two dims divisible by
    # (8, 128) or equal to the array's)
    inputs = [storage, indices.reshape(C, 1, cap)]
    in_specs = [
        pl.BlockSpec(
            (1, nw_c, cap) if (packed_i4 or packed_pq4 or packed_bits)
            else (1, cap, d),
            lambda i, bl, ls: (bl[i], 0, 0),
        ),
        pl.BlockSpec((1, 1, cap), lambda i, bl, ls: (bl[i], 0, 0)),
    ]
    if has_norms:
        inputs.append(norms.reshape(C, 1, cap))
        in_specs.append(
            pl.BlockSpec((1, 1, cap), lambda i, bl, ls: (bl[i], 0, 0))
        )
    if has_filter:
        inputs.append(keep.reshape(C, 1, cap))
        in_specs.append(
            pl.BlockSpec((1, 1, cap), lambda i, bl, ls: (bl[i], 0, 0))
        )
    if has_row_scale:
        inputs.append(row_scale.reshape(C, 1, cap))
        in_specs.append(
            pl.BlockSpec((1, 1, cap), lambda i, bl, ls: (bl[i], 0, 0))
        )
    inputs.append(qv)
    in_specs.append(pl.BlockSpec((1, G, d), lambda i, bl, ls: (i, 0, 0)))
    if packed_pq4:
        # full codebook weights resident per step (small: 16*rot*p)
        inputs.append(lut_weights.astype(qv.dtype))
        in_specs.append(
            pl.BlockSpec(lut_weights.shape, lambda i, bl, ls: (0, 0, 0))
        )
    if metric_kind != IP:
        inputs.append(qaux.reshape(nb, 1, G))
        in_specs.append(
            pl.BlockSpec((1, 1, G), lambda i, bl, ls: (i, 0, 0))
        )

    kernel = functools.partial(
        _scan_kernel,
        k=k, metric_kind=metric_kind, extract=extract,
        has_norms=has_norms, has_filter=has_filter, packed_i4=packed_i4,
        packed_pq4=packed_pq4, packed_bits=packed_bits,
        has_row_scale=has_row_scale,
    )
    # candidate width: the extracting arms emit k columns; the fold arm
    # emits its full R*128 lane-stack buffer (selection deferred)
    kc = 128 * _fold_depth(k) if extract == "fold" else k
    out_d, out_i = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, G, kc), lambda i, bl, ls: (i, 0, 0)),
                pl.BlockSpec((1, G, kc), lambda i, bl, ls: (i, 0, 0)),
            ],
            scratch_shapes=(
                [pltpu.VMEM((d, cap), qv.dtype)]
                if (packed_i4 or packed_bits)
                else [pltpu.VMEM((nw_c * 8, cap), jnp.int32)] if packed_pq4
                else []
            ),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((nb, G, kc), jnp.float32),
            jax.ShapeDtypeStruct((nb, G, kc), jnp.int32),
        ],
        interpret=interpret,
    )(bucket_list, list_sizes, *inputs)
    return out_d, out_i


# ---------------------------------------------------------------------------
# kernel contract (graft-kern; docs/static_analysis.md §engine-4)
# ---------------------------------------------------------------------------

from raft_tpu.analysis.contracts import kernel_contract  # noqa: E402


def _scan_case_derive(case: dict) -> dict:
    case.setdefault("C", 4)
    case.setdefault("G", 8)
    case.setdefault("nb", 4)
    case.setdefault("d", 32)
    case.setdefault("metric_kind", L2)
    has_norms = case["metric_kind"] != IP
    case.setdefault("norms", has_norms)
    case.setdefault("qaux", has_norms)
    case.setdefault("keep", False)
    if case.get("packed_i4"):
        case["nw_c"] = case["d"] // 8
        case["storage_shape"] = ("C", "nw_c", "cap")
        case["storage_dtype"] = "uint32"
        case["lut_weights"] = False
    elif case.get("rabitq"):
        # 1-bit sign codes: 32/word, last word PARTIAL when d % 32 != 0
        # (pad bits decode -1; queries are zero-padded to dp = nw*32)
        case["nw_c"] = -(-case["d"] // 32)
        case["dp"] = case["nw_c"] * 32
        case["storage_shape"] = ("C", "nw_c", "cap")
        case["storage_dtype"] = "uint32"
        case["qv_shape"] = ("nb", "G", "dp")
        case["packed_bits"] = True
        case["row_scale"] = True
        case["row_scale_dtype"] = "float32"
        case["lut_weights"] = False
    elif case.get("pq4"):
        case["nw_c"] = case.setdefault("p", case["d"] // 4) // 8 or 1
        case.setdefault("rot", case["d"])
        case["storage_shape"] = ("C", "nw_c", "cap")
        case["storage_dtype"] = "uint32"
        case["lut_weights"] = True
    else:
        case["storage_shape"] = ("C", "cap", "d")
        case["lut_weights"] = False
    return case


def _scan_case_ok(case: dict) -> bool:
    cap, k, ex = case.get("cap", 0), case.get("k", 1), case["extract"]
    if not 0 < k:
        return False
    if ex == "exact":
        # cap the unrolled k-pass sweep: the dispatch layer hands
        # k > 64 to the binned/fold arms anyway, and a 200-pass unroll
        # makes the interpret sweep minutes-long
        return k <= 32
    if cap % 128 != 0 or cap <= 128:
        return False
    if ex == "binned":
        # the entry point's own loss model at the default target — no
        # hand-mirrored constant to drift (review fix, r6)
        return binned_loss_fits(k)
    return k <= 256


kernel_contract(
    "ivf_scan",
    module=__name__,
    entry="fused_list_scan_topk",
    driver="raft_tpu.analysis.contract_drivers:drive_list_scan",
    tail_rows="masked",          # col >= size masked to +inf in-kernel
    k_range=(1, 256),
    dtypes=("float32", "bfloat16"),
    exactness="bitwise",
    recall_floor=0.93,           # the tpu_parity binned band
    base={"cap": 256, "C": 4, "G": 8, "nb": 4, "d": 32,
          "metric_kind": L2},
    rows_key="cap", batch_key="G",
    arms=({"extract": "exact", "k_max": 32},
          {"extract": "binned", "k_max": binned_k_cap()},
          {"extract": "binned_deep", "k_max": 65},
          {"extract": "fold", "k_max": 256}),
    arrays={"storage": ("C", "cap", "d"), "indices": ("C", "cap"),
            "list_sizes": ("C",), "bucket_list": ("nb",),
            "qv": ("nb", "G", "d"), "qaux": ("nb", "G"),
            "norms": ("C", "cap"), "keep": ("C", "cap"),
            "row_scale": ("C", "cap"), "lut_weights": (16, "rot", "p")},
    derive=_scan_case_derive,
    case_filter=_scan_case_ok,
    extra_cases=(
        # metric spot checks on the exact arm
        {"extract": "exact", "k": 10, "cap": 256, "metric_kind": IP,
         "dtype": "float32"},
        {"extract": "exact", "k": 10, "cap": 256, "metric_kind": COSINE,
         "dtype": "float32"},
        # filtered-scan geometry (keep-mask block rides the site)
        {"extract": "exact", "k": 10, "cap": 256, "keep": True,
         "dtype": "float32", "static_only": True},
        # packed-storage geometry for the static engine; the packed
        # dynamics are pinned by test_ivf_pq + pallas_parity
        {"extract": "exact", "k": 10, "cap": 256, "packed_i4": True,
         "dtype": "bfloat16", "static_only": True},
        {"extract": "exact", "k": 10, "cap": 256, "pq4": True,
         "dtype": "bfloat16", "static_only": True},
        # rabitq sign-bit arm (ISSUE 11): driven DYNAMICALLY here — the
        # estimator's XLA mirror is the oracle. Adversarial classes:
        # dim divisible by 32, dim NOT divisible by 32 (partial last
        # word: pad bits decode -1, zero-padded queries must null them),
        # non-lane-multiple dims, k == n (whole-list edge), and the
        # single/short-row lists every case exercises via the driver's
        # short-size second pass. The estimator-unbiasedness statistical
        # check vs the exact-distance oracle lives in
        # tests/test_kernel_contracts.py::test_rabitq_estimator_unbiased.
        {"extract": "exact", "k": 10, "cap": 256, "rabitq": True,
         "d": 64, "dtype": "float32"},
        {"extract": "exact", "k": 10, "cap": 256, "rabitq": True,
         "d": 48, "dtype": "float32"},          # partial last word
        {"extract": "exact", "k": 10, "cap": 256, "rabitq": True,
         "d": 40, "dtype": "bfloat16"},         # non-lane-multiple dim
        # k == n at lane-legal geometry (cap < 128 cannot reach the
        # kernel through dispatch — _resolve_scan_impl requires
        # cap % 128 == 0 — so the whole-list edge rides the fold arm)
        {"extract": "fold", "k": 256, "cap": 256, "rabitq": True,
         "d": 64, "dtype": "float32"},
        # k == 1: the driver's short-size pass makes this the
        # single-row-list case (size = 1)
        {"extract": "exact", "k": 1, "cap": 256, "rabitq": True,
         "d": 64, "dtype": "float32"},
        {"extract": "binned", "k": 10, "cap": 256, "rabitq": True,
         "d": 64, "dtype": "float32"},
        {"extract": "fold", "k": 65, "cap": 256, "rabitq": True,
         "d": 64, "dtype": "float32"},
    ),
    notes="binned loses ~C(k,2)/128 per list, binned_deep/fold lose "
          "only when > R of the list's top-k share a lane; the "
          "cross-probe merge recovers survivors (docs/kernels.md).",
)
