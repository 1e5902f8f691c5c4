"""Fused CAGRA beam-search step: score + merge + dedup + parent pick.

TPU-native analog of the reference's single-CTA CAGRA search iteration
(cpp/include/raft/neighbors/detail/cagra/search_single_cta_kernel-inl.cuh:585:
topk_by_bitonic_sort :405, pickup_next_parents :682, hashmap dedup
hashmap.hpp:41) — the entire per-iteration pipeline the reference keeps
in CTA shared memory lives here in VMEM:

* the itopk result buffer (distances, ids, explored flags),
* int8 candidate scoring from the PACKED neighbor rows (one int32 row
  per parent carries codes + norms + neighbor ids; measured r3 on v5e
  (PALLAS_PARITY_r03.json): one fused int32 row gather is ~7x faster
  than separate int8-codes + norms + graph gathers of the same bytes),
* the bitonic merge network,
* windowed duplicate collapse (the visited-hashmap analog), and
* next-iteration parent selection,

so one iteration costs one HBM pass over the gathered rows plus a
read+write of the small buffer state, instead of the ~36 full-array HBM
round trips the XLA compare-exchange network pays.

Layout: all per-query state is TRANSPOSED to [slots, n_queries] so the
sort axis is the *sublane* axis — every compare-exchange is a
full-width [j, G]-tile vector op and reshape regrouping touches only
leading dims (the lane dim G stays 128). The un-transposed form would
put the sort axis on lanes, where sub-128 slicing forces relayouts.

Packed row format (built by cagra._attach_inline), per node, int32:
``[deg*d/4 code words | deg norm bitcasts (L2 only) | deg neighbor ids]``
— code word ``e*(d/4)+t`` holds int8 dims ``4t..4t+3`` of neighbor ``e``
(little-endian), so in-kernel decode is shift/mask/sign-extend and the
query rides pre-permuted+tiled (``qrep``) to line up per byte lane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INVALID = -1

# static-unroll the per-parent-slot scoring loop (saves the fori_loop's
# dynamic-offset loads; costs more scoped VMEM — tune on-chip)
_UNROLL_SCORE = False


def _next_pow2(x: int) -> int:
    return 1 << (int(x) - 1).bit_length()


def _a128(v: int) -> int:
    return -(-v // 128) * 128


def beam_step_vmem_bytes(g: int, L: int, width: int, deg: int, d: int,
                         ip: bool = False) -> int:
    """Per-grid-step VMEM bytes of the packed-scoring beam kernel at
    query tile ``g``: in/out blocks + the [C, g] decode scratch + the
    live [LL, g] sort pair. The eligibility rule behind the
    ``beam_step_tile`` dispatch candidates (cagra._resolve_beam_tile) —
    a tile only races when this fits ~half of per-core VMEM."""
    dw = deg * (d // 4)
    W = packed_row_layout(deg, d, ip)[3]
    C = width * deg
    LL = _next_pow2(max(L + C, 2))
    blocks = (
        6 * L * g * 4            # buffer state in + out (d, i, e)
        + g * 4 * dw * 2         # qrep (bf16)
        + g * width * W * 4      # packed rows (flattened)
        + 2 * width * g * 4      # parents in + out
        + 2 * C * g * 4          # cd/ci decode scratch
    )
    live = 2 * LL * g * 4        # the sort network's key + payload
    return blocks + live


def packed_row_layout(deg: int, d: int, ip: bool = False):
    """THE single definition of the packed inline row layout, shared by
    the builder (cagra._pack_tables), the HBM-budget check
    (cagra._attach_inline) and the kernel decode below: returns
    ``(dw, o_norm, o_id, W)`` — code-word count, norm-region offset,
    id-region offset, total int32 row width. Every region is padded to a
    128-lane multiple (dynamic lane loads need aligned offsets); IP rows
    carry no norm region."""
    dw = deg * (d // 4)
    o_norm = _a128(dw)
    o_id = o_norm + (0 if ip else _a128(deg))
    return dw, o_norm, o_id, o_id + _a128(deg)


def _sort_rows(kd, payloads, LL: int):
    """Bitonic sort along axis 0 (sublanes) of [LL, G] arrays; payloads
    ride the same compare-exchange.

    Stage directions are applied structurally (no mask constants, which
    pallas kernels may not capture): at stage ``k`` the direction is
    constant over each k-block and alternates asc/desc per block, so the
    view [B/2, 2, k/(2j), 2, j, G] lets axis 1 select the direction and
    axis 3 the partner."""
    G = kd.shape[-1]

    k = 2
    while k <= LL:
        j = k // 2
        while j >= 1:
            B = LL // k          # k-blocks; all-ascending when B == 1
            if B == 1:
                shape = (1, 1, k // (2 * j), 2, j, G)
            else:
                shape = (B // 2, 2, k // (2 * j), 2, j, G)

            def pair(x):
                v = x.reshape(shape)
                return v[:, :, :, 0], v[:, :, :, 1]  # [B2, D, k/2j, j, G]

            k0, k1 = pair(kd)
            if B == 1:
                swap = k0 > k1
            else:
                # int32 concat, then compare: Mosaic rejects i1 vector
                # concatenation ("invalid vector register cast")
                swap = jnp.concatenate(
                    [(k0[:, :1] > k1[:, :1]).astype(jnp.int32),
                     (k0[:, 1:] < k1[:, 1:]).astype(jnp.int32)], axis=1
                ) != 0

            def exch(x):
                x0, x1 = pair(x)
                lo = jnp.where(swap, x1, x0)
                hi = jnp.where(swap, x0, x1)
                return jnp.stack([lo, hi], axis=3).reshape(LL, G)

            kd = exch(kd)
            payloads = [exch(p) for p in payloads]
            j //= 2
        k *= 2
    return kd, payloads


def _dedup_rows(kd, kie, window: int):
    """Windowed dup collapse on the sorted [LL, G] buffer (duplicate ids
    score near-identically, so they sort adjacent): later copies blank
    to (+inf, -1); the kept copy inherits the explored flag.

    ``kie`` packs ``(id << 1) | explored`` so the sort network carries
    ONE payload instead of two (ids must stay < 2^30; the -1 sentinel
    encodes (id=-1, explored) since (-1<<1)|1 == -1)."""
    LL, G = kie.shape
    ids = kie >> 1
    dup = jnp.zeros((LL, G), jnp.int32)
    for s in range(1, window + 1):
        eq = ((ids[s:] == ids[:-s]) & (ids[s:] >= 0)).astype(jnp.int32)
        dup = dup | jnp.concatenate(
            [jnp.zeros((s, G), jnp.int32), eq], axis=0
        )
        inherit = eq * (kie[s:] & 1)
        kie = kie | jnp.concatenate(
            [inherit, jnp.zeros((s, G), jnp.int32)], axis=0
        )
    isdup = dup != 0
    kd = jnp.where(isdup, jnp.inf, kd)
    kie = jnp.where(isdup, _INVALID, kie)
    return kd, kie


def _pick_rows(kd, kie, width: int):
    """First ``width`` unexplored live rows per column (lane) —
    prefix-sum rank + masked-max extraction (pickup_next_parents)."""
    L, G = kie.shape
    ids = kie >> 1
    une = ((kie & 1) == 0) & (ids >= 0) & (kd < jnp.inf)
    r = une.astype(jnp.int32)
    off = 1
    while off < L:
        r = r + jnp.concatenate(
            [jnp.zeros((off, G), jnp.int32), r[:-off]], axis=0
        )
        off *= 2
    rank = r - 1                                   # 0-based among unexplored
    sel = une & (rank < width)
    parents = [
        jnp.max(jnp.where(sel & (rank == j), ids, _INVALID), axis=0)
        for j in range(width)
    ]                                              # width x [G]
    return parents, kie | sel.astype(jnp.int32)


def _beam_step_kernel(
    *refs,
    L: int, deg: int, d: int, width: int, window: int, ip: bool,
    scored: bool, emit_cands: bool = False,
):
    refs = list(refs)
    bd_ref = refs.pop(0)        # [L, G] f32
    bi_ref = refs.pop(0)        # [L, G] i32
    be_ref = refs.pop(0)        # [L, G] i32
    G = bd_ref.shape[1]

    if scored:
        cd = refs.pop(0)[...]                      # [C, G] f32 pre-scored
        ci = refs.pop(0)[...]                      # [C, G] i32
        C = ci.shape[0]
        cd = jnp.where(ci < 0, jnp.inf, cd)
        obd_ref, obi_ref, obe_ref, par_ref = refs
    else:
        qrep_ref = refs.pop(0)   # [G, 4, dw] bf16 (pre-scaled + tiled)
        pack_ref = refs.pop(0)   # [G, width*W] i32 packed rows (flat)
        par_ref_in = refs.pop(0)  # [width, G] i32 previous parents
        if emit_cands:
            (obd_ref, obi_ref, obe_ref, par_ref,
             ocd_ref, oci_ref) = refs[:6]
            cd_ref, ci_ref = refs[6:]              # [C, G] VMEM scratch
        else:
            obd_ref, obi_ref, obe_ref, par_ref = refs[:4]
            cd_ref, ci_ref = refs[4:]              # [C, G] VMEM scratch
        C = width * deg
        W = pack_ref.shape[1] // width
        dw, o_norm, o_id, _W = packed_row_layout(deg, d, ip)
        a128 = _a128
        qr = qrep_ref[...]                         # [G, 4, dw]
        # per-32-lane-segment reduction as a one-hot MXU matmul (a
        # minor-dim split reshape + sum is an unsupported Mosaic
        # relayout); seg[l, e] = 1 iff lane l belongs to neighbor e
        seg = (
            jax.lax.broadcasted_iota(jnp.int32, (dw, deg), 0) // (d // 4)
            == jax.lax.broadcasted_iota(jnp.int32, (dw, deg), 1)
        ).astype(jnp.float32)

        def score_one(w, _):
            # fori_loop (not unroll) so the decode temporaries of the
            # ``width`` slots share one VMEM allocation — unrolled, the
            # kernel's scoped-VMEM stack overflows at G=128. The packed
            # rows ride FLATTENED to [G, width*W] so the dynamic slot
            # offset w*W is a 128-aligned LANE offset (dynamic sublane
            # indexing is unsupported in Mosaic).
            base = w * W
            words = pack_ref[:, pl.ds(base, a128(dw))][:, :dw]  # [G, dw]
            acc = jnp.zeros((G, dw), jnp.float32)
            for j in range(4):
                # 2-op sign-extending byte extract: left-align the byte,
                # arithmetic-shift back down
                b = (words << (24 - 8 * j)) >> 24
                acc = acc + (
                    b.astype(jnp.bfloat16) * qr[:, j, :]
                ).astype(jnp.float32)
            # HIGHEST: a default f32 dot rounds ``acc`` to bf16 before
            # the segment sum (seg is 0/1, exact in any pass)
            dots = jax.lax.dot_general(
                acc, seg,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                      # [G, deg]
            # load full 128-aligned regions, slice statically after
            idw = pack_ref[:, pl.ds(base + o_id, a128(deg))][:, :deg]
            if ip:
                cdw = -dots
            else:
                cdw = jax.lax.bitcast_convert_type(
                    pack_ref[:, pl.ds(base + o_norm, a128(deg))][:, :deg],
                    jnp.float32,
                ) - dots
            # expand the i32 first: a minor-dim insert on i1 vectors is
            # unsupported in Mosaic
            pokw = par_ref_in[pl.ds(w, 1), :]
            pok = pokw.T >= 0                      # [G, 1]
            cdw = jnp.where((idw < 0) | (~pok), jnp.inf, cdw)
            idw = jnp.where(pok, idw, _INVALID)
            cd_ref[pl.ds(w * deg, deg), :] = cdw.T
            ci_ref[pl.ds(w * deg, deg), :] = idw.T
            return _

        if _UNROLL_SCORE:
            for w in range(width):
                score_one(w, 0)
        else:
            jax.lax.fori_loop(0, width, score_one, 0)
        cd = cd_ref[...]
        ci = ci_ref[...]
        if emit_cands:
            # expose this iteration's scored candidates (filtered-search
            # side accumulation collects them outside the kernel)
            ocd_ref[...] = cd
            oci_ref[...] = ci

    LL = _next_pow2(L + C)
    pad = LL - L - C
    # pack (id << 1) | explored so the sort carries ONE payload; note
    # the -1 sentinel is itself (id=-1, explored) under this encoding
    kd_parts = [bd_ref[...], cd]
    kie_parts = [
        (bi_ref[...] << 1) | (be_ref[...] & 1),
        ci << 1,
    ]
    if pad:
        kd_parts.append(jnp.full((pad, G), jnp.inf, jnp.float32))
        kie_parts.append(jnp.full((pad, G), _INVALID, jnp.int32))
    kd = jnp.concatenate(kd_parts, axis=0)
    kie = jnp.concatenate(kie_parts, axis=0)

    kd, (kie,) = _sort_rows(kd, [kie], LL)
    kd, kie = _dedup_rows(kd, kie, window)
    kd, kie = kd[:L], kie[:L]
    parents, kie = _pick_rows(kd, kie, width)

    obd_ref[...] = kd
    obi_ref[...] = kie >> 1
    obe_ref[...] = kie & 1
    for j in range(width):
        par_ref[j, :] = parents[j]


@functools.partial(
    jax.jit,
    static_argnames=("deg", "d", "width", "window", "ip", "g", "interpret",
                     "emit_cands"),
)
def beam_merge_step(
    buf_d,          # [L, m] f32  (sorted, transposed)
    buf_i,          # [L, m] i32
    buf_e,          # [L, m] i32 explored flags
    qrep=None,      # [m, 4, deg*(d//4)] bf16 pre-scaled/permuted/tiled query
    pack=None,      # [m, width, W] i32 gathered packed neighbor rows
    parents=None,   # [width, m] i32 parents the rows were gathered for
    cand_d=None,    # [C, m] f32 pre-computed candidate distances
    cand_i=None,    # [C, m] i32 candidate ids (with cand_d)
    *,
    deg: int = 0,
    d: int = 0,
    width: int,
    window: int = 2,
    ip: bool = False,
    g: int = 128,
    interpret: bool = False,
    emit_cands: bool = False,
):
    """One fused beam-search step over transposed state.

    Either pass ``cand_d`` + ``cand_i`` (pre-scored candidates — used
    for seeding), or ``qrep`` + ``pack`` + ``parents``, in which case
    the packed rows are decoded and scored in-kernel (fold any dequant
    scale into ``qrep`` beforehand; invalid parents (< 0) mask their
    whole candidate block).

    Returns (buf_d, buf_i, buf_e, parents [width, m]); the output
    buffer is distance-sorted, deduplicated, truncated to L slots, with
    the picked parents marked explored. A query count off the ``g``
    lane tile is padded up with inert columns (empty buffer, invalid
    candidates/parents) and sliced back off the outputs — callers no
    longer need to pre-round m.

    ``emit_cands`` (packed-scoring mode only) additionally returns the
    iteration's raw scored candidates (cand_d [C, m] f32, cand_i
    [C, m] i32) so filtered search can side-accumulate valid results
    outside the kernel while traversal itself stays unfiltered.
    """
    L, m0 = buf_d.shape
    scored = cand_d is not None
    m = -(-m0 // g) * g
    if m != m0:
        # tail columns: empty explored buffer + invalid candidates (and
        # parents -1, which mask their whole candidate block in packed
        # mode), so pad lanes compute nothing and pick no parents
        pc = m - m0
        buf_d = jnp.pad(buf_d, ((0, 0), (0, pc)),
                        constant_values=jnp.inf)
        buf_i = jnp.pad(buf_i, ((0, 0), (0, pc)),
                        constant_values=_INVALID)
        buf_e = jnp.pad(buf_e, ((0, 0), (0, pc)), constant_values=1)
        if scored:
            cand_d = jnp.pad(cand_d, ((0, 0), (0, pc)),
                             constant_values=jnp.inf)
            cand_i = jnp.pad(cand_i, ((0, 0), (0, pc)),
                             constant_values=_INVALID)
        else:
            qrep = jnp.pad(qrep, ((0, pc), (0, 0), (0, 0)))
            pack = jnp.pad(pack, ((0, pc), (0, 0), (0, 0)))
            parents = jnp.pad(parents, ((0, 0), (0, pc)),
                              constant_values=_INVALID)
    nsteps = m // g

    col = lambda i: (0, i)
    inputs = [buf_d, buf_i, buf_e]
    in_specs = [pl.BlockSpec((L, g), col) for _ in range(3)]
    if scored:
        C = cand_i.shape[0]
        inputs += [cand_d, cand_i]
        in_specs += [pl.BlockSpec((C, g), col), pl.BlockSpec((C, g), col)]
        dd = 0
    else:
        if d % 4:
            raise ValueError(f"packed scoring needs d % 4 == 0, got {d}")
        W = pack.shape[2]
        if W % 128:
            raise ValueError(f"packed row width must be 128-aligned, got {W}")
        dwq = qrep.shape[2]
        inputs += [qrep, pack.reshape(m, width * W), parents]
        in_specs += [
            # (g, 4, dwq): the 4-row byte-lane query replication. The
            # old literal-GL006 screen needed a suppression here; the
            # graft-kern computed audit proves the spec legal — sublane
            # dim 4 EQUALS the array dim (the real Mosaic rule), so no
            # relayout and no exception needed (r6)
            pl.BlockSpec((g, 4, dwq), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, width * W), lambda i: (i, 0)),
            pl.BlockSpec((width, g), col),
        ]
        dd = d

    emit = emit_cands and not scored
    kernel = functools.partial(
        _beam_step_kernel,
        L=L, deg=deg, d=dd, width=width, window=window, ip=ip,
        scored=scored, emit_cands=emit,
    )
    scratch = []
    if not scored:
        C = width * deg
        scratch = [
            pltpu.VMEM((C, g), jnp.float32),
            pltpu.VMEM((C, g), jnp.int32),
        ]
    out_specs = [
        pl.BlockSpec((L, g), col),
        pl.BlockSpec((L, g), col),
        pl.BlockSpec((L, g), col),
        pl.BlockSpec((width, g), col),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((L, m), jnp.float32),
        jax.ShapeDtypeStruct((L, m), jnp.int32),
        jax.ShapeDtypeStruct((L, m), jnp.int32),
        jax.ShapeDtypeStruct((width, m), jnp.int32),
    ]
    if emit:
        C = width * deg
        out_specs += [pl.BlockSpec((C, g), col), pl.BlockSpec((C, g), col)]
        out_shape += [
            jax.ShapeDtypeStruct((C, m), jnp.float32),
            jax.ShapeDtypeStruct((C, m), jnp.int32),
        ]
    outs = pl.pallas_call(
        kernel,
        grid=(nsteps,),
        in_specs=in_specs,
        scratch_shapes=scratch,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    if m != m0:
        outs = tuple(o[:, :m0] for o in outs)
    return outs


# ---------------------------------------------------------------------------
# kernel contract (graft-kern; docs/static_analysis.md §engine-4)
# ---------------------------------------------------------------------------

from raft_tpu.analysis.contracts import kernel_contract  # noqa: E402


def _beam_case_derive(case: dict) -> dict:
    case.setdefault("g", 128)
    case.setdefault("m", case["g"])
    case.setdefault("width", 4)
    case.setdefault("window", 2)
    case.setdefault("ip", False)
    case.setdefault("emit_cands", False)
    if case.get("scored", True):
        case.setdefault("C", 32)
        case["cand_d"] = case["cand_i"] = True
        case["qrep"] = case["pack"] = case["parents"] = False
        case.setdefault("deg", 0)
        case.setdefault("d", 0)
    else:
        case.setdefault("deg", 16)
        case.setdefault("d", 32)
        case["C"] = case["width"] * case["deg"]
        case["W"] = packed_row_layout(case["deg"], case["d"],
                                      case["ip"])[3]
        case["dwq"] = case["deg"] * (case["d"] // 4)
        case["qrep"] = case["pack"] = case["parents"] = True
        case["cand_d"] = case["cand_i"] = False
        case["qrep_dtype"] = "bfloat16"
        case["pack_dtype"] = "int32"
    return case


from raft_tpu.tuning import BEAM_STEP_TILES  # noqa: E402

kernel_contract(
    "beam_step",
    module=__name__,
    entry="beam_merge_step",
    driver="raft_tpu.analysis.contract_drivers:drive_beam_step",
    tail_rows="padded",          # m % g pads inert lanes, sliced off
    k_range=(1, 1),
    k_key=None,                  # no k: the buffer length L is static
    dtypes=("float32",),
    exactness="bitwise",
    base={"L": 16, "m": 128, "g": 128},
    arms=(),
    arrays={"buf_d": ("L", "m"), "buf_i": ("L", "m"), "buf_e": ("L", "m"),
            "cand_d": ("C", "m"), "cand_i": ("C", "m"),
            "qrep": ("m", 4, "dwq"), "pack": ("m", "width", "W"),
            "parents": ("width", "m")},
    derive=_beam_case_derive,
    extra_cases=tuple(
        [
            # scored arm: merge/dedup/pick pipeline vs the numpy oracle
            {"scored": True, "L": 16, "C": 32, "m": 128, "width": 4},
            {"scored": True, "L": 8, "C": 8, "m": 128, "width": 2},
            {"scored": True, "L": 16, "C": 32, "m": 256, "width": 4,
             "window": 3},
            # non-pow2 buffer + candidate counts: LL pads internally
            {"scored": True, "L": 12, "C": 20, "m": 128, "width": 3},
            # tail rows: m off the lane tile pads inert columns
            {"scored": True, "L": 16, "C": 32, "m": 100, "width": 4},
            # k/degree boundary cases: one candidate, one parent; a
            # tiny buffer against a wide candidate block
            {"scored": True, "L": 16, "C": 1, "m": 128, "width": 1},
            {"scored": True, "L": 2, "C": 24, "m": 128, "width": 2,
             "window": 1},
            # packed-scoring arm, DRIVEN: in-kernel int8 word decode +
            # scoring vs the same arithmetic through XLA, then the
            # merge oracle (judged per-id within bf16 rounding)
            {"scored": False, "deg": 8, "d": 32, "L": 16, "m": 128,
             "width": 2},
            {"scored": False, "deg": 8, "d": 32, "L": 8, "m": 128,
             "width": 3, "ip": True},
            {"scored": False, "deg": 16, "d": 64, "L": 16, "m": 128,
             "width": 4, "emit_cands": True},
            # packed arm, tail rows: padded parents mask their blocks
            {"scored": False, "deg": 8, "d": 32, "L": 16, "m": 90,
             "width": 2},
            # deg/d geometry boundaries (static bindings): minimal
            # packed row (every region one 128-pad), and a wide row
            # where the id region crosses its own 128 boundary
            {"scored": False, "deg": 4, "d": 4, "L": 16, "m": 128,
             "width": 4, "static_only": True},
            {"scored": False, "deg": 32, "d": 64, "L": 32, "m": 256,
             "width": 4, "static_only": True},
        ]
        + [
            # every dispatchable query tile (op key beam_step_tile;
            # winner strings carry g) gets a geometry case, so the
            # static audit covers each injectable lane tile
            {"scored": False, "deg": 16, "d": 32, "L": 64, "m": 2 * g,
             "g": g, "width": 4, "static_only": True}
            for g in BEAM_STEP_TILES
        ]
        + [
            {"scored": True, "L": 16, "C": 32, "m": 2 * g, "g": g,
             "width": 4}
            for g in BEAM_STEP_TILES
        ]
    ),
    notes="all per-query state rides TRANSPOSED [slots, m] so the sort "
          "axis is the sublane axis; m off the g lane tile is padded "
          "with inert columns and sliced back (tail_rows='padded'); "
          "the packed arm's int8 word decode is driven against the "
          "same arithmetic through XLA (bf16-rounded products, f32 "
          "accumulation).",
)
