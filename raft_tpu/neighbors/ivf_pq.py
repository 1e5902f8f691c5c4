"""IVF-PQ: inverted-file index with product-quantized residuals.

TPU-native analog of the reference's ivf_pq
(cpp/include/raft/neighbors/ivf_pq.cuh; types ivf_pq_types.hpp:48-146; build
detail/ivf_pq_build.cuh:1753; search detail/ivf_pq_search.cuh:732 + LUT
similarity kernel detail/ivf_pq_compute_similarity-inl.cuh).

Build mirrors the reference pipeline: balanced-kmeans coarse centers, an
orthogonal rotation (QR of a random matrix, make_rotation_matrix:122),
per-subspace or per-cluster PQ codebooks trained on residuals
(train_per_subset:395 / train_per_cluster:472), then codes packed into
padded list blocks (process_and_fill_codes:1322).

Search is re-designed for the MXU rather than ported (SURVEY.md §7 "hard
parts" #1): the reference builds a per-(query,probe) LUT in shared memory
and gathers LUT entries per code. TPUs have no fast per-lane gather, so we
**decode-then-matmul**: reconstruct each probed list block from its codes
(a small codebook gather), then score a whole query group against the block
with one ``[G, rot_dim] x [rot_dim, cap]`` MXU contraction — identical
shape to the IVF-Flat scan, with ``||recon||^2`` precomputed at build. The
index stays PQ-compressed in HBM (codes + 1 f32 norm per vector), which is
what buys billion-scale capacity; decode cost is amortized over the whole
query group sharing the list.

Uses the same bucketize-by-list machinery as ivf_flat (bucketize_pairs).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
from raft_tpu import obs
from raft_tpu.core.serialize import read_index_file, write_index_file
from raft_tpu.distance.types import DistanceType, is_min_close, resolve_metric
from raft_tpu.matrix.select_k import select_k
from raft_tpu.neighbors.common import (
    as_filter,
    filter_keep,
    merge_topk,
    resolve_filter_bits,
    sentinel_for,
)
from raft_tpu.neighbors.ivf_flat import (
    _pack_lists,
    bucketize_pairs,
    unbucketize_merge,
)
from raft_tpu.utils.math import round_up_to_multiple
from raft_tpu.utils.precision import argmin_exact, dist_dot

_SERIAL_VERSION = 4  # v4: rabitq sign-bit cache (cache_fac sidecar)
# (v3: serialized cache for cache-only indexes;
#  v2: bit-packed uint32 code words + pq_dim in meta)


class codebook_gen:
    """Codebook training mode (reference ivf_pq_types.hpp:48)."""

    PER_SUBSPACE = 0
    PER_CLUSTER = 1


# metrics the PQ residual scoring path implements; anything else would be
# silently mis-scored as L2 (reference ivf_pq has the same L2/IP restriction)
_SUPPORTED_METRICS = frozenset({
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.L2Unexpanded,
    DistanceType.InnerProduct,
})


@dataclasses.dataclass
class IndexParams:
    """Build params (reference ivf_pq_types.hpp:48-97)."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    metric_arg: float = 2.0
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8
    pq_dim: int = 0  # 0 → auto: dim/4 rounded to a multiple of 8 (reference heuristic)
    codebook_kind: int = codebook_gen.PER_SUBSPACE
    force_random_rotation: bool = False
    add_data_on_build: bool = True
    # coarse-quantizer training GEMM dtype ("f32" | "bf16", see ivf_flat)
    kmeans_compute_dtype: str = "f32"
    # build the decoded-residual cache (fused-Pallas search path);
    # auto-skipped above _CACHE_BUDGET bytes
    cache_decoded: bool = True
    # cache precision: "auto" picks int8 when it fits _CACHE_BUDGET and
    # falls to a half-byte rung (0.5 B/component — the 100M-scale regime
    # where int8 cannot share HBM with the codes) when that fits; which
    # half-byte rung (packed int4 residuals vs pq4 codes, recall-tied at
    # equal bytes) comes from the measured dispatch table
    # (docs/dispatch_tuning.md), defaulting to int4. "i8" / "i4" / "pq4"
    # force a kind (still budget-gated)
    cache_dtype: str = "auto"

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)
        if self.metric not in _SUPPORTED_METRICS:
            raise ValueError(
                f"ivf_pq supports {sorted(m.name for m in _SUPPORTED_METRICS)}, "
                f"got {self.metric!r}"
            )
        if not 4 <= self.pq_bits <= 8:
            raise ValueError(f"pq_bits must be in [4, 8], got {self.pq_bits}")


@dataclasses.dataclass
class SearchParams:
    """Search params (reference ivf_pq_types.hpp:110-146)."""

    n_probes: int = 20
    # Decode/scoring operand dtype ladder (the reference's LUT dtype ladder,
    # ivf_pq_types.hpp lut_dtype fp32/fp16/fp8): "auto" | "i8" | "f32" |
    # "bf16" | "f8". "auto" (default) scans the int8 decoded-residual
    # cache when the index carries one (the fast path; finer than the
    # reference's fp8 LUT) and falls back to f32 decode. "i8" requires the
    # cache. Explicit "f32"/"bf16"/"f8" force the decode-then-matmul scan
    # at that precision (jnp dtypes accepted).
    lut_dtype: object = "auto"
    # Distance accumulation/report dtype: "f32" | "bf16" (the reference's
    # internal_distance_dtype fp32/fp16 analog).
    internal_distance_dtype: object = "f32"
    # TPU tuning knobs (same role as in ivf_flat.SearchParams)
    query_group: int = 256
    bucket_batch: int = 32
    compute_dtype: str = "bf16"        # matmul operand dtype (f32 accumulate)
    # recall target for the per-list approx top-k; >= 1.0 runs it exactly.
    # The fused Pallas path also caps per-list extraction at 256
    # candidates (the reference's kMaxCapacity analog) — see
    # ivf_flat.SearchParams.local_recall_target.
    local_recall_target: float = 0.95
    # recall target for the FINAL cross-probe merge. Default 1.0 = exact
    # final selection, matching the reference's exact select_k merge
    # (ivf_pq_search.cuh:587); < 1.0 opts into the approximate merge.
    merge_recall_target: float = 1.0
    # "auto" = fused Pallas scan over the decoded-residual cache when the
    # index has one (TPU, lane-aligned cap, k<=64), else the XLA
    # decode-then-matmul scan; "pallas" | "pallas_interpret" | "xla" force
    scan_impl: str = "auto"


@dataclasses.dataclass
class Index:
    """IVF-PQ index (reference ivf_pq_types.hpp:199+).

    ``codes`` [n_lists, cap, n_words] uint32 — **bit-packed** PQ codes:
    ``32 // pq_bits`` codes per word (the reference packs a dense byte
    bitfield, ivf_pq_types.hpp:172-187; the word layout here avoids
    word-straddling codes, wasting <= 4 bits/word for pq_bits in {5,6,7}
    and nothing for 4/8 — shift+mask decode stays a pure VPU op).
    ``rec_norms`` [n_lists, cap] f32 (``||reconstructed residual||^2``);
    ``pq_centers``: [pq_dim, K, pq_len] (PER_SUBSPACE) or
    [n_lists, K, pq_len] (PER_CLUSTER); ``rotation`` [rot_dim, dim].
    """

    centers: jax.Array          # [n_lists, dim] f32
    centers_rot: jax.Array      # [n_lists, rot_dim] f32
    rotation: jax.Array         # [rot_dim, dim] f32
    pq_centers: jax.Array
    codes: jax.Array            # [n_lists, cap, n_words] uint32 (packed)
    indices: jax.Array          # [n_lists, cap] int32
    list_sizes: jax.Array       # [n_lists] int32
    rec_norms: jax.Array        # [n_lists, cap] f32
    metric: DistanceType
    pq_dim_: int
    metric_arg: float = 2.0
    codebook_kind: int = codebook_gen.PER_SUBSPACE
    pq_bits: int = 8
    # optional decoded-residual cache: int8 [n_lists, cap, rot_dim] (with
    # scalar ``recon_scale``) or packed int4 [n_lists, rot_dim//8, cap]
    # uint32 (with PER-LIST per-component ``cache_scales``
    # [n_lists, rot_dim] and dequantized norms ``cache_qnorms``). The codes stay the compressed
    # source of truth; search scans the cache with the fused Pallas
    # kernel (one MXU matmul per list block) instead of decode-then-
    # matmul. Budget-gated by _CACHE_BUDGET; rebuilt on load/extend
    # unless the index is cache-only (keep_codes=False), in which case
    # the cache IS serialized.
    recon_cache: object = None
    recon_scale: float = 1.0
    cache_scales: object = None      # [n_lists, rot_dim] f32 (int4 only)
    cache_qnorms: object = None      # [n_lists, cap] f32 (i4/rabitq caches)
    # rabitq per-row correction fac = ||r||²/||r||₁ ([n_lists, cap] f32):
    # the RaBitQ estimator's scalar — <q, r> ≈ fac · Σ_j sign(r_j)·q_j.
    # Presence discriminates the rabitq sign-bit cache from the other
    # uint32 kinds (see cache_kind)
    cache_fac: object = None
    cache_decoded: bool = True
    cache_dtype: str = "auto"

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.rotation.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def pq_dim(self) -> int:
        return self.pq_dim_

    @property
    def pq_len(self) -> int:
        return self.rot_dim // self.pq_dim

    @property
    def pq_book_size(self) -> int:
        return 1 << self.pq_bits

    @property
    def size(self) -> int:
        return int(self.list_sizes.sum())

    @property
    def cache_kind(self) -> str:
        """Which fused-scan operand the index carries: "i8" (int8 decoded
        residuals), "i4" (packed int4 raw residuals + per-list scales),
        "pq4" (transposed packed 4-bit codes — exact one-hot code scan),
        "rabitq" (packed sign bits + per-row norm/fac scalars — the
        ~32×-compressed first-stage rung), or "none". The u32 kinds are
        discriminated by their scalar sidecars: rabitq cannot exist
        without cache_fac, the i4 residual cache not without its
        per-list scales."""
        if self.recon_cache is None:
            return "none"
        if self.recon_cache.dtype == jnp.uint32:
            if self.cache_fac is not None:
                return "rabitq"
            return "i4" if self.cache_scales is not None else "pq4"
        return "i8"


jax.tree_util.register_dataclass(
    Index,
    data_fields=["centers", "centers_rot", "rotation", "pq_centers", "codes",
                 "indices", "list_sizes", "rec_norms", "recon_cache",
                 "cache_scales", "cache_qnorms", "cache_fac"],
    meta_fields=["metric", "pq_dim_", "metric_arg", "codebook_kind",
                 "pq_bits", "recon_scale", "cache_decoded", "cache_dtype"],
)

# decoded-residual cache is skipped when n_lists * cap * rot_dim exceeds
# this budget (bytes) — the decode-then-matmul scan path is used instead
_CACHE_BUDGET = 10 << 30


# ---------------------------------------------------------------------------
# bit-packed code words (reference ivf_pq_types.hpp:172-187 bitfield)
# ---------------------------------------------------------------------------


def codes_per_word(pq_bits: int) -> int:
    return 32 // pq_bits


def packed_words(pq_dim: int, pq_bits: int) -> int:
    return -(-pq_dim // codes_per_word(pq_bits))


def pack_codes(codes, pq_bits: int) -> jax.Array:
    """[..., pq_dim] uint8 -> [..., n_words] uint32 (no straddling)."""
    cpw = codes_per_word(pq_bits)
    p = codes.shape[-1]
    nw = packed_words(p, pq_bits)
    pad = nw * cpw - p
    c = jnp.asarray(codes).astype(jnp.uint32)
    if pad:
        c = jnp.concatenate(
            [c, jnp.zeros((*c.shape[:-1], pad), jnp.uint32)], axis=-1
        )
    c = c.reshape(*c.shape[:-1], nw, cpw)
    shifts = (jnp.arange(cpw, dtype=jnp.uint32) * pq_bits)
    return jnp.sum(c << shifts, axis=-1, dtype=jnp.uint32)


def unpack_codes(packed, pq_dim: int, pq_bits: int) -> jax.Array:
    """[..., n_words] uint32 -> [..., pq_dim] int32."""
    cpw = codes_per_word(pq_bits)
    j = jnp.arange(pq_dim)
    words = jnp.take(packed, j // cpw, axis=-1)          # [..., p]
    shifts = ((j % cpw) * pq_bits).astype(jnp.uint32)
    mask = jnp.uint32((1 << pq_bits) - 1)
    return ((words >> shifts) & mask).astype(jnp.int32)


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------


def make_rotation_matrix(
    rot_dim: int, dim: int, force_random: bool, key
) -> jax.Array:
    """Orthogonal rotation (reference ivf_pq_build.cuh:122): identity-padded
    unless forced random or rot_dim != dim, in which case QR of a Gaussian."""
    if not force_random and rot_dim == dim:
        return jnp.eye(dim, dtype=jnp.float32)
    g = jax.random.normal(key, (max(rot_dim, dim), max(rot_dim, dim)), jnp.float32)
    q, _ = jnp.linalg.qr(g)
    return q[:rot_dim, :dim]


def _auto_pq_dim(dim: int) -> int:
    # reference heuristic: dim/4 rounded down to a multiple of 8, >= 8
    v = max(8, (dim // 4) // 8 * 8)
    return min(v, dim)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _encode_subspace(residuals, pq_centers, K: int, block: int = 1 << 14):
    """codes[n, p] = argmin_j ||residuals[n,p,:] - pq_centers[p,j,:]||^2.

    Row-blocked under ``lax.map`` so the [block, p, K] distance tensor is
    the peak transient — unblocked, n=1M × p=64 × K=256 is a 65 GB
    intermediate (this crashed a v5e at CAGRA-build scale)."""
    n, p, plen = residuals.shape
    cn = jnp.sum(pq_centers * pq_centers, axis=2)[None, :, :]

    def one_block(res_b):
        dots = jnp.einsum(
            "npl,pkl->npk", res_b, pq_centers,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        rn = jnp.sum(res_b * res_b, axis=2)[:, :, None]
        return argmin_exact(rn - 2.0 * dots + cn, axis=2).astype(jnp.uint8)

    if n <= block:
        return one_block(residuals)
    npad = -(-n // block) * block
    res_p = jnp.pad(residuals, ((0, npad - n), (0, 0), (0, 0)))
    out = jax.lax.map(one_block, res_p.reshape(npad // block, block, p, plen))
    return out.reshape(npad, p)[:n]


def _decode_gather(codes, pq_centers, codebook_kind: int, list_ids=None):
    """Reconstruct rotated residuals from codes: one flat row-gather.

    codes [..., pq_dim] uint8 → [..., rot_dim] f32.
    PER_SUBSPACE: pq_centers [p, K, len], row index = s*K + code;
    PER_CLUSTER: pq_centers [C, K, len], row index = list*K + code with
    ``list_ids`` broadcastable to codes[..., 0]."""
    c32 = codes.astype(jnp.int32)
    K = pq_centers.shape[1]
    if codebook_kind == codebook_gen.PER_SUBSPACE:
        p = pq_centers.shape[0]
        flat_idx = c32 + (jnp.arange(p, dtype=jnp.int32) * K)  # [..., p]
    else:
        flat_idx = c32 + (jnp.asarray(list_ids, jnp.int32) * K)[..., None]
    table = pq_centers.reshape(-1, pq_centers.shape[-1])  # [p*K | C*K, len]
    recon = jnp.take(table, flat_idx, axis=0)  # [..., p, len]
    return recon.reshape(*codes.shape[:-1], -1)


def build(params: IndexParams, dataset, batch_size: Optional[int] = None) -> Index:
    """Build the index (reference ivf_pq_build.cuh:1753).

    ``batch_size`` streams an out-of-core host dataset through the encoder
    in fixed-size device batches (the reference's batch_load_iterator
    pipeline, spatial/knn/detail/ann_utils.cuh:397) — only the trainset,
    the per-batch slab, and the compressed codes ever live in HBM.
    """
    stream = batch_size is not None
    if stream and not isinstance(dataset, jax.Array):
        dataset = np.asarray(dataset)
    elif not stream:
        dataset = jnp.asarray(dataset)
    n, dim = dataset.shape

    with obs.entry_span("build", "ivf_pq", rows=int(n),
                        n_lists=int(params.n_lists), streamed=stream):
        # coarse centers train on a subsample (build.cuh: build_clusters)
        frac = float(params.kmeans_trainset_fraction)
        if 0 < frac < 1.0 and int(n * frac) >= int(params.n_lists):
            trainset = jnp.asarray(dataset[:: max(int(1.0 / frac), 1)])
        else:
            trainset = jnp.asarray(dataset)
        with obs.span("ivf_pq.build.train"):
            index = _quantizer_index(params, trainset, dim)
        if not params.add_data_on_build:
            return index
        with obs.span("ivf_pq.build.encode"):
            if not stream:
                return extend(index, dataset, jnp.arange(n, dtype=jnp.int32))
            return _stream_encode(params, index, dataset, n, int(batch_size))


def _quantizer_index(params: IndexParams, trainset, dim: int) -> Index:
    """Train all quantizers (coarse centers, rotation, PQ codebooks) on
    ``trainset`` and return the EMPTY index (reference ivf_pq_build.cuh
    steps: build_clusters, make_rotation_matrix:122, select_residuals:166,
    train_per_subset:395 / train_per_cluster:472)."""
    n_lists = int(params.n_lists)
    pq_dim = int(params.pq_dim) or _auto_pq_dim(dim)
    pq_len = -(-dim // pq_dim)
    rot_dim = pq_dim * pq_len
    K = 1 << int(params.pq_bits)
    key = jax.random.PRNGKey(0)

    kb = KMeansBalancedParams(
        n_clusters=n_lists,
        n_iters=int(params.kmeans_n_iters),
        metric=(
            DistanceType.InnerProduct
            if params.metric == DistanceType.InnerProduct
            else DistanceType.L2Expanded
        ),
        compute_dtype=str(params.kmeans_compute_dtype),
    )
    centers = kmeans_balanced.fit(kb, trainset)

    # 2. rotation (build.cuh:122 make_rotation_matrix)
    key, kr = jax.random.split(key)
    rotation = make_rotation_matrix(
        rot_dim, dim, bool(params.force_random_rotation), kr
    )
    centers_rot = dist_dot(centers, rotation.T)  # [C, rot_dim]

    # 3. residuals of the trainset (build.cuh:166 select_residuals)
    t32 = trainset.astype(jnp.float32)
    t_labels = kmeans_balanced.predict(kb, centers, trainset)
    t_rot = dist_dot(t32, rotation.T)
    t_res = (t_rot - centers_rot[t_labels]).reshape(-1, pq_dim, pq_len)

    # 4. PQ codebooks — batched device training, one compiled program for
    # all books (train_per_subset:395 / train_per_cluster:472 replacements;
    # the reference launches one balanced-kmeans per book)
    key, ks = jax.random.split(key)
    n_train = t_res.shape[0]
    if params.codebook_kind == codebook_gen.PER_SUBSPACE:
        # xs [p, S, len]: same row subsample for every subspace
        S = min(n_train, max(K * 32, 8192))
        sel = jax.random.choice(ks, n_train, (S,), replace=n_train < S)
        xs = jnp.transpose(t_res[sel], (1, 0, 2))          # [p, S, len]
        key, kt = jax.random.split(key)
        pq_centers = kmeans_balanced.build_clusters_batched(xs, K, 10, kt)
    else:
        # xs [C, S, len]: S rows per cluster, wrapped from each cluster's
        # contiguous run in label-sorted order; empty clusters fall back
        # to global rows. S caps the per-book subvector count (~16k) to
        # bound the gather.
        S = max(64, 16384 // pq_dim)
        flat = t_res.reshape(n_train, pq_dim * pq_len)
        order = jnp.argsort(t_labels)
        counts = jnp.bincount(t_labels, length=n_lists)
        starts = jnp.cumsum(counts) - counts
        s_idx = jnp.arange(S)
        pos = starts[:, None] + s_idx[None, :] % jnp.maximum(counts[:, None], 1)
        pos = jnp.where(counts[:, None] > 0, pos, s_idx[None, :] % n_train)
        rows = flat[order][pos]                             # [C, S, p*len]
        # a cluster codebook is trained on all its subvectors jointly
        xs = rows.reshape(n_lists, S * pq_dim, pq_len)
        key, kt = jax.random.split(key)
        pq_centers = kmeans_balanced.build_clusters_batched(xs, K, 10, kt)

    index = Index(
        centers=centers,
        centers_rot=centers_rot,
        rotation=rotation,
        pq_centers=pq_centers,
        codes=jnp.zeros(
            (n_lists, 0, packed_words(pq_dim, int(params.pq_bits))),
            jnp.uint32,
        ),
        indices=jnp.full((n_lists, 0), -1, jnp.int32),
        list_sizes=jnp.zeros((n_lists,), jnp.int32),
        rec_norms=jnp.zeros((n_lists, 0), jnp.float32),
        metric=params.metric,
        pq_dim_=pq_dim,
        metric_arg=params.metric_arg,
        codebook_kind=int(params.codebook_kind),
        pq_bits=int(params.pq_bits),
        cache_decoded=bool(params.cache_decoded),
        cache_dtype=str(params.cache_dtype),
    )
    return index


def _stream_encode(params: IndexParams, index: Index, dataset, n: int,
                   batch_size: int) -> Index:
    """Streaming encode over a materialized (host or device) dataset:
    fixed-shape batches keep one compiled encoder; only compressed codes
    accumulate on device. Device-resident datasets are sliced in place
    (no host round-trip through the BatchLoadIterator)."""
    n_lists = index.n_lists
    pq_dim = index.pq_dim
    parts_labels, parts_codes = [], []
    if isinstance(dataset, jax.Array):
        bs = int(batch_size)
        for off in range(0, n, bs):
            # dynamic_slice clamps an out-of-bounds start, producing the
            # shifted static-shape tail window the `keep` logic expects
            batch = jax.lax.dynamic_slice_in_dim(
                dataset, off, min(bs, n), axis=0,
            )
            lab, packed = encode(index, batch)
            if off + bs > n and n >= bs:
                # final window was shifted back to keep a static shape;
                # keep only the genuinely-new tail rows
                keep = n - off
                lab = lab[-keep:]
                packed = packed[-keep:]
            parts_labels.append(lab)
            parts_codes.append(packed)
    else:
        from raft_tpu.utils.batch import BatchLoadIterator

        for off, batch in BatchLoadIterator(dataset, int(batch_size),
                                            pad_to_full=True):
            lab, packed = encode(index, batch)
            parts_labels.append(lab)
            parts_codes.append(packed)
    labels = jnp.concatenate(parts_labels)[:n]
    packed = jnp.concatenate(parts_codes)[:n]
    ids = jnp.arange(n, dtype=jnp.int32)

    from raft_tpu.neighbors.ivf_flat import _aligned_cap

    counts = np.bincount(np.asarray(labels), minlength=n_lists)
    cap = _aligned_cap(int(counts.max()))
    codes_packed, indices, list_sizes = _pack_lists(
        packed, labels, ids, n_lists, cap
    )
    rec_norms = _rec_norms(
        codes_packed, index.pq_centers, int(params.codebook_kind),
        pq_dim, int(params.pq_bits),
    )
    return _attach_cache(dataclasses.replace(
        index,
        codes=codes_packed,
        indices=indices,
        list_sizes=list_sizes,
        rec_norms=rec_norms,
    ))


def _restore_quantizer(params: IndexParams, arrays, dim: int) -> Index:
    """Rebuild the empty quantizer Index from checkpointed arrays — the
    resume path must NOT retrain kmeans (bitwise identity of the resumed
    build is anchored on the exact quantizers the killed run used)."""
    n_lists = int(params.n_lists)
    pq_dim = int(params.pq_dim) or _auto_pq_dim(dim)
    return Index(
        centers=jnp.asarray(arrays["centers"]),
        centers_rot=jnp.asarray(arrays["centers_rot"]),
        rotation=jnp.asarray(arrays["rotation"]),
        pq_centers=jnp.asarray(arrays["pq_centers"]),
        codes=jnp.zeros(
            (n_lists, 0, packed_words(pq_dim, int(params.pq_bits))),
            jnp.uint32,
        ),
        indices=jnp.full((n_lists, 0), -1, jnp.int32),
        list_sizes=jnp.zeros((n_lists,), jnp.int32),
        rec_norms=jnp.zeros((n_lists, 0), jnp.float32),
        metric=params.metric,
        pq_dim_=pq_dim,
        metric_arg=params.metric_arg,
        codebook_kind=int(params.codebook_kind),
        pq_bits=int(params.pq_bits),
        cache_decoded=bool(params.cache_decoded),
        cache_dtype=str(params.cache_dtype),
    )


def _quant_arrays(index: Index, ts_scales) -> dict:
    out = {
        "centers": index.centers,
        "centers_rot": index.centers_rot,
        "rotation": index.rotation,
        "pq_centers": index.pq_centers,
    }
    if ts_scales is not None:
        out["ts_scales"] = ts_scales
    return out


def build_streamed(
    params: IndexParams,
    make_batches,
    n: int,
    dim: int,
    trainset,
    keep_codes: bool = True,
    cap_rows: Optional[int] = None,
    verbose: bool = False,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 8,
    resume: bool = False,
    token=None,
    pipeline_depth: Optional[int] = None,
) -> Index:
    """Build from a re-iterable stream of fixed-shape device batches —
    the out-of-core path for datasets too large for HBM or host RAM.
    Thin observed entry: opens the ``ivf_pq_streamed.build`` span and
    counts per-phase progress (``stream_chunks_total{stage=build.pass1|
    build.pass2}``) around :func:`_build_streamed_impl`, which carries
    the full memory-model / resilience contract docs."""
    with obs.entry_span("build", "ivf_pq_streamed", rows=int(n),
                        n_lists=int(params.n_lists), resume=bool(resume),
                        keep_codes=bool(keep_codes)):
        return _build_streamed_impl(
            params, make_batches, n, dim, trainset, keep_codes=keep_codes,
            cap_rows=cap_rows, verbose=verbose,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            resume=resume, token=token, pipeline_depth=pipeline_depth,
        )


def _build_streamed_impl(
    params: IndexParams,
    make_batches,
    n: int,
    dim: int,
    trainset,
    keep_codes: bool = True,
    cap_rows: Optional[int] = None,
    verbose: bool = False,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 8,
    resume: bool = False,
    token=None,
    pipeline_depth: Optional[int] = None,
) -> Index:
    """Build from a RE-ITERABLE stream of fixed-shape device batches —
    the path for datasets too large for HBM *or host RAM* (DEEP-100M at
    f32 is 38 GB; the reference handles this scale by mmap +
    batch_load_iterator, ann_utils.cuh:397 + dataset.hpp:45).

    ``make_batches()`` must return a fresh iterator of [batch, dim]
    device arrays each call (iterated twice: label-count pass, then
    encode+scatter pass); the final batch may be zero-padded — only the
    first ``n`` total rows are stored. ``trainset`` is the
    quantizer-training subsample (device array).

    Memory model: accumulators are written in place per batch via buffer
    donation, so peak HBM is the final index plus ONE batch's transients
    — the materialized [n, n_words] code slab of the `build(batch_size=)`
    path never exists. With ``keep_codes=False`` the packed codes
    themselves are dropped and only the quantized residual cache is
    stored — int8 decoded-PQ when it fits _CACHE_BUDGET, else the
    packed-int4 RAW-residual cache at 0.5 B/component (the DEEP-100M
    configuration: codes and any cache together exceed HBM at that
    scale); such an index searches via the fused cache path only.

    Resilience (docs/resilience.md): ``checkpoint_dir`` persists a
    per-chunk manifest + state blob (quantizers after training, labels
    through pass 1, the donated accumulators every ``checkpoint_every``
    batches of pass 2); ``resume=True`` restores the latest state —
    quantizers are NOT retrained, so the resumed build's output is
    bitwise identical to the uninterrupted one (resume with the same
    ``make_batches`` shape). Each blob is SELF-CONTAINED (quantizers +
    labels-so-far + accumulators) so a single file always suffices to
    resume — the cost is rewriting that state every save, so size
    ``checkpoint_every`` to the scale: at 100M rows each pass-2 save
    moves the full accumulator set; larger ``checkpoint_every`` trades
    replayed batches for checkpoint I/O. ``token`` (default: the calling
    thread's :class:`~raft_tpu.core.interruptible.Interruptible`) is
    checked at every batch so ``cancel()`` from another thread stops the
    hours-long job at the next chunk boundary.

    ``pipeline_depth`` (default: the ``pipeline_depth`` tuning budget)
    runs ``make_batches()`` on a graft-flow producer for each pass, so
    the caller's host read + device upload for batch N+1 overlaps batch
    N's label/scatter compute. Bitwise-invariant at any depth (the
    stream's items and order are unchanged); checkpoints still save
    only after a batch's scatter dispatched (a prefetched batch is
    never marked done), and a caller-side read error surfaces at the
    consuming batch, classified as usual.
    """
    from raft_tpu.core import pipeline as _pipeline
    from raft_tpu.neighbors.ivf_flat import _aligned_cap
    from raft_tpu import resilience
    from raft_tpu.core.interruptible import Interruptible
    from raft_tpu.resilience import faultinject

    import time as _time

    _t0 = _time.time()
    if token is None:
        token = Interruptible.get_token()
    ck = (resilience.StreamCheckpoint(checkpoint_dir)
          if checkpoint_dir else None)
    _every = max(int(checkpoint_every), 1)
    _fp = {
        "n": int(n), "dim": int(dim), "n_lists": int(params.n_lists),
        "pq_dim": int(params.pq_dim), "pq_bits": int(params.pq_bits),
        "codebook_kind": int(params.codebook_kind),
        "metric": int(params.metric), "keep_codes": bool(keep_codes),
        "cap_rows": cap_rows, "cache_dtype": str(params.cache_dtype),
    }
    _state = (ck.load(fingerprint=_fp)
              if (ck is not None and resume) else None)
    _phase = _state[0] if _state is not None else None
    _restored_scales = None
    if _state is not None:
        index = _restore_quantizer(params, _state[3], dim)
        if "ts_scales" in _state[3]:
            _restored_scales = jnp.asarray(_state[3]["ts_scales"])
    else:
        index = _quantizer_index(params, jnp.asarray(trainset), int(dim))
        jax.block_until_ready(index.pq_centers)
    kb_scales = KMeansBalancedParams(
        n_clusters=index.n_lists,
        metric=(
            DistanceType.InnerProduct
            if params.metric == DistanceType.InnerProduct
            else DistanceType.L2Expanded
        ),
    )
    ts_scales = _restored_scales
    # The padded i8 footprint is C*cap*rot with cap unknown until pass 1,
    # but it is bounded below by n*rot (C*cap >= n) and, when the caller
    # bounds list capacity, above by C*aligned_cap(cap_rows)*rot — enough
    # to decide BEFORE the expensive labeling pass whether the i4 scales
    # must be precomputed (at 100M scale a post-pass-1 "scales missing"
    # failure throws away hours of work; ADVICE r4).
    _cap_bound = (
        index.n_lists * _aligned_cap(int(cap_rows)) * index.rot_dim
        if cap_rows is not None else None
    )
    _i8_may_miss = (
        n * index.rot_dim > _CACHE_BUDGET // 2      # padding factor <= 2x
        or (_cap_bound is not None and _cap_bound > _CACHE_BUDGET)
        # unbounded cap + fatal-on-miss: any padding blowup must not
        # strike after pass 1, so be conservative and pay the scale pass
        or (cap_rows is None and not keep_codes
            and n * index.rot_dim > _CACHE_BUDGET // 8)
    )
    if str(params.cache_dtype) == "pq4":
        # the pq4 transposed-code cache has no streamed scatter; say so
        # up front instead of silently building without a cache
        raise ValueError(
            "cache_dtype='pq4' is not supported by build_streamed (the "
            "transposed-code cache is attached by the batch build); use "
            "cache_dtype='auto'/'i8'/'i4'/'rabitq' here"
        )
    i4_possible = (
        params.cache_decoded and index.rot_dim % 8 == 0
        and (str(params.cache_dtype) == "i4"
             or (str(params.cache_dtype) == "auto" and _i8_may_miss))
    )
    if not keep_codes:
        # keep_codes=False REQUIRES some cache; decide from the pre-pass-1
        # bounds (floor n*rot since C*cap >= n; cap_rows gives the padded
        # ceiling) whether any requested kind can possibly fit, and fail
        # now rather than after the hours-long labeling pass (ADVICE r4).
        # A cap_rows bound under budget legitimately truncates rows until
        # the cache fits — those builds proceed.
        cd = str(params.cache_dtype)
        i8_can = cd in ("auto", "i8") and (
            n * index.rot_dim <= _CACHE_BUDGET
            or (_cap_bound is not None and _cap_bound <= _CACHE_BUDGET)
        )
        i4_can = (
            cd in ("auto", "i4")
            and params.cache_decoded and index.rot_dim % 8 == 0
            and (n * index.rot_dim // 2 <= _CACHE_BUDGET
                 or (_cap_bound is not None
                     and _cap_bound // 2 <= _CACHE_BUDGET))
        )
        # rabitq: sign bits + 2 f32 scalars per row — feasible whenever
        # its (much smaller) footprint fits; streamed scatter mirrors i4
        rabitq_can = (
            cd in ("auto", "rabitq") and params.cache_decoded
            and n * (bits_words(index.rot_dim) * 4 + 8) <= _CACHE_BUDGET
        )
        if not (i8_can or i4_can or rabitq_can):
            raise ValueError(
                "keep_codes=False requires a residual cache but no "
                f"cache_dtype={cd!r} kind can fit _CACHE_BUDGET at "
                f"{n} rows x {index.rot_dim} rot dims (i4 additionally "
                "needs cache_decoded=True and rot_dim % 8 == 0)"
            )
        # An EXPLICIT cache_dtype passing only on the optimistic floor
        # n*rot (C*cap >= n) with no cap_rows ceiling under budget can
        # still miss after the hours-long labeling pass once list
        # padding inflates C*cap past n — and unlike "auto" it has no
        # i4 fallback to degrade to. Mirror _i8_may_miss's conservative
        # <= 2x padding factor and warn up front (ADVICE r5 finding 4).
        if cd != "auto":
            # per-kind padded-ceiling bytes (from the cap_rows element
            # bound) and optimistic row-floor bytes; rabitq's row cost
            # is its word+scalar bytes, not a rot fraction
            if cd == "rabitq":
                _rb = bits_words(index.rot_dim) * 4 + 8
                _ceil = (None if _cap_bound is None
                         else (_cap_bound // index.rot_dim) * _rb)
                floor = n * _rb
            else:
                _ceil = (None if _cap_bound is None
                         else (_cap_bound if cd == "i8"
                               else _cap_bound // 2))
                floor = (n * index.rot_dim if cd == "i8"
                         else n * index.rot_dim // 2)
        if cd != "auto" and not (_ceil is not None
                                 and _ceil <= _CACHE_BUDGET):
            if floor * 2 > _CACHE_BUDGET:
                import warnings

                warnings.warn(
                    f"build_streamed(keep_codes=False, cache_dtype={cd!r}): "
                    f"the padded {cd} cache fits _CACHE_BUDGET only if "
                    "list padding stays under "
                    f"{_CACHE_BUDGET / max(floor, 1):.2f}x the row floor — "
                    "the build may fail AFTER the labeling pass. Set "
                    "cap_rows to bound list capacity (or lower n_lists "
                    "imbalance) to make feasibility decidable up front.",
                    RuntimeWarning, stacklevel=2,
                )
                print("[build_streamed] WARNING: explicit "
                      f"cache_dtype={cd!r} feasibility depends on list "
                      "padding (floor*2 exceeds _CACHE_BUDGET); consider "
                      "cap_rows", flush=True)
        if i4_can and not i8_can:
            # only i4 can fit: make sure its scales actually get computed
            # (the auto heuristic above may not have triggered)
            i4_possible = True
    if i4_possible and ts_scales is None:
        # per-list int4 scales need the trainset — computed before it is
        # freed, used only if the budget later picks the i4 cache
        ts_scales = _trainset_i4_scales(jnp.asarray(trainset), index,
                                        kb_scales)
        jax.block_until_ready(ts_scales)
    trainset = None   # free before the accumulators go up (HBM headroom)
    if ck is not None and _state is None:
        ck.save("quant", 0, {}, _quant_arrays(index, ts_scales),
                fingerprint=_fp)
    if verbose:
        print(f"[build_streamed] quantizers: {_time.time()-_t0:.0f} s",
              flush=True)
    C = index.n_lists
    pq_dim = index.pq_dim
    pq_bits = int(params.pq_bits)
    nw = packed_words(pq_dim, pq_bits)
    rot = index.rot_dim
    kb = KMeansBalancedParams(
        n_clusters=C,
        metric=(
            DistanceType.InnerProduct
            if params.metric == DistanceType.InnerProduct
            else DistanceType.L2Expanded
        ),
    )

    # ---- pass 1: labels for every row (4 B/row; reused in pass 2) ----
    # throttle: async dispatch would otherwise enqueue EVERY generated
    # batch ahead of execution (batches alive until consumed -> tens of
    # GB of queued inputs); a tiny host fetch forces real completion
    if _phase == "pass2":
        # labels are in the pass-2 checkpoint (post padding-transform)
        labels_all = jnp.asarray(_state[3]["labels_all"])
    else:
        parts = []
        _p1_done = 0
        _p1_restored_rows = 0
        _p1_skipped = 0
        if _phase == "pass1":
            parts = [jnp.asarray(_state[3]["labels_parts"])]
            _p1_done = int(_state[2]["batches_done"])
            _p1_restored_rows = int(parts[0].shape[0])
        # graft-flow: the caller's host read + upload for batch N+1
        # runs on a producer while batch N labels (depth 0 = the old
        # inline loop); closed on every exit path via the context
        with _pipeline.Prefetcher(make_batches, depth=pipeline_depth,
                                  path="build.pass1", token=token) as _pf1:
            for bi, batch in enumerate(_pf1):
                if bi < _p1_done:
                    _p1_skipped += int(batch.shape[0])
                    continue             # resumed past this chunk
                if _p1_done and _p1_skipped != _p1_restored_rows:
                    # the new make_batches yields different shapes than
                    # the killed run's — skipping by batch INDEX would
                    # silently drop or duplicate rows
                    raise ValueError(
                        f"build_streamed resume misalignment: checkpoint "
                        f"covers {_p1_restored_rows} pass-1 rows in "
                        f"{_p1_done} batches but the first {_p1_done} "
                        f"batches of this run hold {_p1_skipped} rows; "
                        "resume with the make_batches shape the "
                        "checkpoint was written at"
                    )
                token.check()
                faultinject.check(stage="build.pass1", chunk=bi)
                obs.counter("stream_chunks_total", stage="build.pass1")
                parts.append(
                    kmeans_balanced.predict(kb, index.centers, batch))
                if bi % 8 == 7:
                    np.asarray(parts[-1][0])
                if ck is not None and (bi + 1) % _every == 0 \
                        and bi + 1 > _p1_done:
                    ck.save(
                        "pass1", bi, {"batches_done": bi + 1},
                        dict(_quant_arrays(index, ts_scales),
                             labels_parts=jnp.concatenate(parts)),
                        fingerprint=_fp,
                    )
        if _p1_done and _p1_skipped != _p1_restored_rows:
            raise ValueError(
                "build_streamed resume misalignment: the stream ended "
                f"inside the resumed prefix ({_p1_skipped} rows skipped "
                f"vs {_p1_restored_rows} checkpointed); resume with the "
                "make_batches shape the checkpoint was written at"
            )
        labels_all = jnp.concatenate(parts)
        del parts
        total = labels_all.shape[0]
        labels_all = jnp.where(
            jnp.arange(total) < n, labels_all, C   # padding rows -> dropped
        ).astype(jnp.int32)
    counts = jnp.zeros((C + 1,), jnp.int32).at[labels_all].add(1)[:C]
    cap = _aligned_cap(int(counts.max()))
    if cap_rows is not None and cap > cap_rows:
        # bounded list capacity: overflow rows of outlier lists are
        # DROPPED (the accumulator's slot bound), trading a small stored
        # fraction for an HBM-sized index — callers see the truncation in
        # list_sizes.sum(); padding-vs-max-list imbalance at 100M scale
        # otherwise inflates the codes array past HBM
        cap = _aligned_cap(int(cap_rows))
    if verbose:
        # graft-lint: allow-host-sync build verbose-path truncation report
        dropped = int(jnp.maximum(counts - cap, 0).sum())
        try:
            st = jax.devices()[0].memory_stats()
            mem = f" hbm_in_use={st.get('bytes_in_use', 0)/2**30:.2f}G"
        except Exception:  # noqa: BLE001  # graft-lint: allow-unclassified-swallow verbose-only memory_stats probe; absence of stats is not a fault
            mem = ""
        print(f"[build_streamed] pass1 labels: {_time.time()-_t0:.0f} s "
              f"cap={cap} dropped={dropped}{mem}", flush=True)

    cache_kind = _cache_kind_for(
        bool(params.cache_decoded), str(params.cache_dtype), C, cap, rot
    ) or "none"
    if not keep_codes and cache_kind == "none":
        raise ValueError(
            "keep_codes=False requires the decoded-residual cache "
            "(cache_decoded=True and the cache within _CACHE_BUDGET)"
        )
    if cache_kind == "i4":
        if ts_scales is None:
            # auto picked i4 only because list-padding inflated the i8
            # footprint past budget while n*rot_dim alone looked safe —
            # the trainset (and its scales) are already gone. Degrade
            # loudly rather than silently mis-scale.
            print("[build_streamed] WARNING: i4 cache wanted but per-list "
                  "scales were not precomputed (borderline auto budget); "
                  "building without a cache. Set cache_dtype='i4' to force "
                  "eager scale computation.", flush=True)
            cache_kind = "none"
            if not keep_codes:
                raise ValueError(
                    "keep_codes=False needs the i4 cache; pass "
                    "cache_dtype='i4' explicitly"
                )
        scale = ts_scales                                  # [C, rot]
    if cache_kind != "i4":
        scale = jnp.maximum(jnp.max(jnp.abs(index.pq_centers)), 1e-30) / 127.0
    nw4 = rot // 8
    nwb = bits_words(rot)

    # ---- pass 2: encode + donated scatter into the final layout ------
    # accumulators stay FLAT [C*cap, ...] through the loop: a 2-D-indexed
    # row scatter on [C, cap, ...] makes XLA relayout-copy the whole
    # multi-GB operand per call, while the 1-D row scatter aliases the
    # donated buffer; the final 3-D view is a donated in-jit reshape
    # (bitcast). The int4 cache accumulates TRANSPOSED as [C*nw4, cap]
    # to match the fused kernel's dense block layout — its scatter is
    # per-element (nw4 words per row) with 2-D (row, col) indices, which
    # keep every coordinate under int32 where a flat element index
    # overflows at 100M scale.
    want_qnorms = cache_kind in ("i4", "rabitq") and keep_codes
    want_fac = cache_kind == "rabitq"
    if _phase == "pass2":
        # restored accumulators ONLY — allocating the zero set first
        # would double peak HBM exactly when a resume is memory-tight
        _a = _state[3]
        acc_codes = jnp.asarray(_a["acc_codes"])
        acc_cache = jnp.asarray(_a["acc_cache"])
        acc_norms = jnp.asarray(_a["acc_norms"])
        acc_qnorms = jnp.asarray(_a["acc_qnorms"])
        acc_fac = (jnp.asarray(_a["acc_fac"]) if "acc_fac" in _a
                   else jnp.zeros((0,), jnp.float32))
        acc_ids = jnp.asarray(_a["acc_ids"])
        fill = jnp.asarray(_a["fill"])
        off = int(_state[2]["off"])
        nbatch = int(_state[2]["nbatch"])
    else:
        acc_codes = jnp.zeros((C * cap, nw if keep_codes else 0),
                              jnp.uint32)
        if cache_kind == "i4":
            acc_cache = jnp.zeros((C * nw4, cap), jnp.uint32)
        elif cache_kind == "rabitq":
            # transposed sign-bit accumulator (same dense layout + 2-D
            # scatter coordinates as the i4 cache, 4x narrower)
            acc_cache = jnp.zeros((C * nwb, cap), jnp.uint32)
        else:
            acc_cache = jnp.zeros(
                (C * cap, rot if cache_kind == "i8" else 0), jnp.int8
            )
        acc_qnorms = jnp.zeros((C * cap if want_qnorms else 0,),
                               jnp.float32)
        acc_fac = jnp.zeros((C * cap if want_fac else 0,), jnp.float32)
        acc_norms = jnp.zeros((C * cap,), jnp.float32)
        acc_ids = jnp.full((C * cap,), -1, jnp.int32)
        fill = jnp.zeros((C,), jnp.int32)
        off = 0
        nbatch = 0
    _p2_done = nbatch
    _p2_skipped = 0
    with _pipeline.Prefetcher(make_batches, depth=pipeline_depth,
                              path="build.pass2", token=token) as _pf2:
        for bi, batch in enumerate(_pf2):
            if bi < _p2_done:
                _p2_skipped += int(batch.shape[0])
                continue                 # resumed past this chunk
            if bi == _p2_done and _p2_done and _p2_skipped != off:
                # index-based skipping only works when the new stream's
                # batch shapes match the killed run's (off is the
                # row-exact encode position the checkpoint restored)
                raise ValueError(
                    f"build_streamed resume misalignment: checkpoint "
                    f"encoded {off} rows in {_p2_done} batches but the "
                    f"first {_p2_done} batches of this run hold "
                    f"{_p2_skipped} rows; resume with the make_batches "
                    "shape the checkpoint was written at"
                )
            token.check()
            faultinject.check(stage="build.pass2", chunk=bi)
            obs.counter("stream_chunks_total", stage="build.pass2")
            bs = batch.shape[0]
            lab = jax.lax.dynamic_slice_in_dim(labels_all, off, bs)
            (acc_codes, acc_cache, acc_norms, acc_qnorms, acc_fac,
             acc_ids, fill) = (
                _scatter_encode_batch(
                    acc_codes, acc_cache, acc_norms, acc_qnorms, acc_fac,
                    acc_ids, fill,
                    batch, lab, jnp.int32(off), scale,
                    index.centers_rot, index.rotation, index.pq_centers,
                    C, cap, int(index.codebook_kind), pq_dim, pq_bits,
                    keep_codes, cache_kind,
                )
            )
            nbatch += 1
            if nbatch % 4 == 0:
                np.asarray(fill[0])    # throttle the async queue (above)
            if verbose and nbatch == 1:
                np.asarray(fill[0])
                print("[build_streamed] first scatter ok", flush=True)
            off += bs
            if ck is not None and nbatch % _every == 0 \
                    and nbatch > _p2_done:
                ck.save(
                    "pass2", nbatch, {"off": off, "nbatch": nbatch},
                    dict(_quant_arrays(index, ts_scales),
                         labels_all=labels_all, acc_codes=acc_codes,
                         acc_cache=acc_cache, acc_norms=acc_norms,
                         acc_qnorms=acc_qnorms, acc_fac=acc_fac,
                         acc_ids=acc_ids, fill=fill),
                    fingerprint=_fp,
                )

    if _p2_done and nbatch == _p2_done and _p2_skipped != off:
        raise ValueError(
            "build_streamed resume misalignment: the stream ended inside "
            f"the resumed prefix ({_p2_skipped} rows skipped vs {off} "
            "checkpointed); resume with the make_batches shape the "
            "checkpoint was written at"
        )
    # the [C, cap, nw] native TPU layout is transposed relative to the
    # flat bytes (small minor dims get split/packed), so materializing it
    # costs a full-array relayout copy — fine at GB scale, impossible at
    # 100M scale. Big code arrays stay FLAT [C*cap, nw]; every consumer
    # (search, extend, serialize) handles both forms.
    big_codes = keep_codes and C * cap * nw * 4 > (2 << 30)
    if cache_kind == "i4":
        recon_cache = _donated_reshape3(acc_cache, C, nw4)
    elif cache_kind == "rabitq":
        recon_cache = _donated_reshape3(acc_cache, C, nwb)
    elif cache_kind == "i8":
        recon_cache = _donated_reshape3(acc_cache, C, cap)
    else:
        recon_cache = None
    out = dataclasses.replace(
        index,
        codes=(acc_codes if big_codes
               else _donated_reshape3(acc_codes, C, cap)),
        indices=_donated_reshape2(acc_ids, C, cap),
        list_sizes=jnp.minimum(fill, cap),
        rec_norms=_donated_reshape2(acc_norms, C, cap),
        recon_cache=recon_cache,
        recon_scale=float(scale) if cache_kind == "i8" else 1.0,
        cache_scales=scale if cache_kind == "i4" else None,
        cache_qnorms=(_donated_reshape2(acc_qnorms, C, cap)
                      if want_qnorms else None),
        cache_fac=(_donated_reshape2(acc_fac, C, cap)
                   if want_fac else None),
    )
    return out


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(1, 2))
def _donated_reshape3(a, C: int, cap: int):
    """Leading-dim split reshape that ALIASES the (donated) input — the
    op-by-op equivalent copies the multi-GB accumulator."""
    return a.reshape(C, cap, -1)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(1, 2))
def _donated_reshape2(a, C: int, cap: int):
    return a.reshape(C, cap)


@functools.partial(
    jax.jit,
    donate_argnums=(0, 1, 2, 3, 4, 5, 6),
    static_argnums=(14, 15, 16, 17, 18, 19, 20),
)
def _scatter_encode_batch(
    acc_codes, acc_cache, acc_norms, acc_qnorms, acc_fac, acc_ids, fill,
    batch, labels, id0, scale, centers_rot, rotation, pq_centers,
    C: int, cap: int, codebook_kind: int, pq_dim: int, pq_bits: int,
    keep_codes: bool, cache_kind: str,
):
    """Encode one batch and scatter rows into their final list slots
    (donated accumulators -> in-place updates; the _pack_lists slotting
    logic, offset by the running per-list fill). Accumulators are FLAT
    [C*cap, ...]: 1-D row scatters alias the donated buffers, where
    2-D-indexed scatters forced an 8.5 GB relayout copy per call."""
    bs, dim = batch.shape
    pq_len = rotation.shape[0] // pq_dim
    K = pq_centers.shape[1]
    x32 = batch.astype(jnp.float32)
    x_rot = dist_dot(x32, rotation.T)
    res = (x_rot - centers_rot[jnp.minimum(labels, C - 1)]).reshape(
        bs, pq_dim, pq_len
    )
    lab_safe = jnp.minimum(labels, C - 1)
    if codebook_kind == codebook_gen.PER_SUBSPACE:
        codes = _encode_subspace(res, pq_centers, K)
        flat_idx = codes.astype(jnp.int32) + (
            jnp.arange(pq_dim, dtype=jnp.int32) * K
        )
    else:
        codes = _encode_per_cluster(res, lab_safe, pq_centers)
        flat_idx = codes.astype(jnp.int32) + (lab_safe * K)[:, None]
    # ||recon||^2 = sum_s ||book_s[code_s]||^2 — a norm-TABLE gather whose
    # minor dim is pq_dim, not pq_len (a [bs, p, len] decode transient is
    # lane-padded len -> 128 by the TPU layout: 64x memory at len=2)
    book_norms = jnp.sum(
        pq_centers.astype(jnp.float32) ** 2, axis=-1
    ).reshape(-1)
    rnorm = jnp.sum(jnp.take(book_norms, flat_idx, axis=0), axis=-1)

    ids_global = id0 + jnp.arange(bs, dtype=jnp.int32)
    # slot assignment: stable sort by label, rank within the batch run,
    # offset by the accumulated fill (labels == C drop out of bounds)
    order = jnp.argsort(labels, stable=True)
    sl = labels[order]
    counts_b = jnp.zeros((C + 1,), jnp.int32).at[labels].add(1)[:C]
    starts = jnp.cumsum(counts_b) - counts_b
    sl_safe = jnp.minimum(sl, C - 1)
    pos = (jnp.arange(bs) - starts[sl_safe]) + fill[sl_safe]
    # dropped rows (label C padding / list overflow): out-of-bounds slots
    # make the scatter update drop
    slot = jnp.where((sl < C) & (pos < cap), sl * cap + pos, C * cap)

    if keep_codes:
        packed = pack_codes(codes, pq_bits)
        acc_codes = acc_codes.at[slot].set(packed[order])
    if cache_kind == "i4":
        # the int4 cache quantizes the RAW rotated residual (not the PQ
        # reconstruction): one quantization error source instead of two —
        # measured 0.917 vs 0.895 recall on DEEP-like data at the same
        # byte budget. The stored norm is the dequantized vector's (what
        # search scores against).
        raw = res.reshape(bs, -1)                          # [bs, rot]
        q, qn = _quant_pack_i4(raw, scale[lab_safe])       # [bs, nw4]
        # transposed element scatter into the [C*nw4, cap] accumulator:
        # word w of the row assigned to (list l, slot pos) lands at
        # (l*nw4 + w, pos). 2-D indices keep every coordinate < 2^31 —
        # a flat 1-D index (l*nw4 + w)*cap + pos OVERFLOWS int32 at the
        # DEEP-100M target shape (32768*16*4352 = 2.28e9 elements)
        nw4 = q.shape[1]
        qs = q[order]
        l_idx = slot // cap
        pos_idx = slot % cap
        row = l_idx[:, None] * nw4 + jnp.arange(nw4, dtype=jnp.int32)[None, :]
        row = jnp.where(slot[:, None] >= C * cap, C * nw4, row)  # drop
        col = jnp.broadcast_to(pos_idx[:, None], row.shape)
        acc_cache = acc_cache.at[row.reshape(-1), col.reshape(-1)].set(
            qs.reshape(-1)
        )
        if keep_codes:
            # codes remain the decode path's source of truth: keep the PQ
            # reconstruction norms in rec_norms and stash the dequantized
            # norms separately for the cache scan
            acc_qnorms = acc_qnorms.at[slot].set(qn[order])
        else:
            rnorm = qn
    elif cache_kind == "rabitq":
        # sign bits of the RAW rotated residual (not the PQ recon —
        # same fidelity choice as the i4 cache above) + the estimator's
        # per-row scalars: fac = ||r||²/||r||₁ and the TRUE ||r||².
        # Needs NO trainset scale pass at all — RaBitQ's build-side win.
        # Same transposed [C*nwb, cap] element scatter as i4 (2-D
        # coordinates keep every index under int32 at 100M scale).
        raw = res.reshape(bs, -1)                          # [bs, rot]
        q, fac_b, qn = _quant_pack_rabitq(raw)             # [bs, nwb]
        nwb = q.shape[1]
        qs = q[order]
        l_idx = slot // cap
        pos_idx = slot % cap
        row = l_idx[:, None] * nwb + jnp.arange(nwb, dtype=jnp.int32)[None, :]
        row = jnp.where(slot[:, None] >= C * cap, C * nwb, row)  # drop
        col = jnp.broadcast_to(pos_idx[:, None], row.shape)
        acc_cache = acc_cache.at[row.reshape(-1), col.reshape(-1)].set(
            qs.reshape(-1)
        )
        acc_fac = acc_fac.at[slot].set(fac_b[order])
        if keep_codes:
            acc_qnorms = acc_qnorms.at[slot].set(qn[order])
        else:
            rnorm = qn
    elif cache_kind == "i8":
        # full decode, chunked: the [chunk, p, len] transient is
        # lane-padded len -> 128, so chunks stay small
        chunk = 1 << 13
        npad = -(-bs // chunk) * chunk
        cpad = jnp.pad(codes, ((0, npad - bs), (0, 0)))
        lpad = jnp.pad(lab_safe, (0, npad - bs))

        def dec(inp):
            cb, lb = inp
            if codebook_kind == codebook_gen.PER_SUBSPACE:
                r = _decode_gather(cb, pq_centers, codebook_kind)
            else:
                r = _decode_gather(cb, pq_centers, codebook_kind, lb)
            return jnp.clip(jnp.round(r / scale), -127, 127).astype(jnp.int8)

        q = jax.lax.map(
            dec,
            (cpad.reshape(npad // chunk, chunk, pq_dim),
             lpad.reshape(npad // chunk, chunk)),
        ).reshape(npad, -1)[:bs]
        acc_cache = acc_cache.at[slot].set(q[order])
    acc_norms = acc_norms.at[slot].set(rnorm[order])
    acc_ids = acc_ids.at[slot].set(ids_global[order])
    fill = fill + counts_b
    # pin the 2-D accumulators to row-major: XLA's scatter layout
    # assignment otherwise drifts them to a transposed layout, which
    # turns the final [C, cap, ...] view into an 8.5 GB relayout copy
    # (row-major -> the view is a pure bitcast)
    from jax.experimental.layout import Layout, with_layout_constraint

    acc_codes = with_layout_constraint(acc_codes, Layout((0, 1)))
    # both cache accumulators are 2-D with a leading-split final
    # reshape ([C*cap, rot] -> [C, cap, rot]; [C*nw4, cap] ->
    # [C, nw4, cap]), so the row-major pin keeps that view a bitcast
    acc_cache = with_layout_constraint(acc_cache, Layout((0, 1)))
    return (acc_codes, acc_cache, acc_norms, acc_qnorms, acc_fac, acc_ids,
            fill)


def encode(index: Index, vectors) -> Tuple[jax.Array, jax.Array]:
    """Label + PQ-encode vectors against an index's quantizers (reference
    process_and_fill_codes:1322, minus the list scatter). Returns
    (labels [n] int32, packed codes [n, n_words] uint32)."""
    vectors = jnp.asarray(vectors)
    kb = KMeansBalancedParams(
        n_clusters=index.n_lists,
        metric=(
            DistanceType.InnerProduct
            if index.metric == DistanceType.InnerProduct
            else DistanceType.L2Expanded
        ),
    )
    labels = kmeans_balanced.predict(kb, index.centers, vectors)

    # encode: rotated residual → per-subspace nearest codebook entry
    x32 = vectors.astype(jnp.float32)
    x_rot = dist_dot(x32, index.rotation.T)
    res = (x_rot - index.centers_rot[labels]).reshape(
        -1, index.pq_dim, index.pq_len
    )
    if index.codebook_kind == codebook_gen.PER_SUBSPACE:
        codes = _encode_subspace(res, index.pq_centers, index.pq_book_size)
    else:
        codes = _encode_per_cluster(res, labels, index.pq_centers)
    return labels, pack_codes(codes, index.pq_bits)


def _encode_per_cluster(res, labels, pq_centers, block: int = 1 << 14):
    """PER_CLUSTER encode, row-blocked like _encode_subspace (the book
    gather [n, K, len] plus the [n, p, K] distances OOM unblocked)."""
    n, p, plen = res.shape

    def one_block(inp):
        res_b, lab_b = inp
        books = pq_centers[lab_b]  # [block, K, len]
        dots = jnp.einsum(
            "npl,nkl->npk", res_b, books,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        rn = jnp.sum(res_b * res_b, axis=2)[:, :, None]
        cn = jnp.sum(books * books, axis=2)[:, None, :]
        return argmin_exact(rn - 2.0 * dots + cn, axis=2).astype(jnp.uint8)

    if n <= block:
        return one_block((res, labels))
    npad = -(-n // block) * block
    res_p = jnp.pad(res, ((0, npad - n), (0, 0), (0, 0)))
    lab_p = jnp.pad(labels, (0, npad - n))
    out = jax.lax.map(
        one_block,
        (res_p.reshape(npad // block, block, p, plen),
         lab_p.reshape(npad // block, block)),
    )
    return out.reshape(npad, p)[:n]


def extend(index: Index, new_vectors, new_ids=None) -> Index:
    """Encode + add vectors (reference ivf_pq_build.cuh extend /
    process_and_fill_codes:1322)."""
    if index.codes.shape[-1] == 0 and index.size > 0:
        raise ValueError(
            "cache-only index (built with keep_codes=False) cannot be "
            "extended — the packed codes were dropped at build"
        )
    new_vectors = jnp.asarray(new_vectors)
    n_new = new_vectors.shape[0]
    if new_ids is None:
        new_ids = jnp.arange(index.size, index.size + n_new, dtype=jnp.int32)
    new_ids = jnp.asarray(new_ids).astype(jnp.int32)

    labels, new_packed = encode(index, new_vectors)

    # merge with existing lists and repack, all on device: old padding rows
    # get the out-of-range label n_lists so _pack_lists drops them (no
    # host round-trip)
    C = index.n_lists
    nw = packed_words(index.pq_dim, index.pq_bits)
    old_cap = index.indices.shape[1]
    if old_cap > 0 and index.size > 0:
        old_codes = index.codes.reshape(-1, nw)
        old_ids = index.indices.reshape(-1)
        old_labels = jnp.where(
            old_ids >= 0,
            jnp.repeat(jnp.arange(C, dtype=jnp.int32), old_cap),
            jnp.int32(C),
        )
        codes_all = jnp.concatenate([old_codes, new_packed], axis=0)
        labels_all = jnp.concatenate([old_labels, labels])
        ids_all = jnp.concatenate([old_ids, new_ids])
    else:
        codes_all, labels_all, ids_all = new_packed, labels, new_ids

    counts = np.asarray(index.list_sizes) + np.bincount(
        np.asarray(labels), minlength=C
    )
    from raft_tpu.neighbors.ivf_flat import _aligned_cap

    cap = _aligned_cap(int(counts.max()))
    codes_packed, indices, list_sizes = _pack_lists(
        codes_all, labels_all, ids_all, C, cap
    )

    rec_norms = _rec_norms(
        codes_packed, index.pq_centers, index.codebook_kind,
        index.pq_dim, index.pq_bits,
    )

    return _attach_cache(dataclasses.replace(
        index,
        codes=codes_packed,
        indices=indices,
        list_sizes=list_sizes,
        rec_norms=rec_norms,
    ))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _rec_norms(codes_packed, pq_centers, codebook_kind: int, pq_dim: int,
               pq_bits: int):
    """||reconstructed residual||^2 per stored vector, scanned over lists
    so the unpacked [cap, pq_dim] codes never materialize for the whole
    index at once."""
    C = codes_packed.shape[0]

    def body(_, inp):
        blk, lid = inp                                     # [cap, nw], []
        u = unpack_codes(blk, pq_dim, pq_bits)             # [cap, p]
        if codebook_kind == codebook_gen.PER_SUBSPACE:
            recon = _decode_gather(u, pq_centers, codebook_kind)
        else:
            recon = _decode_gather(u, pq_centers, codebook_kind,
                                   jnp.full((u.shape[0],), lid))
        return None, jnp.sum(recon * recon, axis=-1)

    _, norms = jax.lax.scan(
        body, None, (codes_packed, jnp.arange(C, dtype=jnp.int32))
    )
    return norms


# ---------------------------------------------------------------------------
# int4 reconstruction cache (the cache-doesn't-fit regime)
# ---------------------------------------------------------------------------
#
# At 100M scale the int8 cache (1 B/component) cannot share HBM with the
# packed codes, which forced round 3's DEEP-100M search onto the slow
# decode-gather path (195 QPS). The int4 cache halves that to 0.5
# B/component — for pq_len=2 exactly the size of the codes themselves —
# so a cache-only (keep_codes=False) index fits 100M x rot128 in ~9 GB
# and keeps the fused one-matmul-per-block scan. This is the TPU answer
# to the reference's in-register compressed-code scoring
# (ivf_pq_compute_similarity-inl.cuh:164-185): the "compressed form" is
# re-quantized reconstructions rather than raw PQ codes, because TPUs
# score via the MXU (which wants dense operands) instead of per-lane
# shared-memory LUT gathers.
#
# Layout is TRANSPOSED [C, rot//8, cap]: components-packed-in-words on
# sublanes, rows on lanes — dense under the (8, 128) Mosaic tiling
# (row-major [cap, rot//8] would lane-pad the narrow word dim 8x).
# Per-component scales come from the codebook itself (every reconstructed
# component IS a codebook entry), so no data pass is needed.


def _quant_pack_i4(recon, scales):
    """[..., rot] f32 -> ([..., rot//8] u32 packed signed nibbles,
    [...] f32 dequantized-vector norms)."""
    q = jnp.clip(jnp.round(recon / scales), -8, 7).astype(jnp.int32)
    deq = q.astype(jnp.float32) * scales
    qnorm = jnp.sum(deq * deq, axis=-1)
    nib = (q & 0xF).astype(jnp.uint32)
    nib = nib.reshape(*q.shape[:-1], q.shape[-1] // 8, 8)
    shifts = (jnp.arange(8, dtype=jnp.uint32) * 4)
    return jnp.sum(nib << shifts, axis=-1, dtype=jnp.uint32), qnorm


def _trainset_i4_scales(trainset, index: "Index", kb) -> jax.Array:
    """Per-list int4 scales [C, rot] estimated from the quantizer-training
    subsample's residual ranges (the streamed build must know scales
    before its single encode+scatter pass; out-of-sample rows beyond the
    1.15x headroom saturate at +/-8, which is rare and bounded)."""
    C, rot = index.n_lists, index.rot_dim
    chunk = min(1 << 19, trainset.shape[0])
    n = trainset.shape[0]
    npad = -(-n // chunk) * chunk
    ts = jnp.asarray(trainset)
    # pad the tail chunk by wrapping real rows (zero-padding would inject
    # |0 - c_rot| phantom residuals that inflate one list's scale)
    tp = jnp.concatenate([ts, ts[: npad - n]]) if npad > n else ts
    tchunks = tp.reshape(npad // chunk, chunk, -1)

    def res_of(tb):
        lab = kmeans_balanced.predict(kb, index.centers, tb)
        t_rot = dist_dot(tb.astype(jnp.float32), index.rotation.T)
        return lab, t_rot - index.centers_rot[lab]

    def max_body(lmax, tb):
        lab, res = res_of(tb)
        return lmax.at[lab].max(jnp.abs(res)), None

    lmax0 = jnp.zeros((C, rot), jnp.float32)
    lmax, _ = jax.lax.scan(max_body, lmax0, tchunks)
    # thin/empty lists fall back to the global max
    gmax = jnp.max(lmax, axis=0)
    lmax = jnp.where(lmax > 0, lmax, gmax[None, :])
    base = jnp.maximum(lmax * 1.1, 1e-30) / 7.0

    # second pass: per-list MSE-optimal clip multiplier on the trainset
    # residuals (see _pick_clip_scale)
    M = len(_CLIP_CANDIDATES)

    def err_body(errs, tb):
        lab, res = res_of(tb)
        s_rows = base[lab]                                  # [chunk, rot]
        for mi, m in enumerate(_CLIP_CANDIDATES):
            s = s_rows * m
            q = jnp.clip(jnp.round(res / s), -8, 7)
            e = jnp.sum((q * s - res) ** 2, axis=-1)        # [chunk]
            errs = errs.at[lab, mi].add(e)
        return errs, None

    errs, _ = jax.lax.scan(err_body, jnp.zeros((C, M), jnp.float32), tchunks)
    m_best = jnp.asarray(_CLIP_CANDIDATES, jnp.float32)[
        argmin_exact(errs, axis=1)
    ]                                                       # [C]
    return base * m_best[:, None]


def unpack_i4(packed):
    """[..., nw] u32 -> [..., nw*8] f32 raw values in [-8, 7] (callers
    apply scales). XLA analog of the kernel's sign-extending decode."""
    w = packed.astype(jnp.int32)
    j = jnp.arange(8, dtype=jnp.int32)
    vals = (w[..., None] << (28 - 4 * j)) >> 28          # [..., nw, 8]
    return vals.reshape(*packed.shape[:-1], -1).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _recon_cache_scan_i4(codes_packed, indices, pq_centers,
                         codebook_kind: int, pq_dim: int, pq_bits: int):
    """Packed-int4 decoded-residual cache ([C, rot//8, cap] u32 transposed)
    + PER-LIST per-component scales [C, rot] + dequantized norms, scanned
    over lists. Per-list scales measured ~0.14 recall better than global
    max-based scales on adversarial blob sets (list residual ranges vary
    widely when coarse clusters differ in spread)."""
    C = codes_packed.shape[0]
    lids = jnp.arange(C, dtype=jnp.int32)

    def decode(blk, lid):
        u = unpack_codes(blk, pq_dim, pq_bits)             # [cap, p]
        if codebook_kind == codebook_gen.PER_SUBSPACE:
            return _decode_gather(u, pq_centers, codebook_kind)
        return _decode_gather(u, pq_centers, codebook_kind,
                              jnp.full((u.shape[0],), lid))

    def max_body(_, inp):
        blk, ids_row, lid = inp
        recon = decode(blk, lid)                           # [cap, rot]
        m = jnp.max(jnp.where(ids_row[:, None] >= 0, jnp.abs(recon), 0.0),
                    axis=0)
        return None, m

    _, list_max = jax.lax.scan(max_body, None, (codes_packed, indices, lids))
    base = jnp.maximum(list_max, 1e-30) / 7.0              # [C, rot]

    def body(_, inp):
        blk, ids_row, lid = inp                            # [cap, nw], []
        recon = decode(blk, lid)
        ok = (ids_row >= 0)[:, None]
        # per-list clip multiplier: a clipped quantizer (scale < max/7)
        # often beats full range coverage in MSE — pick per list
        s_best = _pick_clip_scale(recon, base[lid], ok)
        packed, qnorm = _quant_pack_i4(recon, s_best)      # [cap, nw4]
        return None, (packed.T, qnorm, s_best)

    _, (cache_t, qnorms, scales) = jax.lax.scan(
        body, None, (codes_packed, indices, lids)
    )
    return cache_t, scales, qnorms


_CLIP_CANDIDATES = (0.6, 0.7, 0.8, 0.9, 1.0)


def _pick_clip_scale(vals, base_scale, ok, qmax: int = 7):
    """Per-list MSE-optimal clip multiplier: quantize ``vals``
    [..., n, rot] (validity mask ``ok`` [..., n, 1]) at each candidate
    scale m * base_scale [..., rot] and keep, per leading batch entry,
    the m with least total squared error (measured: m=0.7 lifts
    DEEP-like int4 recall 0.882 -> 0.917 vs full-range m=1.0). The one
    clip-search implementation shared by the streamed scale pass, the
    decoded-cache scan, and attach_raw_residual_cache."""
    best_err = best_m = None
    for m in _CLIP_CANDIDATES:
        s = base_scale * m
        q = jnp.clip(jnp.round(vals / s[..., None, :]), -qmax - 1, qmax)
        err = jnp.sum(jnp.where(ok, (q * s[..., None, :] - vals) ** 2, 0.0),
                      axis=(-2, -1))
        if best_err is None:
            best_err, best_m = err, jnp.full_like(err, m)
        else:
            take = err < best_err
            best_err = jnp.minimum(err, best_err)
            best_m = jnp.where(take, m, best_m)
    return base_scale * best_m[..., None]


# ---------------------------------------------------------------------------
# rabitq sign-bit cache (the ~32x-compressed first-stage rung, ISSUE 11)
# ---------------------------------------------------------------------------
#
# IVF-RaBitQ (PAPERS.md) quantizes each rotated residual r to ONE sign
# bit per component plus two per-row f32 scalars, and recovers an
# UNBIASED estimate of <q, r> from them:
#
#     r̂ = fac · sign(r),   fac = ||r||² / ||r||₁
#     <q, r> ≈ <q, r̂> = fac · Σ_j sign(r_j) · q_j
#
# (<r̂, r> = ||r||² exactly — the collinearity-corrected projection; for
# incoherent directions, i.e. after a random rotation, the cross terms
# cancel in expectation). The L2 estimator then uses the TRUE stored
# norm, not ||r̂||²:  d²(q_res, r) ≈ ||q_res||² + ||r||² − 2·fac·S.
# Storage is sign bits packed 32-per-u32 lane word, TRANSPOSED to
# [C, ceil(rot/32), cap] like the i4 cache (components on sublanes, rows
# on lanes — Mosaic-dense); rot dims beyond the last full word are pad
# bits (decode −1, nulled by zero-padded queries). At 1 bit/dim this is
# ~32× less HBM per scanned row than f32 and 4× less than the i4 rung —
# the first-stage scan of the multi-stage rerank pipeline
# (search_refined), never a fidelity source on its own.


def bits_words(rot: int) -> int:
    """Sign-bit words per row: ceil(rot / 32) (partial last word ok)."""
    return -(-rot // 32)


def pack_sign_bits(vals) -> jax.Array:
    """[..., d] f32 -> [..., ceil(d/32)] u32 sign-bit words (bit j of
    word w set where vals[..., 32w + j] > 0; pad bits zero)."""
    d = vals.shape[-1]
    nwb = bits_words(d)
    pad = nwb * 32 - d
    b = (vals > 0).astype(jnp.uint32)
    if pad:
        b = jnp.concatenate(
            [b, jnp.zeros((*b.shape[:-1], pad), jnp.uint32)], axis=-1)
    b = b.reshape(*b.shape[:-1], nwb, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def unpack_sign_bits(packed, d: int) -> jax.Array:
    """[..., nw] u32 -> [..., d] f32 in {−1, +1} (pad bits dropped).
    XLA analog of the kernel's 2-op bit decode."""
    w = packed.astype(jnp.int32)
    j = jnp.arange(d, dtype=jnp.int32)
    words = jnp.take(w, j // 32, axis=-1)                # [..., d]
    bit = (words >> (j % 32)) & 1
    return (2 * bit - 1).astype(jnp.float32)


def _quant_pack_rabitq(res):
    """[..., rot] f32 residuals -> (packed [..., ceil(rot/32)] u32,
    fac [...] f32, norm2 [...] f32). All-zero rows (padding slots,
    exact-center residuals) get fac 0 — their estimated dot is 0."""
    norm2 = jnp.sum(res * res, axis=-1)
    l1 = jnp.sum(jnp.abs(res), axis=-1)
    fac = norm2 / jnp.maximum(l1, 1e-30)
    return pack_sign_bits(res), fac, norm2


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _rabitq_cache_scan(codes_packed, indices, pq_centers,
                       codebook_kind: int, pq_dim: int, pq_bits: int):
    """Sign-bit cache from the PQ codes, scanned over lists: binarize
    the DECODED reconstruction (the batch-build analog of the streamed
    path's raw-residual signs — same asymmetry the i4 cache has; the
    sign pattern survives PQ quantization far better than magnitudes
    do). Returns (cache_t [C, nw, cap] u32, fac [C, cap],
    qnorms [C, cap] — the reconstruction's true norms, what the
    estimator scores against). Padding slots (ids < 0) are zeroed."""
    C = codes_packed.shape[0]
    lids = jnp.arange(C, dtype=jnp.int32)

    def body(_, inp):
        blk, ids_row, lid = inp                          # [cap, nw], []
        u = unpack_codes(blk, pq_dim, pq_bits)           # [cap, p]
        if codebook_kind == codebook_gen.PER_SUBSPACE:
            recon = _decode_gather(u, pq_centers, codebook_kind)
        else:
            recon = _decode_gather(u, pq_centers, codebook_kind,
                                   jnp.full((u.shape[0],), lid))
        recon = jnp.where((ids_row >= 0)[:, None], recon, 0.0)
        packed, fac, n2 = _quant_pack_rabitq(recon)      # [cap, nw], ...
        return None, (packed.T, fac, n2)

    _, (cache_t, fac, qnorms) = jax.lax.scan(
        body, None, (codes_packed, indices, lids)
    )
    return cache_t, fac, qnorms


def scan_bytes_per_row(kind: str, rot: int, pq_dim: int = 0):
    """First-stage scan cost model, ONE home for bench + tests:
    returns ``(code_bytes, total_bytes)`` streamed per scanned row.

    ``code_bytes`` is the quantized payload alone — the
    rows-per-HBM-byte ladder figure (the convention behind the "~32×
    compressed" 1-bit claim; i4→rabitq is exactly 4× here when
    ``rot % 32 == 0``). ``total_bytes`` adds the per-row scalar
    sidecars and the 4-byte id/slot row the scan also streams — the
    honest roofline traffic (the rabitq ratio lands ~2.3–3.5× there
    because two f32 estimator scalars ride every 1-bit row)."""
    if kind == "rabitq":
        return bits_words(rot) * 4, bits_words(rot) * 4 + 12
    if kind == "i4":
        return rot // 2, rot // 2 + 8
    if kind == "i8":
        return rot, rot + 8
    if kind == "pq4":
        return pq_dim // 2, pq_dim // 2 + 8
    raise ValueError(f"unknown scan kind {kind!r}")


def attach_rabitq_cache(index: Index) -> Index:
    """Swap the index onto the rabitq rung: rebuild the sign-bit cache
    (+ fac/norm sidecars) from the packed codes, replacing whatever
    cache the index carried — the batch-path attach for A/B runs and
    for serving an existing index through the multi-stage pipeline
    without retraining quantizers."""
    if index.codes.ndim != 3 or index.codes.shape[-1] == 0:
        raise ValueError(
            "attach_rabitq_cache needs the packed codes (cache-only "
            "indexes already carry their final cache)")
    cache_t, fac, qnorms = _rabitq_cache_scan(
        index.codes, index.indices, index.pq_centers,
        index.codebook_kind, index.pq_dim, index.pq_bits,
    )
    return dataclasses.replace(
        index, recon_cache=cache_t, recon_scale=1.0,
        cache_scales=None, cache_qnorms=qnorms, cache_fac=fac,
    )


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _recon_cache_scan(codes_packed, pq_centers, codebook_kind: int,
                      pq_dim: int, pq_bits: int):
    """int8-quantized decoded residuals per stored vector ([C, cap,
    rot_dim]), scanned over lists. The dequant scale is bounded by the
    codebook itself (every reconstructed component IS a codebook entry),
    so no data pass is needed."""
    C = codes_packed.shape[0]
    scale = jnp.maximum(jnp.max(jnp.abs(pq_centers)), 1e-30) / 127.0

    def body(_, inp):
        blk, lid = inp                                     # [cap, nw], []
        u = unpack_codes(blk, pq_dim, pq_bits)             # [cap, p]
        if codebook_kind == codebook_gen.PER_SUBSPACE:
            recon = _decode_gather(u, pq_centers, codebook_kind)
        else:
            recon = _decode_gather(u, pq_centers, codebook_kind,
                                   jnp.full((u.shape[0],), lid))
        q = jnp.clip(jnp.round(recon / scale), -127, 127).astype(jnp.int8)
        return None, q

    _, cache = jax.lax.scan(
        body, None, (codes_packed, jnp.arange(C, dtype=jnp.int32))
    )
    return cache, scale


def attach_raw_residual_cache(index: Index, dataset,
                              block_lists: int = 64,
                              dtype: str = "i4") -> Index:
    """Attach a RAW rotated-residual cache (packed int4 at 0.5
    B/component or int8 at 1 B/component, both with per-list scales)
    built from the original dataset — the refine/scan fidelity source
    for in-core and sharded indexes (streamed keep_codes=False builds
    produce the identical i4 cache on the fly; this is the batch-path
    equivalent).

    The distinction matters: ``_attach_cache``'s kinds quantize the
    DECODED PQ reconstruction (fidelity = PQ, usable by the fused scan
    but worthless as a refine source — re-ranking PQ scores with PQ
    fidelity gains nothing), while this cache quantizes the raw rotated
    residual. dtype picks the rung: "i4" matches the PQ bytes (0.5
    B/dim) and "i8" doubles them for ~16x lower quantization error —
    the DEEP-1B per-chip refine source (1.8 GB/chip at 1B rows/64
    chips). On the quantization-hostile unit-norm synthetic
    (scripts/sharded_deep1b.py), end-to-end residual-cache recall@10 is
    ~0.95 at i8 vs ~0.58 at i4 (and quantizing the VECTORS directly,
    with no residual structure subtracting the ~4x-smaller list offsets,
    ranks at 0.897/0.123 — the floor the residual form lifts). The
    reference refines from the raw f32 dataset instead
    (detail/refine_host-inl.hpp), which at 1B scale can never be HBM
    resident. Scales are per-list MSE-optimal-clip on the actual stored
    residuals. Processes ``block_lists`` lists per step to bound the
    [B, cap, rot] f32 transient."""
    if dtype not in ("i4", "i8"):
        raise ValueError(f"dtype must be i4|i8, got {dtype!r}")
    qmax = 7 if dtype == "i4" else 127
    C, cap = index.indices.shape
    rot = index.rot_dim
    if dtype == "i4" and rot % 8 != 0:
        raise ValueError(f"int4 cache needs rot_dim % 8 == 0, got {rot}")
    ds = jnp.asarray(dataset)
    caches, scales, qnorms = [], [], []
    for c0 in range(0, C, block_lists):
        ids = index.indices[c0:c0 + block_lists]           # [B, cap]
        B = ids.shape[0]
        ok = (ids >= 0)[..., None]
        rows = ds[jnp.maximum(ids, 0)].astype(jnp.float32)  # [B, cap, d]
        r_rot = dist_dot(rows.reshape(B * cap, -1), index.rotation.T)
        res = (r_rot.reshape(B, cap, rot)
               - index.centers_rot[c0:c0 + B][:, None, :])
        res = jnp.where(ok, res, 0.0)
        base = jnp.maximum(
            jnp.max(jnp.abs(res), axis=1), 1e-30) / qmax    # [B, rot]
        s_blk = _pick_clip_scale(res, base, ok, qmax=qmax)  # [B, rot]
        if dtype == "i4":
            packed, qn = _quant_pack_i4(res, s_blk[:, None, :])
            caches.append(jnp.swapaxes(packed, 1, 2))       # [B, nw4, cap]
        else:
            q8 = jnp.clip(jnp.round(res / s_blk[:, None, :]), -128, 127)
            deq = q8 * s_blk[:, None, :]
            qn = jnp.sum(deq * deq, axis=-1)
            caches.append(q8.astype(jnp.int8))              # [B, cap, rot]
        scales.append(s_blk)
        qnorms.append(jnp.where(ok[..., 0], qn, 0.0))
    return dataclasses.replace(
        index,
        recon_cache=jnp.concatenate(caches),
        recon_scale=1.0,
        cache_scales=jnp.concatenate(scales),
        cache_qnorms=jnp.concatenate(qnorms),
        cache_fac=None,
    )


def _cache_kind_for(cache_decoded: bool, cache_dtype: str, C: int,
                    cap: int, rot: int, pq_bits: int = 8,
                    pq_dim: int = 0, per_subspace: bool = True,
                    ) -> Optional[str]:
    """The budget/dtype ladder shared by batch and streamed builds.

    "auto" is fidelity-first at the top: i8 (1 matmul pass,
    1 B/component, the finest cache) whenever it fits. Below the i8
    budget the two half-byte rungs — packed i4 raw residuals (1 MXU
    pass + in-kernel nibble decode, slightly lossy) and pq4 transposed
    codes (exact PQ distances, 16-pass one-hot contraction) — measured
    recall-TIED at equal bytes (EQUAL_BYTES_r05.json), so picking
    between them is a pure throughput question: it goes through the
    per-backend dispatch table (the measured ``pq_scan`` race,
    docs/dispatch_tuning.md), with i4 as the analytic fallback (~16x
    less MXU work per the projection; a table can overturn that where
    the one-hot contraction's locality actually wins). pq4 stays the
    explicit choice for pq_dim < dim compression below 0.5 B/dim —
    the reference's high-compression regime
    (ivf_pq_compute_similarity-inl.cuh LUT scoring) where no residual
    cache can operate.

    "rabitq" (ISSUE 11) is the 1-bit/dim bottom rung — sign-bit codes
    plus two per-row scalars, ~4× fewer code bytes than the half-byte
    rungs. Its FIRST-STAGE recall sits well below i4's, so "auto"
    only ever picks it through a MEASURED table winner (microbench
    races it at matched recall through its rerank pipeline — an arm
    that can't hit the band is filtered before the race); the analytic
    fallback never does, and when no kind fits the budget "auto" still
    returns None (no cache — plain search keeps its exact PQ code
    scan, the pre-r10 semantics; a silent 1-bit downgrade there would
    regress recall for plain-search callers). An auto- or
    explicitly-rabitq index should be searched through
    ``search_refined`` (the multi-stage pipeline); plain ``search``
    serves first-stage estimates."""
    if not cache_decoded or cap == 0:
        return None
    i8_ok = C * cap * rot <= _CACHE_BUDGET
    i4_ok = rot % 8 == 0 and C * cap * rot // 2 <= _CACHE_BUDGET
    pq4_ok = (pq_bits == 4 and per_subspace and pq_dim > 0
              and pq_dim % 8 == 0
              and C * cap * pq_dim // 2 <= _CACHE_BUDGET)
    # sign-bit cache: nw u32 words + fac/norm f32 scalars per row;
    # word padding makes any rot legal
    rabitq_ok = C * cap * (bits_words(rot) * 4 + 8) <= _CACHE_BUDGET
    if cache_dtype == "auto":
        if i8_ok:
            return "i8"
        feasible = [kind for kind, ok in
                    (("i4", i4_ok), ("pq4", pq4_ok),
                     ("rabitq", rabitq_ok)) if ok]
        if not feasible:
            return None
        from raft_tpu import tuning

        return tuning.choose(
            "pq_scan",
            {"n_lists": C, "cap": cap, "rot": rot, "pq_dim": pq_dim,
             "pq_bits": pq_bits},
            feasible, "i4" if i4_ok else None,
        )
    if cache_dtype == "i8":
        return "i8" if i8_ok else None
    if cache_dtype == "i4":
        return "i4" if i4_ok else None
    if cache_dtype == "pq4":
        return "pq4" if pq4_ok else None
    if cache_dtype == "rabitq":
        return "rabitq" if rabitq_ok else None
    raise ValueError(f"unknown cache_dtype {cache_dtype!r}")


def _resolve_cache_kind(index: "Index") -> Optional[str]:
    """Which cache precision to build for this index (None = no cache)."""
    return _cache_kind_for(
        bool(index.cache_decoded), str(index.cache_dtype), index.n_lists,
        index.indices.shape[1], index.rot_dim, int(index.pq_bits),
        int(index.pq_dim),
        int(index.codebook_kind) == codebook_gen.PER_SUBSPACE,
    )


def _attach_cache(index: "Index") -> "Index":
    """(Re)build the decoded-residual cache when enabled and affordable.
    Cache-only indexes (codes dropped at build) keep their existing cache
    — there is nothing to rebuild from."""
    kind = _resolve_cache_kind(index)
    if index.codes.ndim != 3 or index.codes.shape[-1] == 0:
        # flat streamed codes / cache-only: never rebuilt here
        if index.codes.shape[-1] == 0 and index.recon_cache is not None:
            return index
        return dataclasses.replace(
            index, recon_cache=None, cache_scales=None, cache_qnorms=None,
            cache_fac=None,
        )
    if kind is None:
        return dataclasses.replace(
            index, recon_cache=None, cache_scales=None, cache_qnorms=None,
            cache_fac=None,
        )
    if kind == "i8":
        cache, scale = _recon_cache_scan(
            index.codes, index.pq_centers, index.codebook_kind,
            index.pq_dim, index.pq_bits,
        )
        return dataclasses.replace(
            index, recon_cache=cache, recon_scale=float(scale),
            cache_scales=None, cache_qnorms=None, cache_fac=None,
        )
    if kind == "pq4":
        # the "cache" IS the packed codes, transposed to the kernel's
        # dense [C, nw, cap] layout (discriminated from the i4 residual
        # cache by cache_scales is None — see Index.cache_kind)
        return dataclasses.replace(
            index, recon_cache=jnp.swapaxes(index.codes, 1, 2),
            recon_scale=1.0, cache_scales=None, cache_qnorms=None,
            cache_fac=None,
        )
    if kind == "rabitq":
        cache_t, fac, qnorms = _rabitq_cache_scan(
            index.codes, index.indices, index.pq_centers,
            index.codebook_kind, index.pq_dim, index.pq_bits,
        )
        return dataclasses.replace(
            index, recon_cache=cache_t, recon_scale=1.0,
            cache_scales=None, cache_qnorms=qnorms, cache_fac=fac,
        )
    cache_t, scales, qnorms = _recon_cache_scan_i4(
        index.codes, index.indices, index.pq_centers, index.codebook_kind,
        index.pq_dim, index.pq_bits,
    )
    return dataclasses.replace(
        index, recon_cache=cache_t, recon_scale=1.0,
        cache_scales=scales, cache_qnorms=qnorms, cache_fac=None,
    )


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnums=(1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15),
)
def _pq_search(
    arrays,
    k: int,
    n_probes: int,
    metric_val: int,
    group: int,
    bucket_batch: int,
    codebook_kind: int,
    filter_nbits: int,
    compute_dtype: str = "bf16",
    local_recall_target: float = 0.95,
    merge_recall_target: float = 1.0,
    lut_dtype: str = "f32",
    internal_dtype: str = "f32",
    pq_dim: int = 0,
    pq_bits: int = 8,
    scan_impl: str = "xla",
):
    (queries, centers, centers_rot, rotation, pq_centers, codes, indices,
     list_sizes, rec_norms, filter_bits, recon_cache, recon_scale,
     cache_scales, cache_qnorms, cache_fac) = arrays
    cache_kind = ("none" if recon_cache is None
                  else "i8" if recon_cache.dtype != jnp.uint32
                  else "rabitq" if cache_fac is not None
                  else "i4" if cache_scales is not None
                  else "pq4")
    cache_i4 = cache_kind == "i4"
    cache_rabitq = cache_kind == "rabitq"
    metric = DistanceType(metric_val)
    select_min = is_min_close(metric)
    C, cap = indices.shape   # codes may be FLAT [C*cap, nw] (streamed
    # 100M-scale builds: the 3-D native layout would need a multi-GB
    # relayout copy) or the regular [C, cap, nw]
    p = pq_dim
    rot_dim = rotation.shape[0]
    q32 = queries.astype(jnp.float32)
    m = q32.shape[0]
    sentinel = sentinel_for(metric, jnp.float32)

    # coarse phase (ivf_pq_search.cuh:70 select_clusters); the stages
    # run under the same named scopes as ivf_flat._ivf_search's
    with jax.named_scope("ivf.coarse"):
        cdot = dist_dot(q32, centers.T)
        if metric == DistanceType.InnerProduct:
            coarse = cdot
        else:
            qn2 = jnp.sum(q32 * q32, axis=1, keepdims=True)
            cn2 = jnp.sum(centers * centers, axis=1)
            coarse = qn2 + cn2[None, :] - 2.0 * cdot
        _, probes = select_k(coarse, n_probes, select_min=select_min)

    with jax.named_scope("ivf.bucketize"):
        (bucket_list, bucket_q, pair_bucket, pair_pos, order, total,
         nb_pad) = bucketize_pairs(probes, m, n_probes, C, group,
                                   bucket_batch)

    kl = min(k, cap)
    q_rot = dist_dot(q32, rotation.T)  # [m, rot_dim]
    mm = jnp.bfloat16 if compute_dtype == "bf16" else jnp.float32
    # lut_dtype lowers the decode precision below the compute dtype —
    # the reference's fp16/fp8 LUT ladder (detail/ivf_pq_fp_8bit.cuh)
    if lut_dtype == "bf16" and mm is jnp.float32:
        mm = jnp.bfloat16
    decode_via_f8 = lut_dtype == "f8"

    def body(_, inp):
        bl, bq = inp  # [bb], [bb, group]
        ids = indices[bl]
        sizes = list_sizes[bl]
        # pq4's transposed-code "cache" is not a decoded-residual block;
        # the XLA body scores it from the packed codes like any code index
        use_cache_blk = (cache_kind in ("i8", "i4", "rabitq")
                         and lut_dtype in ("auto", "i8"))
        rn = (cache_qnorms if use_cache_blk and cache_qnorms is not None
              else rec_norms)[bl]
        if use_cache_blk:
            # decoded-residual cache: a contiguous block load + cast
            # replaces the per-element codebook gather (the decode gather
            # measured ~5x the block matmul at CAGRA-build shapes). Only
            # taken when lut_dtype allows it — explicit f32/bf16/f8 get
            # the true decode at that precision
            if cache_rabitq:
                # XLA mirror of the kernel's estimator: dequantized
                # r̂ = fac·sign(r) scores the cross term, rn (above)
                # already selected the TRUE residual norms
                blk_t = recon_cache[bl]                # [bb, nwb, cap]
                signs = unpack_sign_bits(
                    jnp.swapaxes(blk_t, 1, 2), rot_dim)
                recon = signs * cache_fac[bl][:, :, None]
            elif cache_i4:
                blk_t = recon_cache[bl]                # [bb, nw4, cap]
                raw = unpack_i4(jnp.swapaxes(blk_t, 1, 2))
                recon = raw * cache_scales[bl][:, None, :]
            else:
                sc = (cache_scales[bl][:, None, :]
                      if cache_scales is not None      # raw i8 per-list
                      else recon_scale)
                recon = recon_cache[bl].astype(jnp.float32) * sc
        else:
            if codes.ndim == 2:
                # flat streamed codes: gather each probed list's row range
                rows = bl[:, None] * cap + jnp.arange(cap)[None, :]
                blk_raw = codes[rows]                  # [bb, cap, nw]
            else:
                blk_raw = codes[bl]
            blk_codes = unpack_codes(blk_raw, p, pq_bits)  # [bb, cap, p]
            if codebook_kind == codebook_gen.PER_SUBSPACE:
                recon = _decode_gather(blk_codes, pq_centers, codebook_kind)
            else:
                recon = _decode_gather(
                    blk_codes, pq_centers, codebook_kind, bl[:, None]
                )                        # [bb, cap, rot_dim]
        if decode_via_f8:
            # scaled round-trip through e4m3 (the reference's fp8 LUT
            # stores a shared exponent bias, ivf_pq_fp_8bit.cuh) —
            # unscaled values beyond ±448 would become NaN
            f8_scale = jnp.maximum(jnp.max(jnp.abs(recon)), 1e-30) / 240.0
            recon = (
                (recon / f8_scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * f8_scale
            )
        recon = recon.astype(mm)
        qsafe = jnp.maximum(bq, 0)
        q_res = q_rot[qsafe] - centers_rot[bl][:, None, :]  # [bb, g, rot_dim]
        dots = jnp.einsum(
            "bgd,bcd->bgc", q_res.astype(mm), recon,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        if metric == DistanceType.InnerProduct:
            # q·x ≈ q·c_l + q_rot·recon (rotation is orthogonal)
            qc = jnp.einsum(
                "bgd,bd->bg", q_rot[qsafe], centers_rot[bl],
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            qdots = jnp.einsum(
                "bgd,bcd->bgc", q_rot[qsafe].astype(mm), recon,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            dist = qc[:, :, None] + qdots
        else:
            qrn = jnp.sum(q_res * q_res, axis=2)  # [bb, g]
            dist = jnp.maximum(
                qrn[:, :, None] - 2.0 * dots + rn[:, None, :], 0.0
            )
        col_ok = (jnp.arange(cap)[None, :] < sizes[:, None])[:, None, :]
        valid = col_ok & (bq >= 0)[:, :, None]
        if filter_bits is not None:
            valid = valid & filter_keep(filter_bits, filter_nbits, ids)[:, None, :]
        dist = jnp.where(valid, dist, sentinel)
        if internal_dtype == "bf16":
            # lower-precision internal distances (reference fp16 analog)
            dist = dist.astype(jnp.bfloat16).astype(jnp.float32)
        ld, li = merge_topk(
            dist, jnp.broadcast_to(ids[:, None, :], dist.shape), kl, select_min,
            approx=local_recall_target < 1.0,
            recall_target=local_recall_target,
        )
        # flatten [bb, group, kl] -> [bb, group*kl]: the scan's stacked
        # output otherwise pads the kl minor dim to 128 lanes (12.8x HBM
        # at k=10 — 5.2 GB at the DEEP-100M config)
        bb = ld.shape[0]
        return None, (ld.reshape(bb, -1), li.reshape(bb, -1))

    with jax.named_scope("ivf.scan"):
        if scan_impl.startswith("pallas"):
            # fused Pallas scan over the int8 decoded-residual cache: identical
            # machinery to ivf_flat's kernel — the PQ twist is that the scanned
            # space is the rotated residual space, so the per-bucket "queries"
            # are query residuals vs the probed list's center, with the int8
            # dequant scale folded into them (dots then equal q_res . recon)
            from raft_tpu.ops import ivf_scan

            kl = min(kl, 256)  # in-kernel extraction budget (see ivf_flat)
            qsafe_b = jnp.maximum(bucket_q, 0)
            q_res = q_rot[qsafe_b] - centers_rot[bucket_list][:, None, :]
            # dequant scaling folds into the query side so the kernel scores
            # raw cached integers: scalar recon_scale for int8, the per-LIST
            # per-component scale rows for packed int4 (qv is per-bucket and a
            # bucket is one list — free per-list granularity). The pq4 code
            # scan is scale-free (the codebook lives in the kernel's LUT
            # weights), so qv stays the raw residual.
            qscale = (cache_scales[bucket_list][:, None, :]
                      if cache_scales is not None       # per-list (raw caches)
                      else 1.0 if cache_kind in ("pq4", "rabitq")
                      else recon_scale)
            qv = (q_res * qscale).astype(mm)                     # [nb, G, rot]
            ip = metric == DistanceType.InnerProduct
            if ip:
                # dist contribution = -(q_rot . recon); the per-(query, list)
                # constant q_rot . c_l is added back after the kernel
                qv = (q_rot[qsafe_b] * qscale).astype(mm)
                mk, qaux = ivf_scan.IP, None
            else:
                mk, qaux = ivf_scan.L2, jnp.sum(q_res * q_res, axis=2)
            if cache_rabitq:
                # zero-pad queries to the sign-word width: pad bits decode
                # -1 in-kernel, so a zero query component nulls them; the
                # per-row fac scale rides as the kernel's row_scale operand
                # and norms hold the TRUE residual norms (the estimator's
                # correct norm term — not the reconstruction's)
                dpad = recon_cache.shape[1] * 32 - rot_dim
                if dpad:
                    qv = jnp.pad(qv, ((0, 0), (0, 0), (0, dpad)))
            keep = None
            if filter_bits is not None:
                keep = filter_keep(filter_bits, filter_nbits, indices).astype(
                    jnp.int32
                )
            lut_w = None
            if cache_kind == "pq4":
                # block-diagonal codebook weights W[v][s*pl + l, s] =
                # pq_centers[s, v, l]: one [rot, p] matmul per code value
                # turns the per-subspace LUT build into MXU work (PER_SUBSPACE
                # only — a per-list codebook would need C of these)
                p_, K_, pl_ = pq_centers.shape
                eye = jnp.eye(p_, dtype=jnp.float32)
                lut_w = (pq_centers.transpose(1, 0, 2)[:, :, :, None]
                         * eye[None, :, None, :]).reshape(K_, p_ * pl_, p_)
            norms = rec_norms if cache_qnorms is None else cache_qnorms
            out_d, cand_i = ivf_scan.fused_list_scan_topk(
                recon_cache, indices, list_sizes, bucket_list, qv, qaux,
                None if ip else norms,       # IP kernel never reads norms
                keep,
                lut_weights=lut_w,
                row_scale=cache_fac if cache_rabitq else None,
                k=kl, metric_kind=mk, approx=local_recall_target < 1.0,
                recall_target=float(local_recall_target),
                interpret=scan_impl == "pallas_interpret",
                packed_i4=cache_i4,
                packed_bits=cache_rabitq,
            )                                                # ids in-kernel
            if ip:
                qc = jnp.einsum(
                    "bgd,bd->bg", q_rot[qsafe_b], centers_rot[bucket_list],
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                )
                cand_d = qc[:, :, None] + (-out_d)   # min-space -> score
            else:
                cand_d = out_d
            cand_d = jnp.where(jnp.isinf(out_d), sentinel, cand_d)
        else:
            xs = (
                bucket_list.reshape(-1, bucket_batch),
                bucket_q.reshape(-1, bucket_batch, group),
            )
            _, (cand_d, cand_i) = jax.lax.scan(body, None, xs)
            cand_d = cand_d.reshape(nb_pad, group, kl)
            cand_i = cand_i.reshape(nb_pad, group, kl)

    # candidate width off the scan's output (the kernel's fold arm
    # emits R*128)
    with jax.named_scope("ivf.merge"):
        out_d, out_i = unbucketize_merge(
            cand_d, cand_i, pair_bucket, pair_pos, order, total, m,
            n_probes, int(cand_d.shape[2]), k, select_min, sentinel,
            approx=merge_recall_target < 1.0,
            recall_target=merge_recall_target,
        )
        # fewer than k valid candidates: id must be -1 (documented
        # contract); otherwise refine re-scores filtered-out ids back
        # into the top-k
        out_i = jnp.where(out_d == sentinel, -1, out_i)
        if metric == DistanceType.L2SqrtExpanded:
            out_d = jnp.sqrt(jnp.maximum(out_d, 0.0))
    return out_d, out_i


def search(
    search_params: SearchParams,
    index: Index,
    queries,
    k: int,
    prefilter=None,
) -> Tuple[jax.Array, jax.Array]:
    """Approximate k-NN search (reference ivf_pq-inl.cuh:480). Distances are
    PQ approximations — pair with ``neighbors.refine`` for exact re-ranking
    (the reference benchmarks do the same)."""
    queries = jnp.asarray(queries)
    n_probes = int(min(search_params.n_probes, index.n_lists))
    cap = index.indices.shape[1]
    if cap == 0:
        raise ValueError("index is empty — build with add_data_on_build or extend")
    if k > n_probes * cap:
        raise ValueError(f"k={k} exceeds n_probes*list_capacity={n_probes * cap}")
    with obs.entry_span("search", "ivf_pq", queries=int(queries.shape[0]),
                        k=int(k), n_probes=n_probes) as _sp:
        filt = as_filter(prefilter)
        # materializes "keep"-mode tombstone filters (new ids past the
        # filter default to kept) for the drop-semantics scan kernels —
        # docs/serving.md §5; index.size stays lazy (device reduction)
        bits = resolve_filter_bits(filt, lambda: index.size)
        arrays = (
            queries, index.centers, index.centers_rot, index.rotation,
            index.pq_centers, index.codes, index.indices, index.list_sizes,
            index.rec_norms, None if bits is None else bits.bits,
            index.recon_cache, jnp.float32(index.recon_scale),
            index.cache_scales, index.cache_qnorms, index.cache_fac,
        )  # recon_cache rides along; the body gates its use on lut_dtype
        from raft_tpu.neighbors.ivf_flat import (
            adaptive_query_group, _resolve_scan_impl,
        )

        group = adaptive_query_group(
            int(queries.shape[0]), n_probes, index.n_lists,
            int(search_params.query_group),
        )
        requested = str(search_params.scan_impl)
        lut = _norm_dtype_knob(search_params.lut_dtype)
        use_cache = index.recon_cache is not None and lut in ("auto", "i8")
        if lut == "i8" and index.cache_kind not in ("i8", "i4"):
            raise ValueError(
                "lut_dtype='i8' needs the decoded-residual cache; build with "
                "cache_decoded=True (and within _CACHE_BUDGET)"
            )
        if not use_cache:
            if requested.startswith("pallas"):
                raise ValueError(
                    "scan_impl=%r needs the decoded-residual cache (build "
                    "with cache_decoded=True and keep lut_dtype='auto'/'i8')"
                    % requested
                )
            if index.codes.shape[-1] == 0:
                raise ValueError(
                    "this index was built with keep_codes=False (cache-only); "
                    "decode-path scoring needs the packed codes — search with "
                    "lut_dtype='auto' and the cache scan instead"
                )
            impl = "xla"
        else:
            # cache-only indexes are fine on BOTH impls here: the XLA body
            # also scores from recon_cache when lut_dtype is auto/i8
            impl = _resolve_scan_impl(
                requested, cap, min(k, cap),
                approx=float(search_params.local_recall_target) < 1.0,
            )
            if impl.startswith("pallas") and k > n_probes * min(cap, 256):
                raise ValueError(
                    f"k={k} exceeds the fused kernel's candidate pool "
                    f"n_probes*min(cap,256)={n_probes * min(cap, 256)}; raise "
                    "n_probes or use scan_impl='xla'"
                )
        _sp.set(scan_impl=impl, lut=lut)
        return _pq_search(
            arrays,
            int(k),
            n_probes,
            int(index.metric),
            group,
            int(search_params.bucket_batch),
            int(index.codebook_kind),
            0 if bits is None else int(bits.n_bits),
            str(search_params.compute_dtype),
            float(search_params.local_recall_target),
            float(search_params.merge_recall_target),
            lut,
            _norm_dtype_knob(search_params.internal_distance_dtype),
            int(index.pq_dim),
            int(index.pq_bits),
            impl,
        )


def coarse_margins(index: Index, queries, p: int = 2) -> jax.Array:
    """Per-query difficulty margin from the coarse quantizer (see
    ``ivf_flat.coarse_margins`` — the ivf_pq coarse phase runs the same
    queries x centers selection, so the signal and the jitted kernel
    are shared)."""
    from raft_tpu.neighbors.ivf_flat import coarse_margins as _cm

    return _cm(index, queries, p=p)


def _decode_slots(slots, recon_cache, cache_scales, centers_rot,
                  recon_scale):
    """Decode flattened list slots (``list * cap + slot``) [m, c] from the
    residual cache to [m, c, rot_dim] f32 vectors in rotated space.

    The per-candidate fidelity source for cache-resident refine: packed
    int4 caches hold raw rotated residuals (per-list scales), int8 caches
    hold decoded-PQ residuals (scalar scale); either way the vector is
    ``centers_rot[list] + residual``."""
    if recon_cache.dtype == jnp.uint32:                  # packed int4
        C, nw4, cap = recon_cache.shape
        lst = slots // cap
        sl = slots % cap
        words = recon_cache[lst, :, sl]                  # [m, c, nw4]
        res = unpack_i4(words) * cache_scales[lst]
    else:                                                # int8
        C, cap, _rot = recon_cache.shape
        lst = slots // cap
        sl = slots % cap
        sc = (cache_scales[lst] if cache_scales is not None  # raw i8
              else recon_scale)
        res = recon_cache[lst, sl].astype(jnp.float32) * sc
    return centers_rot[lst] + res


def _refine_slots(queries, slots, k: int, metric_val: int,
                  recon_cache, cache_scales, centers_rot, rotation,
                  recon_scale):
    """Exact re-rank of slot candidates against cache-decoded vectors —
    the refine source that fits the DEEP-1B per-chip budget (the
    reference refines from the raw dataset, detail/refine_device.cuh /
    detail/refine_host-inl.hpp; at 1B scale the f32 dataset is 384 GB
    and never sharded into HBM, but the int4 cache IS — so refine
    decodes the <= k*ratio candidates per query from it on-chip).

    Distances are computed at f32 in rotated space (the rotation is
    orthonormal, so L2/IP are preserved); slots < 0 are invalid.
    Returns (dist [m, k], slots [m, k])."""
    with jax.named_scope("ivf_pq.rerank"):
        metric = DistanceType(metric_val)
        q32 = jnp.asarray(queries).astype(jnp.float32)
        qrot = dist_dot(q32, rotation.T)                     # [m, rot]
        valid = slots >= 0
        safe = jnp.maximum(slots, 0)
        vec = _decode_slots(safe, recon_cache, cache_scales, centers_rot,
                            recon_scale)                     # [m, c, rot] f32
        if metric == DistanceType.InnerProduct:
            # elementwise mult-sum: XLA fuses it into the gather consumer
            # (the "md,mcd" einsum form measured 4x slower on v5e, r4)
            d = jnp.sum(vec * qrot[:, None, :], axis=-1, dtype=jnp.float32)
        else:
            diff = qrot[:, None, :] - vec
            d = jnp.sum(diff * diff, axis=-1, dtype=jnp.float32)
            if metric == DistanceType.L2SqrtExpanded:
                d = jnp.sqrt(d)
        sentinel = sentinel_for(metric, jnp.float32)
        d = jnp.where(valid, d, sentinel)
        out_d, out_s = merge_topk(d, slots.astype(jnp.int32), k,
                                  is_min_close(metric))
        out_s = jnp.where(out_d == sentinel, -1, out_s)
        return out_d, out_s


def _slot_indices(indices):
    """Replace stored global ids [C, cap] with flattened slot positions,
    keeping -1 at padding slots, so a search over the substituted index
    emits WHERE each candidate lives instead of what it is — the id is
    recovered afterwards by one flat gather (``indices.reshape(-1)[slot]``)
    and no O(n_rows) inverse map ever exists."""
    C, cap = indices.shape
    slot_ids = jnp.arange(C * cap, dtype=jnp.int32).reshape(C, cap)
    return jnp.where(indices >= 0, slot_ids, -1)


@functools.partial(jax.jit, static_argnums=(2, 3, 7, 8, 9))
def _refine_slots_codes(queries, slots, k: int, metric_val: int,
                        codes, pq_centers, centers_rot,
                        codebook_kind: int, pq_dim: int, pq_bits: int,
                        rotation=None):
    """Exact re-rank of slot candidates against the PQ-DECODED vectors —
    the rerank source for the rabitq pipeline when the index still
    carries its codes: stage 1 scans 1-bit estimates, stage 2 re-scores
    the shortlist at full PQ fidelity (one codebook gather per
    candidate, ≤ k·ratio rows per query — FusionANNS's
    move-only-shortlist-bytes shape). Distances are f32 in rotated
    space; slots < 0 are invalid. Returns (dist [m, k], slots [m, k])."""
    with jax.named_scope("ivf_pq.rerank"):
        metric = DistanceType(metric_val)
        q32 = jnp.asarray(queries).astype(jnp.float32)
        qrot = dist_dot(q32, rotation.T)                     # [m, rot]
        valid = slots >= 0
        safe = jnp.maximum(slots, 0)
        if codes.ndim == 2:                                  # flat streamed
            C = centers_rot.shape[0]
            cap = codes.shape[0] // C
            words = codes[safe]                              # [m, c, nw]
        else:
            C, cap, _nw = codes.shape
            words = codes.reshape(C * cap, -1)[safe]         # [m, c, nw]
        lst = safe // cap
        u = unpack_codes(words, pq_dim, pq_bits)             # [m, c, p]
        if codebook_kind == codebook_gen.PER_SUBSPACE:
            recon = _decode_gather(u, pq_centers, codebook_kind)
        else:
            recon = _decode_gather(u, pq_centers, codebook_kind, lst)
        vec = centers_rot[lst] + recon                       # [m, c, rot]
        if metric == DistanceType.InnerProduct:
            d = jnp.sum(vec * qrot[:, None, :], axis=-1, dtype=jnp.float32)
        else:
            diff = qrot[:, None, :] - vec
            d = jnp.sum(diff * diff, axis=-1, dtype=jnp.float32)
            if metric == DistanceType.L2SqrtExpanded:
                d = jnp.sqrt(d)
        sentinel = sentinel_for(metric, jnp.float32)
        d = jnp.where(valid, d, sentinel)
        out_d, out_s = merge_topk(d, slots.astype(jnp.int32), k,
                                  is_min_close(metric))
        out_s = jnp.where(out_d == sentinel, -1, out_s)
        return out_d, out_s


def _slot_prefilter(index: Index, prefilter):
    """Translate a stored-id prefilter into SLOT space for the
    slot-substituted inner search: the user/tombstone bitset is keyed by
    global id, but the first stage emits slots — so the keep decision is
    materialized per (list, slot) once, packed into a slot-indexed
    bitset, and composed BEFORE the shortlist exists (a filtered row can
    never reach the rerank). Returns a BitsetFilter or None.

    Cached on the filter object keyed by (bitset version, indices
    identity) — steady-state serving calls this per batch with one
    composed tombstone filter, and the translation's device ops (keep
    test + bit pack) must not be paid N times (the
    ``resolve_filter_bits`` caching idiom)."""
    import weakref

    filt = as_filter(prefilter)
    bits = resolve_filter_bits(filt, lambda: index.size)
    if bits is None:
        return None
    # The cache lives on the LONG-LIVED underlying Bitset, not the
    # BitsetFilter wrapper: serve constructs a fresh wrapper per batch
    # (engine._run_search), so a wrapper-resident entry would never hit
    # and every batch would re-pay the translation's device ops
    # (review fix, r10). The key carries the SOURCE bitset's version,
    # not (only) the resolved one — a keep-mode filter narrower than
    # the index materializes through copy().resize(), whose result
    # sits at _version == 1 every time, which would serve a stale slot
    # filter after the source mutates — plus the wrapper's
    # out_of_range mode (two wrappers over one bitset may disagree).
    src = getattr(filt, "bitset", None)
    host = src if src is not None else filt
    key = (getattr(src, "_version", 0), getattr(bits, "_version", 0),
           int(bits.n_bits), getattr(filt, "out_of_range", "drop"))
    cached = getattr(host, "_slot_filter", None)
    if (cached is not None and cached[0] == key
            and cached[2]() is index.indices):
        return cached[1]
    from raft_tpu.core.bitset import Bitset

    keep = filter_keep(bits.bits, int(bits.n_bits), index.indices)
    keep = keep & (index.indices >= 0)
    out = as_filter(Bitset.from_dense(keep.reshape(-1)))
    try:
        # a WEAK ref ties the entry to this exact indices array without
        # pinning a retired generation's [C, cap] int32 block alive on
        # a long-lived bitset object (review fix, r10); a dead or
        # different referent simply misses the cache
        host._slot_filter = (key, out, weakref.ref(index.indices))
    except (AttributeError, TypeError):  # slotted host / unweakrefable
        pass
    return out


def refined_shortlist_width(search_params: SearchParams, index: Index,
                            k: int, refine_ratio: int) -> int:
    """The first-stage over-fetch width ``search_refined`` uses for
    ``k`` at ``refine_ratio`` — exposed so serve's warmup can trace the
    tiered rerank at exactly the shortlist shapes dispatch will see."""
    cap = index.indices.shape[1]
    n_probes = int(min(search_params.n_probes, index.n_lists))
    return max(int(k), min(int(k * refine_ratio), n_probes * cap))


def search_refined(
    search_params: SearchParams,
    index: Index,
    queries,
    k: int,
    refine_ratio: int = 2,
    prefilter=None,
    dataset=None,
) -> Tuple[jax.Array, jax.Array]:
    """Multi-stage search: cheap first-stage scan over the compressed
    cache, exact re-rank of the over-fetched shortlist (the reference's
    ``refine_ratio`` pattern, bench/ann raft_ivf_pq_wrapper.h; the
    FusionANNS architecture — only shortlist bytes move at fidelity).

    The first stage runs over slot-substituted indices at
    ``k * refine_ratio``; the shortlist is then re-ranked from the
    finest available source and slots resolve to global ids. Rerank
    source resolution:

    * ``dataset`` given — exact originals. A **device** ``jax.Array``
      keeps the resident full-upload fast path
      (:mod:`~raft_tpu.neighbors.refine`); a **host** numpy array or
      ``np.memmap`` routes through the tiered shortlist-only fetch
      (:class:`raft_tpu.neighbors.tiered.HostArraySource` — only the
      unique shortlist rows ever cross the link, bitwise-identical
      results); a :class:`~raft_tpu.neighbors.tiered.RerankSource`
      instance is used as-is (the persistent hot-row-cache path).
      Stage 1 returns global ids directly; no slot indirection needed;
    * i8/i4 residual cache — decoded at f32 on-chip (the billion-scale
      source: the dataset is never HBM-resident);
    * the packed PQ codes (rabitq indexes that kept them) — full PQ
      fidelity over the 1-bit first stage's shortlist.

    ``prefilter`` (tombstone/user bitsets) composes with the FIRST
    stage — filtered rows never enter the shortlist (translated to slot
    space for the inner search). A pq4/no-cache index without a dataset
    still errors: its own scan is already exact PQ, so a codes rerank
    adds nothing. Rerank-stage observability (docs/observability.md):
    ``rerank.queries_total``/``rerank.shortlist_rows`` (valid slots
    only)/``rerank.bytes_fetched_total{source}`` (unique rows on the
    tiered path) + the first-stage vs rerank latency split
    (``rerank.stage_ms{stage}``, device-complete), and ``tiered.*``
    for the host tiers.
    """
    from raft_tpu.neighbors import tiered as _tiered

    if refine_ratio < 1:
        raise ValueError(f"refine_ratio must be >= 1, got {refine_ratio}")
    kind = index.cache_kind
    has_codes = index.codes.shape[-1] > 0
    if dataset is None and kind not in ("i8", "i4") and not (
            kind == "rabitq" and has_codes):
        raise ValueError(
            "search_refined needs a rerank source finer than the first "
            "stage: a residual cache (i8/i4), the packed codes (rabitq "
            "indexes built with keep_codes=True), or an explicit "
            "dataset= — a pq4/no-cache index's own scan is already "
            "exact PQ; for raw-dataset refine there, pass dataset= or "
            "use neighbors.refine"
        )
    from raft_tpu import plan as _plan

    src_obj = None if dataset is None else _tiered.as_source(dataset)
    queries = jnp.asarray(queries)
    kc = refined_shortlist_width(search_params, index, k, refine_ratio)
    # the pipeline is the canonical plan (raft_tpu/plan/canonical.py),
    # compiled fresh per call — the bind work is a handful of closures
    # (serve caches its compiled variants per handle; library callers
    # pay exactly what the hand-wired dispatch paid, since the legacy
    # path also rebuilt the slot substitution per call). The stage
    # spans + rerank.* counters (docs/observability.md) are emitted by
    # the node executors, byte-identical names/labels to the
    # hand-wired emission.
    with obs.span("ivf_pq.search_refined", refine_ratio=int(refine_ratio),
                  k=int(k), cache_kind=kind) as _sp:
        if src_obj is not None:
            source = "host" if src_obj.kind == "host" else "dataset"
            p = _plan.refined_plan("tiered")
        else:
            source = "cache" if kind in ("i8", "i4") else "codes"
            p = _plan.refined_plan(source)
        compiled = _plan.compile(p, index, k=int(k),
                                 search_params=search_params,
                                 refine_ratio=int(refine_ratio),
                                 source=src_obj)
        d, ids = compiled(queries, prefilter=prefilter)
        if obs.enabled():
            _sp.set(source=source, shortlist=kc)
        return d, ids


def search_refined_stream(
    search_params: SearchParams,
    index: Index,
    queries,
    k: int,
    refine_ratio: int = 2,
    prefilter=None,
    dataset=None,
    batch_rows: int = 1024,
    pipeline_depth: Optional[int] = None,
    token=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Batched :func:`search_refined` with graft-flow overlap: batch
    N+1's first-stage scan + shortlist fetch (host gather + H2D upload
    — :meth:`~raft_tpu.neighbors.tiered.RerankSource.prepare`) runs on
    a bounded background producer while batch N's exact rerank scores
    and lands in the host result arrays. This is the batched tiered
    path the serial per-batch loop becomes once the fetch dominates:
    the memmap gather disappears behind device compute
    (``pipeline.stall_ms{path=tiered.rerank}`` shows what is left).

    Requires ``dataset`` (host array / memmap / ``RerankSource`` — the
    overlap hides *its* fetch; the cache/codes reranks never fetch).
    Results are bitwise :func:`search_refined` over the same batches at
    any ``pipeline_depth`` including 0 (off): an overlapped
    ``prepare(N+1)`` can at most classify a row as a host miss that a
    serialized run would have served from the hot cache — the gathered
    values are identical either way (tiered module docstring), only
    ``FetchInfo`` traffic accounting shifts between tiers. ``token``
    cancellation drains the producer at the next batch boundary.
    """
    from raft_tpu.core import pipeline as _pipeline
    from raft_tpu.core.interruptible import Interruptible
    from raft_tpu.neighbors import tiered as _tiered
    from raft_tpu.resilience import faultinject

    if refine_ratio < 1:
        raise ValueError(f"refine_ratio must be >= 1, got {refine_ratio}")
    if dataset is None:
        raise ValueError(
            "search_refined_stream needs dataset= (a host array, memmap "
            "or tiered.RerankSource): the pipeline overlaps the rerank "
            "FETCH, and the cache/codes rerank paths never fetch — use "
            "search_refined for those")
    src_obj = _tiered.as_source(dataset)
    m = int(queries.shape[0])
    kc = refined_shortlist_width(search_params, index, k, refine_ratio)
    bs = max(int(batch_rows), 1)
    out_d = np.empty((m, k), np.float32)
    out_i = np.empty((m, k), np.int32)
    if token is None:
        token = Interruptible.get_token()

    def produce():
        for off in range(0, m, bs):
            qb = jnp.asarray(queries[off:off + bs])
            _, ids1 = search(search_params, index, qb, kc,
                             prefilter=prefilter)
            # the producer's host sync + gather + upload; score() stays
            # with the consumer so device results complete in order
            yield off, src_obj.prepare(qb, ids1)

    pf = _pipeline.Prefetcher(produce, depth=pipeline_depth,
                              path="tiered.rerank", token=token)
    with obs.span("ivf_pq.search_refined_stream", k=int(k),
                  refine_ratio=int(refine_ratio), n_queries=m,
                  batch_rows=bs, pipeline_depth=pf.depth), pf:
        for ci, (off, prepared) in enumerate(pf):
            token.check()
            # the CONSUMING dispatch's fault point: chunk-scoped specs
            # (oom@chunk:N) attribute here — never to the producer's
            # prefetch — and slow@stage:tiered.score lets the CPU-smoke
            # bench model the device scan time the overlap hides behind
            faultinject.check(stage="tiered.score", chunk=ci)
            d, i, _ = src_obj.score(prepared, int(k), index.metric)
            rows = min(bs, m - off)
            out_d[off:off + rows] = np.asarray(d, np.float32)[:rows]
            out_i[off:off + rows] = np.asarray(i)[:rows]
    return out_d, out_i


def _norm_dtype_knob(v) -> str:
    """Normalize a lut/internal dtype knob (string or jnp dtype) to
    'f32' | 'bf16' | 'f8'."""
    if isinstance(v, str):
        s = v.lower()
        if s in ("auto", "i8", "int8"):
            return "auto" if s == "auto" else "i8"
        if s in ("f32", "float32", "fp32"):
            return "f32"
        if s in ("bf16", "bfloat16", "f16", "fp16", "float16"):
            return "bf16"
        if s in ("f8", "fp8", "float8", "float8_e4m3fn", "e4m3"):
            return "f8"
        raise ValueError(f"unknown dtype knob {v!r}")
    dt = jnp.dtype(v)
    if dt == jnp.dtype(jnp.float32):
        return "f32"
    if dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        return "bf16"
    if "float8" in dt.name:
        return "f8"
    raise ValueError(f"unknown dtype knob {v!r}")


# ---------------------------------------------------------------------------
# serialization (reference detail/ivf_pq_serialize.cuh)
# ---------------------------------------------------------------------------


def save(path: str, index: Index) -> None:
    cap = index.indices.shape[1]
    codes_h = np.asarray(index.codes)
    if codes_h.ndim == 2:
        # flat streamed layout: host reshape is free (row-major bytes)
        codes_h = codes_h.reshape(index.n_lists, cap, -1)
    arrays = {
        "centers": np.asarray(index.centers),
        "centers_rot": np.asarray(index.centers_rot),
        "rotation": np.asarray(index.rotation),
        "pq_centers": np.asarray(index.pq_centers),
        "codes": codes_h,
        "indices": np.asarray(index.indices),
        "list_sizes": np.asarray(index.list_sizes),
        "rec_norms": np.asarray(index.rec_norms),
    }
    cache_only = codes_h.shape[-1] == 0 and cap > 0
    if cache_only and index.recon_cache is None:
        raise ValueError("cache-only index has no recon_cache to serialize")
    cache_kind = "none"
    # per-list-scaled caches hold RAW-residual fidelity (i4 streamed/
    # attach_raw_residual_cache, i8 raw) that a rebuild from decoded
    # codes would lose — serialize them, like cache-only caches (round 3
    # silently wrote empty codes and rebuilt a wrong cache on load). The
    # scalar-scale decoded-i8 cache and the pq4 transposed-code cache
    # rebuild exactly from codes and are not serialized.
    # the rabitq cache is serialized whenever present: streamed builds
    # binarize the RAW residual (a rebuild from decoded codes would lose
    # that fidelity), batch builds rebuild identically but the cache is
    # tiny (1 bit/dim + 8 B/row) so one rule covers both
    raw_scaled = (index.cache_scales is not None
                  or index.cache_fac is not None)
    if cache_only or raw_scaled:
        arrays["recon_cache"] = np.asarray(index.recon_cache)
        cache_kind = index.cache_kind
        if raw_scaled:
            if index.cache_scales is not None:
                arrays["cache_scales"] = np.asarray(index.cache_scales)
            if index.cache_fac is not None:
                arrays["cache_fac"] = np.asarray(index.cache_fac)
            if index.cache_qnorms is not None:
                arrays["cache_qnorms"] = np.asarray(index.cache_qnorms)
    write_index_file(
        path, "ivf_pq", _SERIAL_VERSION,
        {
            "metric": int(index.metric),
            "metric_arg": index.metric_arg,
            "codebook_kind": index.codebook_kind,
            "pq_bits": index.pq_bits,
            "pq_dim": index.pq_dim,
            "cache_decoded": bool(index.cache_decoded),
            "cache_dtype": str(index.cache_dtype),
            "serialized_cache": cache_kind,
            "recon_scale": float(index.recon_scale),
        },
        arrays,
    )


def load(path: str) -> Index:
    _, meta, arrays = read_index_file(path, "ivf_pq")
    ser_cache = meta.get("serialized_cache", "none")
    idx = Index(
        centers=jnp.asarray(arrays["centers"]),
        centers_rot=jnp.asarray(arrays["centers_rot"]),
        rotation=jnp.asarray(arrays["rotation"]),
        pq_centers=jnp.asarray(arrays["pq_centers"]),
        codes=jnp.asarray(arrays["codes"]),
        indices=jnp.asarray(arrays["indices"]),
        list_sizes=jnp.asarray(arrays["list_sizes"]),
        rec_norms=jnp.asarray(arrays["rec_norms"]),
        metric=DistanceType(meta["metric"]),
        pq_dim_=int(meta["pq_dim"]),
        metric_arg=meta["metric_arg"],
        codebook_kind=int(meta["codebook_kind"]),
        pq_bits=int(meta["pq_bits"]),
        cache_decoded=bool(meta.get("cache_decoded", True)),
        cache_dtype=str(meta.get("cache_dtype", "auto")),
    )
    if ser_cache != "none":
        # restore the serialized cache verbatim (for cache-only indexes
        # the rec_norms on disk are already the dequantized-vector norms)
        return dataclasses.replace(
            idx,
            recon_cache=jnp.asarray(arrays["recon_cache"]),
            recon_scale=float(meta.get("recon_scale", 1.0)),
            cache_scales=(jnp.asarray(arrays["cache_scales"])
                          if "cache_scales" in arrays else None),
            cache_qnorms=(jnp.asarray(arrays["cache_qnorms"])
                          if "cache_qnorms" in arrays else None),
            cache_fac=(jnp.asarray(arrays["cache_fac"])
                       if "cache_fac" in arrays else None),
        )
    return _attach_cache(idx)
