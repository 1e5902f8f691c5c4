"""IVF-Flat: inverted-file index with uncompressed vectors.

TPU-native analog of the reference's ivf_flat
(cpp/include/raft/neighbors/ivf_flat.cuh; types ivf_flat_types.hpp:49-84;
build detail/ivf_flat_build.cuh:343; search detail/ivf_flat_search-inl.cuh:38
+ the fused interleaved-scan kernel
detail/ivf_flat_interleaved_scan-inl.cuh:663).

Design — idiomatic TPU, not a port (SURVEY.md §7):

* **Storage**: the reference interleaves each list in groups of 32 vectors
  for warp-coalesced loads (ivf_flat_types.hpp:154-176). TPU vector lanes
  are fed by contiguous (8,128) tiles, so interleaving is pointless; lists
  live in a dense padded block ``[n_lists, cap, dim]`` (cap = longest list,
  tile-aligned) built by sort-by-label + scatter — no atomics
  (the reference's build_index_kernel, ivf_flat_build.cuh:115).

* **Search**: the reference launches one CTA per (query, probe) to scan a
  list with a warp-level priority queue. The TPU analog inverts the
  parallelism: all (query, probe) pairs are grouped **by list** so each
  step is a dense ``[G, d] x [d, cap]`` MXU matmul between a group of
  queries and one list block, followed by a local top-k; a final
  ``select_k`` merges each query's n_probes x k candidates (same merge the
  reference does at ivf_flat_search-inl.cuh:194). Grouping, bucketing and
  un-bucketing are all static-shape sort/cumsum/scatter — jit-compatible.

The per-list query groups are what make this fast: with balanced lists,
m x n_probes / n_lists queries share every list block, so the MXU runs at
high utilization instead of doing per-query gathers.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import obs
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
from raft_tpu.core.serialize import read_index_file, write_index_file
from raft_tpu.distance.types import DistanceType, is_min_close, resolve_metric
from raft_tpu.neighbors.common import (
    as_filter,
    filter_keep,
    merge_topk,
    sentinel_for,
)
from raft_tpu.matrix.select_k import select_k
from raft_tpu.utils.math import round_up_to_multiple
from raft_tpu.utils.precision import dist_dot

_SERIAL_VERSION = 1


# metrics the list-scan kernel implements; anything else would silently be
# scored as expanded L2
_SUPPORTED_METRICS = frozenset({
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.L2Unexpanded,
    DistanceType.InnerProduct,
    DistanceType.CosineExpanded,
})


@dataclasses.dataclass
class IndexParams:
    """Build params (reference ivf_flat_types.hpp:49-78)."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    metric_arg: float = 2.0
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    adaptive_centers: bool = False
    add_data_on_build: bool = True
    conservative_memory_allocation: bool = False  # API parity; no-op here
    # coarse-quantizer training GEMM dtype: "f32" (HIGH-precision passes,
    # safe for tightly clustered data) or "bf16" (~3x faster training,
    # r2 v5e)
    kmeans_compute_dtype: str = "f32"
    # stored-vector dtype: "f32" keeps the dataset bit-exact (reference
    # ivf_flat stores raw T); "bf16" halves list-scan HBM bytes — the
    # fused kernel is bandwidth-bound, so this trades ~3 significant
    # digits of stored precision for up to ~2x scan throughput (the
    # reference's int8/fp16 ivf_flat instantiations make the same trade).
    # Norms are computed FROM the rounded storage so distances stay
    # internally consistent.
    storage_dtype: str = "f32"

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)
        if self.metric not in _SUPPORTED_METRICS:
            raise ValueError(
                f"ivf_flat supports {sorted(m.name for m in _SUPPORTED_METRICS)}, "
                f"got {self.metric!r}"
            )


@dataclasses.dataclass
class SearchParams:
    """Search params (reference ivf_flat_types.hpp:81-84)."""

    n_probes: int = 20
    # TPU tuning knobs (no reference analog): queries per list-group matmul
    # and list blocks processed per XLA scan step (measured r2 on v5e:
    # 8 -> 4.7k QPS, 32 -> 11.2k, 64 -> 14.7k on SIFT-1M; 32 balances
    # compile time vs throughput)
    query_group: int = 256
    bucket_batch: int = 32
    # matmul operand dtype: "bf16" = single-pass MXU (distances still
    # accumulate in f32), "f32" = exact 6-pass. The reference's analog is
    # its fp16/fp8 LUT ladder (ivf_pq_types.hpp lut_dtype).
    compute_dtype: str = "bf16"
    # recall target for the per-list approx top-k (lane-binned Pallas
    # extraction / approx merge_topk); >= 1.0 switches to exact per-list
    # selection. NOTE: each list's extraction also caps at 256 candidates
    # per list on the fused Pallas path (the reference's kMaxCapacity=256,
    # select_warpsort.cuh:100) — with k > 256 entries of one list's true
    # top-k, the excess is unrecoverable; raise n_probes or force
    # scan_impl="xla" for exact semantics.
    local_recall_target: float = 0.95
    # recall target for the FINAL cross-probe merge. Default 1.0 = exact
    # final selection, matching the reference (ivf_flat_search-inl.cuh:194
    # runs exact select_k); set < 1.0 to use lax.approx_min_k there too
    # (measured r2 on v5e: ~1.2x QPS at 0.95 for ~0.5% recall on
    # SIFT-1M).
    merge_recall_target: float = 1.0
    # scan backend: "auto" picks the fused Pallas kernel on TPU when the
    # index layout allows it, else the XLA bucketized scan. Explicit:
    # "pallas" | "pallas_interpret" (CPU-debug) | "xla"
    scan_impl: str = "auto"


@dataclasses.dataclass
class Index:
    """IVF-Flat index (reference ivf_flat_types.hpp:127+).

    ``storage`` [n_lists, cap, dim] — padded list blocks (source dtype);
    ``indices`` [n_lists, cap] — source row ids, -1 in padding;
    ``list_sizes`` [n_lists]; ``centers`` [n_lists, dim] f32;
    ``data_norms`` — per-point squared norms for expanded-L2/cosine search.
    """

    centers: jax.Array
    storage: jax.Array
    indices: jax.Array
    list_sizes: jax.Array
    metric: DistanceType
    metric_arg: float = 2.0
    adaptive_centers: bool = False
    data_norms: Optional[jax.Array] = None

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def size(self) -> int:
        return int(self.list_sizes.sum())


jax.tree_util.register_dataclass(
    Index,
    data_fields=["centers", "storage", "indices", "list_sizes", "data_norms"],
    meta_fields=["metric", "metric_arg", "adaptive_centers"],
)


def _aligned_cap(max_count: int) -> int:
    """List capacity: lane-aligned (128) once lists are big enough for the
    fused scan kernel; 8-aligned for tiny test indexes."""
    if max_count >= 64:
        return round_up_to_multiple(max_count, 128)
    return max(8, round_up_to_multiple(max_count, 8))


def _coarse_metric(metric: DistanceType) -> DistanceType:
    """Metric for the coarse quantizer: pass IP/Cosine through (the
    reference trains kmeans_balanced with the index metric,
    detail/kmeans_balanced.cuh:659); L2 variants all train as L2."""
    if metric in (DistanceType.InnerProduct, DistanceType.CosineExpanded):
        return metric
    return DistanceType.L2Expanded


def _needs_norms(metric: DistanceType) -> bool:
    return metric in (
        DistanceType.L2Expanded,
        DistanceType.L2SqrtExpanded,
        DistanceType.L2Unexpanded,
        DistanceType.CosineExpanded,
    )


@functools.partial(jax.jit, static_argnums=(3, 4))
def _pack_lists(data, labels, row_ids, n_lists: int, cap: int):
    """Scatter rows into padded list blocks (sort-by-label, no atomics).

    Rows labelled >= n_lists are dropped (their scatter slots fall out of
    bounds, which XLA drops) — device-side ``extend`` uses this to discard
    the padding rows of the old storage without a host round-trip.

    Lists holding more than ``cap`` rows are truncated to their first
    ``cap`` rows in stable row order (IVF builds size cap >= max list
    count so this never fires there; the CAGRA/nn-descent reverse-graph
    packers rely on it to cap hub in-degree). Returned sizes are the
    *stored* (truncated) counts."""
    n, d = data.shape
    if n_lists * cap >= 2**31:
        raise ValueError(
            f"padded list storage n_lists*cap = {n_lists}*{cap} overflows "
            "int32 row indexing — the coarse lists are badly skewed "
            "(undertrained kmeans?) or cap_rows should bound list size"
        )
    order = jnp.argsort(labels, stable=True)
    sorted_labels = labels[order]
    counts = jnp.bincount(labels, length=n_lists)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n) - starts[jnp.minimum(sorted_labels, n_lists - 1)]
    slot = jnp.where(
        (sorted_labels < n_lists) & (pos < cap),
        sorted_labels * cap + pos,
        n_lists * cap,
    )
    counts = jnp.minimum(counts, cap)
    storage = (
        jnp.zeros((n_lists * cap, d), data.dtype).at[slot].set(data[order])
    ).reshape(n_lists, cap, d)
    indices = (
        jnp.full((n_lists * cap,), -1, jnp.int32).at[slot].set(
            row_ids[order].astype(jnp.int32))
    ).reshape(n_lists, cap)
    return storage, indices, counts.astype(jnp.int32)


def build(params: IndexParams, dataset, row_ids=None) -> Index:
    """Build the index (reference ivf_flat-inl.cuh:65 → build.cuh:343):
    subsample a trainset, balanced-kmeans the coarse centers, label every
    row, and scatter rows into padded lists."""
    dataset = jnp.asarray(dataset)
    n, d = dataset.shape
    n_lists = int(params.n_lists)

    with obs.entry_span("build", "ivf_flat", rows=n, n_lists=n_lists):
        with obs.span("ivf_flat.build.train"):
            # 1. trainset subsample + balanced kmeans (ivf_flat_build.cuh:384)
            frac = float(params.kmeans_trainset_fraction)
            if 0 < frac < 1.0 and int(n * frac) >= n_lists:
                step = max(int(1.0 / frac), 1)
                trainset = dataset[::step]
            else:
                trainset = dataset
            kb = KMeansBalancedParams(
                n_clusters=n_lists,
                n_iters=int(params.kmeans_n_iters),
                metric=_coarse_metric(params.metric),
                compute_dtype=str(params.kmeans_compute_dtype),
            )
            centers = kmeans_balanced.fit(kb, trainset)

        st_dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}.get(
            str(params.storage_dtype))
        if st_dtype is None:
            raise ValueError(
                f"storage_dtype must be f32|bf16, got {params.storage_dtype!r}")
        if st_dtype == jnp.bfloat16 and dataset.dtype not in (jnp.float32,
                                                              jnp.bfloat16):
            # The halved-bandwidth path narrows f32 storage; for any other
            # dataset dtype (f16, int8, ...) narrowing semantics are
            # undefined-to-lossy, and silently keeping dataset.dtype (the
            # pre-r5 behavior) gave the caller no signal (ADVICE r4).
            raise ValueError(
                f"storage_dtype='bf16' requires a float32 dataset, got "
                f"{dataset.dtype}; pass the dataset as f32 or leave "
                "storage_dtype='f32' to store in the dataset dtype")
        index = Index(
            centers=centers,
            storage=jnp.zeros((n_lists, 0, d),
                              st_dtype if dataset.dtype == jnp.float32
                              else dataset.dtype),
            indices=jnp.full((n_lists, 0), -1, jnp.int32),
            list_sizes=jnp.zeros((n_lists,), jnp.int32),
            metric=params.metric,
            metric_arg=params.metric_arg,
            adaptive_centers=bool(params.adaptive_centers),
        )
        if not params.add_data_on_build:
            return index
        if row_ids is None:
            row_ids = jnp.arange(n, dtype=jnp.int32)
        with obs.span("ivf_flat.build.pack"):
            return extend(index, dataset, jnp.asarray(row_ids))


def extend(index: Index, new_vectors, new_ids=None) -> Index:
    """Add vectors (reference ivf_flat_build.cuh:162 extend): label new rows,
    repack all lists at the new capacity, optionally adapt centers."""
    new_vectors = jnp.asarray(new_vectors)
    n_new = new_vectors.shape[0]
    if new_ids is None:
        new_ids = jnp.arange(index.size, index.size + n_new, dtype=jnp.int32)
    new_ids = jnp.asarray(new_ids).astype(jnp.int32)

    kb = KMeansBalancedParams(
        n_clusters=index.n_lists,
        metric=_coarse_metric(index.metric),
    )
    new_labels = kmeans_balanced.predict(kb, index.centers, new_vectors)

    # flatten existing lists + append, all on device: padding rows get the
    # out-of-range label n_lists so _pack_lists drops them (no host
    # round-trip — the reference extends lists in place on device too,
    # ivf_flat_build.cuh:162)
    C = index.n_lists
    old_cap = index.storage.shape[1]
    if old_cap > 0 and index.size > 0:
        flat = index.storage.reshape(-1, index.dim)
        flat_ids = index.indices.reshape(-1)
        flat_labels = jnp.where(
            flat_ids >= 0,
            jnp.repeat(jnp.arange(C, dtype=jnp.int32), old_cap),
            jnp.int32(C),
        )
        data = jnp.concatenate(
            [flat, new_vectors.astype(flat.dtype)], axis=0
        )
        labels = jnp.concatenate([flat_labels, new_labels])
        ids = jnp.concatenate([flat_ids, new_ids])
    else:
        data = new_vectors.astype(index.storage.dtype)
        labels, ids = new_labels, new_ids

    # only the per-list counts come to the host (they size the static cap)
    counts = np.asarray(index.list_sizes) + np.bincount(
        np.asarray(new_labels), minlength=C
    )
    cap = _aligned_cap(int(counts.max()))
    storage, indices, list_sizes = _pack_lists(data, labels, ids, C, cap)

    centers = index.centers
    if index.adaptive_centers:
        # recompute centers as the mean of their lists
        # (ivf_flat_build.cuh extend with adaptive_centers=true)
        centers, _ = kmeans_balanced.calc_centers_and_sizes(
            data, labels, index.n_lists
        )

    norms = None
    if _needs_norms(index.metric):
        s32 = storage.astype(jnp.float32)
        norms = jnp.sum(s32 * s32, axis=2)  # [n_lists, cap]

    return dataclasses.replace(
        index,
        centers=centers,
        storage=storage,
        indices=indices,
        list_sizes=list_sizes,
        data_norms=norms,
    )


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3))
def _coarse_margins(queries, centers, metric_val: int, p: int):
    """Normalized coarse-selection margin per query: the top-1 vs top-p
    centroid-distance gap in min-close space, scaled into [0, 1].

    This is the same queries x centers GEMM + select the coarse phase
    of ``_ivf_search`` runs (and ``ivf_pq._pq_search`` mirrors) — the
    difficulty signal is already paid for there; this standalone entry
    exposes it to the serving policy (serve/adaptive.py), which must
    pick the probe rung BEFORE the shape-static search dispatches."""
    metric = DistanceType(metric_val)
    q32 = queries.astype(jnp.float32)
    cdot = dist_dot(q32, centers.T)
    if metric == DistanceType.InnerProduct:
        coarse = -cdot                           # min-close space
    elif metric == DistanceType.CosineExpanded:
        qn = jnp.linalg.norm(q32, axis=1, keepdims=True)
        cn = jnp.linalg.norm(centers, axis=1)
        coarse = 1.0 - cdot / jnp.maximum(qn * cn[None, :], 1e-30)
    else:
        qn2 = jnp.sum(q32 * q32, axis=1, keepdims=True)
        cn2 = jnp.sum(centers * centers, axis=1)
        coarse = qn2 + cn2[None, :] - 2.0 * cdot
    vals, _ = select_k(coarse, p, select_min=True)      # ascending
    d1 = vals[:, 0]
    dp = vals[:, p - 1]
    return jnp.clip((dp - d1) / (jnp.abs(d1) + jnp.abs(dp) + 1e-12),
                    0.0, 1.0)


def coarse_margins(index, queries, p: int = 2) -> jax.Array:
    """Per-query difficulty margin [m] in [0, 1] from the coarse
    quantizer: ~0 means the best ``p`` centroids are indistinguishable
    (hard/ambiguous query — probe wide), large means the query sits
    firmly in one list's basin (easy — few probes recover its
    neighbors). Shared by ivf_flat and ivf_pq (both coarse phases run
    the identical queries x centers selection)."""
    queries = jnp.asarray(queries)
    C = int(index.centers.shape[0])
    if C < 2:
        return jnp.ones((queries.shape[0],), jnp.float32)
    return _coarse_margins(queries, index.centers, int(index.metric),
                           int(max(2, min(int(p), C))))


def adaptive_query_group(m: int, n_probes: int, n_lists: int,
                         base: int) -> int:
    """Pick the per-list query-group size for a batch.

    The bucket table's static bound is total/group + n_lists buckets and
    every bucket costs one [cap, d] list-block fetch (DMA-dominant for
    group ≲ 240 on v5e: block DMA time ≈ matmul time at group ≈ 240), so
    the group never shrinks below a lane-efficient 128 — small batches
    only drop from ``base`` toward 128 to bound the mostly-empty-bucket
    compute waste."""
    from raft_tpu.utils.math import cdiv

    total = m * n_probes
    need = round_up_to_multiple(cdiv(total, max(n_lists, 1)), 8)
    return min(int(base), max(128, need))


def bucketize_pairs(
    probes, m: int, n_probes: int, C: int, group: int, bucket_batch: int
):
    """Group (query, probed-list) pairs into fixed-size per-list buckets.

    The core of the TPU IVF search layout (shared by IVF-Flat and IVF-PQ):
    sort pairs by list id, split each list's pair run into buckets of
    ``group`` queries, and GATHER the dense [n_buckets, group] tables from
    the sorted pair array (element scatters measured 2x the gathers).
    ``n_buckets`` has the static bound total/group + C (each list wastes at
    most one partial bucket), so everything jits with static shapes.

    Returns (bucket_list [nb], bucket_q [nb, group] (-1 = empty slot),
    pair_bucket [total], pair_pos [total], order [total] (the sort
    permutation), total, nb).
    """
    total = m * n_probes
    pair_q = jnp.repeat(jnp.arange(m, dtype=jnp.int32), n_probes)
    pair_l = probes.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(pair_l, stable=True)
    sl = pair_l[order]
    sq = pair_q[order]
    # per-list counts from the sorted keys (binary search beats a
    # 640k-element bincount scatter-add by ~7 ms at SIFT-1M shapes)
    bounds = jnp.searchsorted(sl, jnp.arange(C + 1, dtype=jnp.int32))
    counts = jnp.diff(bounds)
    starts = bounds[:-1]
    rank_in_list = jnp.arange(total) - starts[sl]
    nb_per_list = -(-counts // group)  # ceil
    bucket_start = jnp.cumsum(nb_per_list) - nb_per_list
    pair_bucket = bucket_start[sl] + rank_in_list // group
    pair_pos = rank_in_list % group

    n_buckets = total // group + C + 1  # static upper bound on used buckets
    nb_pad = round_up_to_multiple(n_buckets, bucket_batch)
    # bucket tables by GATHER, not scatter (element scatters measured 2x
    # the equivalent gathers here): each list owns the contiguous bucket
    # range [bucket_start[l], bucket_start[l] + nb_per_list[l]), so a
    # bucket's list id is a binary search and its query slots read the
    # sorted pair array at starts[l] + rel_bucket*group + pos
    b_idx = jnp.arange(nb_pad, dtype=jnp.int32)
    bl = (
        jnp.searchsorted(bucket_start, b_idx, side="right").astype(jnp.int32)
        - 1
    )
    bl = jnp.clip(bl, 0, C - 1)
    rel_b = b_idx - bucket_start[bl]
    src = (starts[bl] + rel_b * group)[:, None] + jnp.arange(
        group, dtype=jnp.int32
    )[None, :]
    valid = src < (starts[bl] + counts[bl])[:, None]
    bucket_q = jnp.where(
        valid, sq[jnp.clip(src, 0, total - 1)], -1
    )
    return bl, bucket_q, pair_bucket, pair_pos, order, total, nb_pad


def unbucketize_merge(
    cand_d, cand_i, pair_bucket, pair_pos, order, total, m, n_probes, kl, k,
    select_min, sentinel, approx: bool = False, recall_target: float = 0.95,
):
    """Map per-bucket top-kl candidates back to query-major order and merge
    each query's n_probes x kl candidates into the final top-k.

    The back-mapping is ONE composed row gather: pair-major slot p reads
    bucket slot ``flat_slot[inv_order[p]]`` (a gather-then-scatter pair
    costs 2x the row traffic; row scatters measured slower still).
    ``approx`` uses the TPU partial-reduce top-k for the final merge —
    k=10 of 1280 candidates is its sweet spot (exact lax.top_k there
    costs ~40 ms at m=10k)."""
    group = cand_d.shape[1]
    flat_slot = pair_bucket * group + pair_pos
    inv = jnp.zeros((total,), jnp.int32).at[order].set(
        jnp.arange(total, dtype=jnp.int32)
    )
    comp = flat_slot[inv]
    pd = cand_d.reshape(-1, kl)[comp]
    pi = cand_i.reshape(-1, kl)[comp]
    return merge_topk(
        pd.reshape(m, n_probes * kl), pi.reshape(m, n_probes * kl), k,
        select_min, approx=approx, recall_target=recall_target,
    )


@functools.partial(jax.jit, static_argnames=("out_of_range",))
def _build_slot_keep(filter_bits, filter_nbits, indices, *,
                     out_of_range: str = "drop"):
    """The list scan's per-slot keep-mask: int32 ``[n_lists, cap]``, 1
    where the slot's id passes the filter (``filter_keep``, under its
    ``filter.keep_mask`` scope). ``filter_nbits`` is traced, so one
    program serves every filter of a word count over an index shape."""
    return filter_keep(filter_bits, filter_nbits, indices,
                       out_of_range=out_of_range).astype(jnp.int32)


# per-slot keep-masks an index keeps: the last few filters' masks
_SLOT_KEEP_MAX = 4


def _slot_keep(filt, index: "Index"):
    """The per-slot keep-mask of ``filt`` over ``index`` (None when
    unfiltered), built once per (bitset, ``out_of_range``, slot-id array
    ``index.indices``) and reused by every later search with the same.
    An entry is used only for the very words array and ``n_bits`` it was
    built from: ``Bitset.set``/``flip``/``resize`` replace the words, so
    a mutated bitset misses, as does an extended index (a new
    ``indices``). The masks live on the index, the last
    ``_SLOT_KEEP_MAX`` filters' at most, and go with it. Counts
    ``filter.slot_keep_hits`` / ``filter.slot_keep_misses``; a build runs
    under the ``filter.slot_keep_build`` span."""
    bits = getattr(filt, "bitset", None)
    if bits is None:
        return None
    oor = getattr(filt, "out_of_range", "drop")
    indices = index.indices
    if isinstance(bits.bits, jax.core.Tracer) or isinstance(
            indices, jax.core.Tracer):
        # under an outer jit: part of the caller's program, never cached
        return _build_slot_keep(bits.bits, int(bits.n_bits), indices,
                                out_of_range=oor)
    key = (id(bits), oor)
    masks = getattr(index, "_slot_keep_masks", {})
    hit = masks.get(key)
    if (hit is not None and hit[0] is bits.bits
            and hit[1] == int(bits.n_bits) and hit[2] is indices):
        obs.counter("filter.slot_keep_hits")
        return hit[3]
    obs.counter("filter.slot_keep_misses")
    with obs.span("filter.slot_keep_build", n_bits=int(bits.n_bits),
                  slots=int(indices.size)):
        mask = _build_slot_keep(bits.bits, int(bits.n_bits), indices,
                                out_of_range=oor)
    # copied and rebound, never changed in place: a concurrent search
    # reads the old dict or the new one, and a racing build costs one
    # more miss at worst
    live = {k: v for k, v in masks.items()
            if k != key and v[2] is indices}
    live[key] = (bits.bits, int(bits.n_bits), indices, mask)
    while len(live) > _SLOT_KEEP_MAX:
        live.pop(next(iter(live)))
    index._slot_keep_masks = live
    return mask


@functools.partial(
    jax.jit,
    static_argnums=(5, 6, 7, 8, 9, 10, 11, 12),
    static_argnames=("scan_impl",),
)
def _ivf_search(
    queries,
    centers,
    storage,
    indices,
    list_sizes,
    k: int,
    n_probes: int,
    metric_val: int,
    group: int,
    bucket_batch: int,
    compute_dtype: str = "bf16",
    local_recall_target: float = 0.95,
    merge_recall_target: float = 1.0,
    data_norms=None,
    slot_keep=None,
    *,
    scan_impl: str = "xla",
):
    """The search program. ``slot_keep`` is the per-slot keep-mask
    (int32 ``[n_lists, cap]``, nonzero = the slot's id passes the
    filter; :func:`_slot_keep`), or None for an unfiltered search."""
    metric = DistanceType(metric_val)
    select_min = is_min_close(metric)
    C, cap, d = storage.shape
    q32 = queries.astype(jnp.float32)
    m = q32.shape[0]
    sentinel = sentinel_for(metric, jnp.float32)

    # ---- coarse phase: queries x centers GEMM + select n_probes ----------
    # (reference ivf_flat_search-inl.cuh:90-130). Each stage runs under a
    # named scope, so the device trace names its ops after the stage.
    with jax.named_scope("ivf.coarse"):
        cdot = dist_dot(q32, centers.T)
        if metric == DistanceType.InnerProduct:
            coarse = cdot
        elif metric == DistanceType.CosineExpanded:
            qn = jnp.linalg.norm(q32, axis=1, keepdims=True)
            cn = jnp.linalg.norm(centers, axis=1)
            coarse = 1.0 - cdot / jnp.maximum(qn * cn[None, :], 1e-30)
        else:
            qn2 = jnp.sum(q32 * q32, axis=1, keepdims=True)
            cn2 = jnp.sum(centers * centers, axis=1)
            coarse = qn2 + cn2[None, :] - 2.0 * cdot
        _, probes = select_k(coarse, n_probes,
                             select_min=select_min)          # [m, np]

    # ---- bucketize (query, probe) pairs by list --------------------------
    with jax.named_scope("ivf.bucketize"):
        (bucket_list, bucket_q, pair_bucket, pair_pos, order, total,
         nb_pad) = bucketize_pairs(probes, m, n_probes, C, group,
                                   bucket_batch)

    # ---- scan list blocks: one MXU matmul per (group x list) -------------
    # per-list top-k cannot exceed the list capacity; the final merge over
    # n_probes lists restores k (requires n_probes * cap >= k)
    kl = min(k, cap)
    qnorm = jnp.sum(q32 * q32, axis=1)
    qlen = jnp.sqrt(qnorm)

    mm = jnp.bfloat16 if compute_dtype == "bf16" else jnp.float32

    def body(_, inp):
        bl, bq = inp  # [bb], [bb, group]
        block = storage[bl].astype(mm)  # [bb, cap, d] contiguous
        ids = indices[bl]  # [bb, cap]
        sizes = list_sizes[bl]  # [bb]
        qsafe = jnp.maximum(bq, 0)
        qv = q32[qsafe].astype(mm)  # [bb, group, d]
        dots = jnp.einsum(
            "bgd,bcd->bgc", qv, block,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        if metric == DistanceType.InnerProduct:
            dist = dots
        elif metric == DistanceType.CosineExpanded:
            pn = jnp.sqrt(jnp.maximum(
                data_norms[bl] if data_norms is not None
                else jnp.sum(block * block, axis=2), 1e-30))
            dist = 1.0 - dots / jnp.maximum(
                qlen[qsafe][:, :, None] * pn[:, None, :], 1e-30)
        else:
            pn2 = (data_norms[bl] if data_norms is not None
                   else jnp.sum(block * block, axis=2))
            dist = jnp.maximum(
                qnorm[qsafe][:, :, None] + pn2[:, None, :] - 2.0 * dots, 0.0)

        col_ok = (jnp.arange(cap)[None, :] < sizes[:, None])[:, None, :]
        valid = col_ok & (bq >= 0)[:, :, None]
        if slot_keep is not None:
            valid = valid & (slot_keep[bl] != 0)[:, None, :]
        dist = jnp.where(valid, dist, sentinel)
        ld, lsel = merge_topk(
            dist, jnp.broadcast_to(ids[:, None, :], dist.shape), kl, select_min,
            approx=local_recall_target < 1.0,
            recall_target=local_recall_target,
        )  # [bb, group, kl]
        # flattened minor dims: the scan's stacked output otherwise pads
        # kl to 128 lanes (12.8x HBM at k=10)
        return None, (ld.reshape(ld.shape[0], -1),
                      lsel.reshape(lsel.shape[0], -1))

    with jax.named_scope("ivf.scan"):
        if scan_impl.startswith("pallas"):
            # fused Pallas kernel: list blocks DMA'd by scalar-prefetch
            # index, distances + top-k stay in VMEM (raft_tpu.ops.ivf_scan);
            # k <= 256 per list (the R-deep binned extraction's capacity —
            # the reference's fused path similarly caps at
            # kMaxCapacity=256, ivf_pq_search.cuh:439 manage_local_topk)
            from raft_tpu.ops import ivf_scan

            kl = min(kl, 256)
            qsafe_b = jnp.maximum(bucket_q, 0)
            qv = q32[qsafe_b].astype(mm)                     # [nb, G, d]
            if metric == DistanceType.InnerProduct:
                mk, qaux, pn2 = ivf_scan.IP, None, None
            elif metric == DistanceType.CosineExpanded:
                mk, qaux = ivf_scan.COSINE, qlen[qsafe_b]
                pn2 = (data_norms if data_norms is not None else
                       jnp.sum(storage.astype(jnp.float32) ** 2, axis=2))
            else:
                mk, qaux = ivf_scan.L2, qnorm[qsafe_b]
                pn2 = (data_norms if data_norms is not None else
                       jnp.sum(storage.astype(jnp.float32) ** 2, axis=2))
            out_d, cand_i = ivf_scan.fused_list_scan_topk(
                storage, indices, list_sizes, bucket_list, qv, qaux, pn2,
                slot_keep, k=kl, metric_kind=mk,
                approx=local_recall_target < 1.0,
                recall_target=float(local_recall_target),
                interpret=scan_impl == "pallas_interpret",
            )                                                # ids in-kernel
            if metric == DistanceType.InnerProduct:
                cand_d = -out_d                      # min-space -> score
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

    # ---- un-bucketize + final merge (search-inl.cuh:194) -----------------
    # candidate width comes off the scan's output: the kernel's fold
    # extraction arm emits its R*128 lane-stack buffer instead of kl
    with jax.named_scope("ivf.merge"):
        out_d, out_i = unbucketize_merge(
            cand_d, cand_i, pair_bucket, pair_pos, order, total, m,
            n_probes, int(cand_d.shape[2]), k, select_min, sentinel,
            approx=merge_recall_target < 1.0,
            recall_target=merge_recall_target,
        )
        # fewer than k valid candidates in the probed lists: report id -1,
        # not whatever id rode along at sentinel distance (the documented
        # contract; refine would otherwise resurrect filtered-out points)
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
    """Approximate k-NN search (reference ivf_flat-inl.cuh:516).

    Returns (distances [m, k], source ids [m, k]); ids are -1 where fewer
    than k valid candidates were found in the probed lists.
    """
    queries = jnp.asarray(queries)
    n_probes = int(min(search_params.n_probes, index.n_lists))
    cap = index.storage.shape[1]
    if cap == 0:
        raise ValueError("index is empty — build with add_data_on_build or extend")
    if k > n_probes * cap:
        raise ValueError(
            f"k={k} exceeds n_probes*list_capacity={n_probes * cap}"
        )
    with obs.entry_span("search", "ivf_flat",
                        queries=int(queries.shape[0]), k=int(k),
                        n_probes=n_probes) as _sp:
        scan_impl = _resolve_scan_impl(
            str(search_params.scan_impl), cap, min(int(k), cap),
            approx=float(search_params.local_recall_target) < 1.0,
        )
        _sp.set(scan_impl=scan_impl)
        if scan_impl.startswith("pallas") and k > n_probes * min(cap, 256):
            raise ValueError(
                f"k={k} exceeds the fused kernel's candidate pool "
                f"n_probes*min(cap,256)={n_probes * min(cap, 256)}; raise "
                "n_probes or use scan_impl='xla'"
            )
        group = adaptive_query_group(
            int(queries.shape[0]), n_probes, index.n_lists,
            int(search_params.query_group),
        )
        # out_of_range is applied per slot ("keep" admits ids past the
        # filter, docs/serving.md §5)
        slot_keep = _slot_keep(as_filter(prefilter), index)
        return _ivf_search(
            queries,
            index.centers,
            index.storage,
            index.indices,
            index.list_sizes,
            int(k),
            n_probes,
            int(index.metric),
            group,
            int(search_params.bucket_batch),
            str(search_params.compute_dtype),
            float(search_params.local_recall_target),
            float(search_params.merge_recall_target),
            index.data_norms,
            slot_keep,
            scan_impl=scan_impl,
        )


def _resolve_scan_impl(requested: str, cap: int, kl: int,
                       approx: bool = True) -> str:
    """Pick the scan backend through the per-backend dispatch table
    (``tuning.choose("ivf_scan", ...)`` — docs/dispatch_tuning.md). The
    fused Pallas kernel is only a candidate on TPU with a lane-aligned
    list capacity; the analytic fallback (table miss /
    RAFT_TPU_TUNING=off) additionally requires k <= 64: the kernel's
    R-deep binned extraction supports k <= 256 (force with
    scan_impl="pallas"), but the k-pass unrolled extraction measured
    ~7x slower end-to-end than the XLA path at k=130 (r4 v5e; CAGRA
    self-search, SIFT-100k). Everything else runs the XLA bucketized
    scan."""
    if requested != "auto":
        return requested
    from raft_tpu import tuning

    on_tpu = tuning.backend_name() == "tpu"
    # kl <= 256 is structural (the kernel's per-list extraction budget,
    # the reference's kMaxCapacity analog) — beyond it pallas is not a
    # candidate no matter what the table interpolates
    pallas_ok = on_tpu and cap % 128 == 0 and kl <= 256
    candidates = ["xla"] + (["pallas"] if pallas_ok else [])
    analytic = "pallas" if pallas_ok and kl <= 64 else "xla"
    return tuning.choose(
        "ivf_scan", {"cap": cap, "k": kl, "approx": bool(approx)},
        candidates, analytic,
    )


# ---------------------------------------------------------------------------
# helpers (reference ivf_flat_helpers.cuh / codepacker)
# ---------------------------------------------------------------------------


def get_list_data(index: Index, label: int) -> Tuple[np.ndarray, np.ndarray]:
    """Unpack one list's (vectors, source ids) — codepacker analog."""
    size = int(index.list_sizes[label])
    vecs = np.asarray(index.storage[label, :size])
    ids = np.asarray(index.indices[label, :size])
    return vecs, ids


def reconstruct_dataset(index: Index) -> Tuple[np.ndarray, np.ndarray]:
    """All (vectors, source ids) in storage order."""
    flat = np.asarray(index.storage).reshape(-1, index.dim)
    ids = np.asarray(index.indices).reshape(-1)
    valid = ids >= 0
    return flat[valid], ids[valid]


# ---------------------------------------------------------------------------
# serialization (reference ivf_flat_serialize.cuh)
# ---------------------------------------------------------------------------


def save(path: str, index: Index) -> None:
    storage = index.storage
    bf16 = storage.dtype == jnp.bfloat16
    if bf16:
        # the .npy container stays pure-numpy for interop (the reference
        # serializer writes standard npy, mdspan_numpy_serializer.hpp);
        # ml_dtypes bfloat16 round-trips as an opaque V2 dtype that
        # numpy/jax reject on load, so store bf16 widened to f32 (exact)
        # and narrow back on load via the recorded storage_dtype
        storage = storage.astype(jnp.float32)
    arrays = {
        "centers": np.asarray(index.centers),
        "storage": np.asarray(storage),
        "indices": np.asarray(index.indices),
        "list_sizes": np.asarray(index.list_sizes),
    }
    if index.data_norms is not None:
        arrays["data_norms"] = np.asarray(index.data_norms)
    write_index_file(
        path,
        "ivf_flat",
        _SERIAL_VERSION,
        {
            "metric": int(index.metric),
            "metric_arg": index.metric_arg,
            "adaptive_centers": index.adaptive_centers,
            "storage_dtype": "bf16" if bf16 else str(index.storage.dtype),
        },
        arrays,
    )


def load(path: str) -> Index:
    _, meta, arrays = read_index_file(path, "ivf_flat")
    storage = jnp.asarray(arrays["storage"])
    if meta.get("storage_dtype") == "bf16":
        storage = storage.astype(jnp.bfloat16)
    return Index(
        centers=jnp.asarray(arrays["centers"]),
        storage=storage,
        indices=jnp.asarray(arrays["indices"]),
        list_sizes=jnp.asarray(arrays["list_sizes"]),
        metric=DistanceType(meta["metric"]),
        metric_arg=meta["metric_arg"],
        adaptive_centers=bool(meta["adaptive_centers"]),
        data_norms=(
            jnp.asarray(arrays["data_norms"]) if "data_norms" in arrays else None
        ),
    )
