"""Shared ANN scaffolding: param structs, sample filters, search utilities.

Analog of the reference's neighbors common layer (SURVEY.md §2.9):
ann_types.hpp (index_params/search_params bases),
sample_filter_types.hpp (none/bitset filters), and the top-k merge used by
multi-part searches (detail/knn_merge_parts.cuh).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core.bitset import Bitset
from raft_tpu.distance.types import DistanceType, is_min_close, resolve_metric


@dataclasses.dataclass
class IndexParams:
    """Base build params (reference neighbors/ann_types.hpp:32-46)."""

    metric: DistanceType = DistanceType.L2Expanded
    metric_arg: float = 2.0
    add_data_on_build: bool = True

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)


@dataclasses.dataclass
class SearchParams:
    """Base search params (reference neighbors/ann_types.hpp:48)."""


# --------------------------------------------------------------------------
# Sample filters (reference sample_filter_types.hpp)
# --------------------------------------------------------------------------


class NoneSampleFilter:
    """Accept everything (reference none_ivf_sample_filter:27)."""

    def mask(self, sample_ids: jax.Array) -> jax.Array:
        return jnp.ones(sample_ids.shape, jnp.bool_)


#: valid ``out_of_range`` modes for bitset filters (docs/serving.md §5):
#: ``"drop"`` — a sample id beyond the filter's n_bits is rejected (the
#: historical behavior; right for allow-lists, where absence means
#: not-allowed); ``"keep"`` — an out-of-range id is accepted (right for
#: tombstone/deny-derived keep-masks over an index that was *extended*
#: after the filter was built: new rows were never deleted, so they
#: must default to kept).
OUT_OF_RANGE_MODES = ("drop", "keep")


class BitsetFilter:
    """Keep samples whose bit is set (reference bitset_filter).

    ``out_of_range`` picks the fate of sample ids ``>= bitset.n_bits``
    (see :data:`OUT_OF_RANGE_MODES`). Negative ids (the library-wide
    no-neighbor padding) are always rejected in either mode.
    """

    def __init__(self, bitset: Bitset, out_of_range: str = "drop"):
        if out_of_range not in OUT_OF_RANGE_MODES:
            raise ValueError(
                f"out_of_range must be one of {OUT_OF_RANGE_MODES}, "
                f"got {out_of_range!r}"
            )
        self.bitset = bitset
        self.out_of_range = out_of_range

    def mask(self, sample_ids: jax.Array) -> jax.Array:
        return filter_keep(self.bitset.bits, self.bitset.n_bits,
                           sample_ids, out_of_range=self.out_of_range)


def as_filter(f) -> NoneSampleFilter | BitsetFilter:
    if f is None:
        return NoneSampleFilter()
    if isinstance(f, Bitset):
        return BitsetFilter(f)
    return f


def filter_keep(filter_bits, filter_nbits: int, sample_ids,
                out_of_range: str = "drop"):
    """Jit-safe keep-mask for a raw bitset: True where the sample id's bit
    is set. The single implementation behind BitsetFilter and the IVF scan
    kernels. ``out_of_range`` (static) decides ids ``>= filter_nbits``:
    ``"drop"`` rejects them (allow-list semantics), ``"keep"`` accepts
    them (tombstone semantics over an extended index — new rows were
    never deleted). Negative ids are always rejected. Its device ops run
    under the named scope ``filter.keep_mask``."""
    with jax.named_scope("filter.keep_mask"):
        safe = jnp.clip(sample_ids, 0, filter_nbits - 1)
        tested = Bitset.test_bits(filter_bits, safe)
        in_range = sample_ids < filter_nbits
        if out_of_range == "keep":
            tested = tested | ~in_range
            return tested & (sample_ids >= 0)
        return tested & in_range & (sample_ids >= 0)


def resolve_filter_bits(filt, id_bound):
    """Resolve a filter's bitset against an index whose valid ids live in
    ``[0, id_bound)``, honoring its ``out_of_range`` mode for kernels
    that only implement "drop".

    Returns the :class:`~raft_tpu.core.bitset.Bitset` to hand to a scan
    kernel, or ``None`` for an unfiltered search. A ``"keep"``-mode
    filter narrower than ``id_bound`` is *materialized*: resized (on a
    copy) with new bits set, so drop-semantics kernels behave as keep
    without threading another static through every scan. Only meaningful
    when ids are the default contiguous row ids (true for every build in
    this repo unless the caller passed custom ``new_ids`` to extend).

    The materialized bitset is cached on the filter object keyed by
    ``(id_bound, Bitset._version)``, so N filtered searches with one
    filter pay the resize's device ops (copy + pad + set) once, not N
    times; an in-place mutation of the underlying bitset bumps
    ``_version`` and invalidates the entry (the same keying the serve
    engine uses for its composed tombstone filters).

    ``id_bound`` may be a callable evaluated only for "keep"-mode
    filters: ``Index.size`` is a device reduction, and forcing it to a
    Python int on the no-filter/drop path would concretize a tracer when
    the search entry runs under an outer ``jit`` (the GL002 hazard the
    jaxpr audit traces for).
    """
    bits = getattr(filt, "bitset", None)
    if bits is None:
        return None
    if getattr(filt, "out_of_range", "drop") != "keep":
        return bits
    bound = int(id_bound() if callable(id_bound) else id_bound)
    if bits.n_bits >= bound:
        return bits
    key = (bound, getattr(bits, "_version", 0))
    cached = getattr(filt, "_materialized_keep", None)
    if cached is not None and cached[0] == key:
        return cached[1]
    resized = bits.copy().resize(bound, default=True)
    try:
        filt._materialized_keep = (key, resized)
    except AttributeError:      # slotted/frozen filter: serve correct,
        pass                    # just uncached
    return resized


# --------------------------------------------------------------------------
# Sentinels and top-k merge
# --------------------------------------------------------------------------


def sentinel_for(metric: DistanceType, dtype=jnp.float32):
    """Worst-possible distance for masking invalid candidates."""
    return jnp.asarray(jnp.inf if is_min_close(metric) else -jnp.inf, dtype)


def merge_topk(
    dists: jax.Array,
    idxs: jax.Array,
    k: int,
    select_min: bool = True,
    approx: bool = False,
    recall_target: float = 0.95,
) -> Tuple[jax.Array, jax.Array]:
    """Merge candidate lists along the last axis into a top-k.

    ``dists``/``idxs``: [..., c] with c >= k. Returns ([..., k], [..., k])
    sorted best-first. This is the XLA analog of the reference's warp-queue
    ``knn_merge_parts`` merge kernel (detail/knn_merge_parts.cuh:33,140).

    ``approx=True`` uses the TPU-optimized ``lax.approx_min_k`` /
    ``approx_max_k`` (the TPU-KNN partial-reduce op) — dramatically faster
    than a full sort for k << c, at a configurable ``recall_target``. Use it
    for inner candidate-generation stages whose output feeds an exact merge.

    The exact arm routes through ``matrix.select_k``, so large-k merges
    (k > 256, c >> k — CAGRA-build candidate selection, cross-probe
    merges at high refine ratios) can dispatch to the compacting
    tournament instead of ``lax.top_k``'s full-row sort (the reference
    serves this regime with radix select,
    matrix/detail/select_radix.cuh:231). The arm is picked from the
    per-backend dispatch table under the dedicated ``merge_topk`` op key
    (merge pools are wider-batch / shorter-row than raw selects, so they
    get their own measured crossover); a table miss defers to
    ``select_k``'s own dispatch. Tournament rows with fewer than k
    finite entries return id -1 — the library-wide no-neighbor
    convention callers already mask on.
    """
    if approx and k < dists.shape[-1]:
        fn = jax.lax.approx_min_k if select_min else jax.lax.approx_max_k
        vals, sel = fn(dists, k, recall_target=recall_target)
        return vals, jnp.take_along_axis(idxs, sel, axis=-1)
    from raft_tpu import obs
    from raft_tpu.matrix.select_k import dispatch_select_impl, select_k

    shape = dists.shape
    reshaped = dists.ndim != 2
    if reshaped:
        dists = dists.reshape(-1, shape[-1])
        idxs = idxs.reshape(-1, shape[-1])
    impl = dispatch_select_impl(
        int(dists.shape[0]), int(dists.shape[-1]), int(k), dists.dtype,
        op="merge_topk",
        fallback="auto",  # miss -> select_k's own (table-driven) dispatch
    )
    # trace-time span (merge_topk runs under the callers' jits): compile
    # attribution per chosen arm, silent on cached steady-state dispatch
    with obs.span("merge_topk", impl=impl, c=int(dists.shape[-1]),
                  k=int(k)):
        vals, out_i = select_k(dists, k, in_idx=idxs,
                               select_min=select_min, impl=impl)
    if reshaped:
        vals = vals.reshape(*shape[:-1], k)
        out_i = out_i.reshape(*shape[:-1], k)
    return vals, out_i


def knn_merge_parts(
    part_dists: jax.Array,
    part_idxs: jax.Array,
    k: Optional[int] = None,
    select_min: bool = True,
    translations=None,
) -> Tuple[jax.Array, jax.Array]:
    """Merge per-part KNN results into a global top-k.

    ``part_dists``/``part_idxs``: [n_parts, n_queries, k_part]. Optional
    ``translations`` [n_parts] are added to each part's indices (the
    reference uses them to offset shard-local ids —
    detail/knn_merge_parts.cuh:140).
    """
    n_parts, n_q, k_part = part_dists.shape
    k = k if k is not None else k_part
    if translations is not None:
        t = jnp.asarray(translations).reshape(n_parts, 1, 1)
        part_idxs = part_idxs + t.astype(part_idxs.dtype)
    flat_d = jnp.transpose(part_dists, (1, 0, 2)).reshape(n_q, n_parts * k_part)
    flat_i = jnp.transpose(part_idxs, (1, 0, 2)).reshape(n_q, n_parts * k_part)
    return merge_topk(flat_d, flat_i, k, select_min)
