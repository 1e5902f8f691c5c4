"""graft-plan compiler: bind a declarative :class:`~raft_tpu.plan.ir.Plan`
to an index and produce one executable program per (bucket, k, rung).

The compiled program is a closure pipeline over the SAME tuned, jitted
entry points the hand-wired pipelines called (``ivf_pq.search`` /
``_refine_slots`` / ``_refine_slots_codes`` / ``RerankSource.prepare``
+ ``score`` / ``brute_force.search`` / ``merge_topk`` / ...), so two
properties hold *by construction* rather than by test luck:

* **bitwise identity** — a compiled canonical plan runs the exact same
  kernel sequence with the exact same arguments as the legacy dispatch
  it replaced (tests/test_plan.py pins the matrix);
* **zero steady-state retraces** — compilation itself never calls
  ``jax.jit``; every device program belongs to an already-warmed entry
  point on ``serve.TRACKED_JITS``, so serve warmup walks compiled
  plans exactly like today's ladder and the GL007 ``_cache_size`` hook
  stays flat (docs/plans.md §4).

Each node's ``op`` key is the dispatch-table name of its kernel
family; the underlying ops keep calling ``tuning.choose`` per node, so
the dispatch table keeps picking kernels stage by stage.  Executors
are looked up in :data:`OPS` — adding a workload is adding an op (and
a canonical plan), not a new code path (ROADMAP item 8).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import obs
from raft_tpu.plan.ir import (
    CANDIDATE_STAGES,
    Node,
    Plan,
    PlanError,
    validate,
)

__all__ = ["CompiledPlan", "compile_plan", "OPS", "register_op"]


class _Ctx:
    """Per-execution scratch: node values, runtime operands, and the
    stage-stat side channel the rerank observability block reads.
    One instance per call — compiled plans are stateless and safe to
    share across serving threads."""

    __slots__ = ("queries", "prefilter", "arrays", "extra", "values",
                 "stats")

    def __init__(self, queries, prefilter, arrays, extra):
        self.queries = queries
        self.prefilter = prefilter
        self.arrays = arrays
        self.extra = extra or {}
        self.values: Dict[str, object] = {}
        self.stats: Dict[str, object] = {}


@dataclasses.dataclass
class _Binds:
    """Everything a plan needs beyond the IR: the index, resolved
    search params, widths, and optional rerank source — bound once at
    compile, shared by every execution."""

    index: object
    k: int
    bucket: Optional[int]
    rung: object
    search_params: object
    refine_ratio: int
    source: object            # RerankSource or None
    raw_dev: object           # device raw rows (serve refine) or None
    memo: Dict[str, object]   # cross-variant shared derived arrays
    extra: Dict[str, object]  # op-family statics (sharded, hybrid, ...)

    def rows(self) -> int:
        idx = self.index
        size = getattr(idx, "size", None)
        if size is not None:
            return int(size)
        return int(idx.dataset.shape[0])


# (stage, op) -> builder(node, binds, plan) -> executor(ctx) -> value
OPS: Dict[Tuple[str, str], Callable] = {}


def register_op(stage: str, op: str):
    def deco(fn):
        OPS[(stage, op)] = fn
        return fn
    return deco


def _filter_input(ctx: _Ctx, node: Node, by_id: Mapping[str, Node]):
    """The value of this node's filter input, if it declares one; a
    plan without an explicit filter node falls back to the call-time
    prefilter untouched (identical composition either way)."""
    for src in node.inputs:
        if by_id[src].stage == "filter":
            return ctx.values[src]
    return ctx.prefilter


def _candidate_inputs(ctx: _Ctx, node: Node, by_id: Mapping[str, Node]):
    return [ctx.values[src] for src in node.inputs
            if by_id[src].stage in CANDIDATE_STAGES]


def _resolve_width(node: Node, binds: _Binds) -> int:
    """A node's candidate width: literal, or one of the symbolic
    widths (ir.WIDTH_SYMBOLS) resolved against the compile bindings —
    each formula byte-identical to the hand-wired pipeline it came
    from."""
    w = node.params.get("width", "k")
    if isinstance(w, int):
        return int(w)
    if w == "k":
        return int(binds.k)
    if w == "shortlist":
        from raft_tpu.neighbors import ivf_pq

        return ivf_pq.refined_shortlist_width(
            binds.search_params, binds.index, int(binds.k),
            int(binds.refine_ratio))
    if w == "refine":
        # serve's raw-refine over-fetch (engine._Handle.search_main)
        return min(int(binds.k) * int(binds.refine_ratio), binds.rows())
    if w == "fuse":
        expand = int(node.params.get("expand",
                                     binds.extra.get("fuse_expand", 4)))
        return min(binds.rows(), max(int(binds.k) * expand, 16))
    raise PlanError(f"node {node.id!r}: unresolvable width {w!r}")


# ---------------------------------------------------------------------------
# filter stage
# ---------------------------------------------------------------------------

@register_op("filter", "prefilter")
def _build_prefilter(node, binds, plan):
    """The user/tombstone prefilter, passed through untouched — the
    composition into keep-bits happens inside the consuming scan
    (resolve_filter_bits caching idiom)."""
    def run(ctx):
        return ctx.prefilter
    return run


@register_op("filter", "slot_prefilter")
def _build_slot_prefilter(node, binds, plan):
    """Translate the stored-id prefilter into SLOT space for a
    slot-substituted first stage (ivf_pq._slot_prefilter, with its
    long-lived-bitset cache intact)."""
    from raft_tpu.neighbors import ivf_pq

    index = binds.index

    def run(ctx):
        return ivf_pq._slot_prefilter(index, ctx.prefilter)
    return run


# ---------------------------------------------------------------------------
# coarse / probe — annotation nodes, fused into the scan kernel
# ---------------------------------------------------------------------------

def _build_fused_marker(node, binds, plan):
    """Coarse scan and probe-rung selection live INSIDE the scan
    kernels (one traced program — splitting them out would retrace
    per stage and double-pay the centers matmul). The IR still spells
    them as nodes so plans are honest about the pipeline and
    graft-lint/graft-kern can audit the DAG as a unit; the compiler
    fuses them: the marker contributes nothing at runtime, and the
    scan consumes its effective n_probes from the compile-time rung
    binding instead."""
    def run(ctx):
        return None
    return run


register_op("coarse", "ivf.centers")(_build_fused_marker)
register_op("probe", "rung")(_build_fused_marker)


# ---------------------------------------------------------------------------
# scan stage
# ---------------------------------------------------------------------------

@register_op("scan", "brute_force.search")
def _build_bf_scan(node, binds, plan):
    from raft_tpu.neighbors import brute_force

    index = binds.index
    width = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        return brute_force.search(index, ctx.queries, width,
                                  prefilter=_filter_input(ctx, node,
                                                          by_id))
    return run


@register_op("scan", "ivf_flat.search")
def _build_ivf_flat_scan(node, binds, plan):
    from raft_tpu.neighbors import ivf_flat

    index, sp = binds.index, binds.search_params
    width = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        return ivf_flat.search(sp, index, ctx.queries, width,
                               prefilter=_filter_input(ctx, node, by_id))
    return run


@register_op("scan", "cagra.search")
def _build_cagra_scan(node, binds, plan):
    from raft_tpu.neighbors import cagra

    index, sp = binds.index, binds.search_params
    width = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        return cagra.search(sp, index, ctx.queries, width,
                            prefilter=_filter_input(ctx, node, by_id))
    return run


@register_op("scan", "ivf_pq.search")
def _build_ivf_pq_scan(node, binds, plan):
    """Plain IVF-PQ scan (coarse + probe + list scan in one traced
    program); also the refined pipeline's first stage when the rerank
    source is an explicit dataset (stage 1 then returns global ids —
    no slot indirection)."""
    from raft_tpu.neighbors import ivf_pq

    index, sp = binds.index, binds.search_params
    width = _resolve_width(node, binds)
    first_stage = bool(node.params.get("first_stage", False))
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        filt = _filter_input(ctx, node, by_id)
        if not first_stage:
            return ivf_pq.search(sp, index, ctx.queries, width,
                                 prefilter=filt)
        with obs.span("ivf_pq.first_stage", kc=width) as s1:
            d, ids = ivf_pq.search(sp, index, ctx.queries, width,
                                   prefilter=filt)
            if obs.enabled():
                s1.sync(ids)
        ctx.stats["shortlist"] = ids
        ctx.stats["kc"] = width
        ctx.stats["first_stage_ms"] = getattr(s1, "device_ms", None)
        return d, ids
    return run


@register_op("scan", "ivf_pq.first_stage")
def _build_ivf_pq_first_stage(node, binds, plan):
    """Slot-substituted first stage of the cacheless refined pipeline:
    the scan emits WHERE each candidate lives (flat slot) instead of
    its id, so the rerank can decode it straight from the cache/codes
    without an O(n_rows) inverse map (ivf_pq._slot_indices)."""
    from raft_tpu.neighbors import ivf_pq

    index, sp = binds.index, binds.search_params
    width = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}

    def slot_index():
        # shared across this handle's compiled (k, rung) variants —
        # the substituted [C, cap] block is identical for all of them
        cached = binds.memo.get("slot_index")
        if cached is None:
            cached = dataclasses.replace(
                index, indices=ivf_pq._slot_indices(index.indices))
            binds.memo["slot_index"] = cached
        return cached

    def run(ctx):
        slot_filt = _filter_input(ctx, node, by_id)
        with obs.span("ivf_pq.first_stage", kc=width) as s1:
            d, slots = ivf_pq.search(sp, slot_index(), ctx.queries,
                                     width, prefilter=slot_filt)
            if obs.enabled():
                s1.sync(slots)
        ctx.stats["shortlist"] = slots
        ctx.stats["kc"] = width
        ctx.stats["first_stage_ms"] = getattr(s1, "device_ms", None)
        return d, slots
    return run


# ---------------------------------------------------------------------------
# fetch / rerank stages — the refined pipeline's tail
# ---------------------------------------------------------------------------

def _emit_rerank_obs(ctx: _Ctx, m: int, source: str, row_bytes: int,
                     fetch_info=None) -> None:
    """The rerank-stage observability block (docs/observability.md):
    bytes ACTUALLY moved at fidelity (valid slots; unique rows on the
    tiered path) + the first_stage/fetch/rerank latency split —
    byte-identical metric names/labels to the hand-wired
    search_refined emission so dashboards survive the re-plumb."""
    if not obs.enabled():
        return
    shortlist = ctx.stats.get("shortlist")
    if source == "host" and fetch_info is not None:
        valid_slots = int(fetch_info.valid_slots)
        fetched_rows = int(fetch_info.unique_rows)
    else:
        valid_slots = int(np.count_nonzero(np.asarray(shortlist) >= 0)) \
            if shortlist is not None else 0
        fetched_rows = valid_slots
    obs.counter("rerank.queries_total", m, algo="ivf_pq")
    obs.counter("rerank.shortlist_rows", valid_slots, algo="ivf_pq")
    obs.counter("rerank.bytes_fetched_total", fetched_rows * row_bytes,
                source=source)
    obs.gauge("rerank.bytes_per_query",
              fetched_rows * row_bytes / max(m, 1), source=source)
    if ctx.stats.get("first_stage_ms") is not None:
        obs.observe("rerank.stage_ms", ctx.stats["first_stage_ms"],
                    stage="first_stage")
    if ctx.stats.get("fetch_ms") is not None:
        obs.observe("rerank.stage_ms", ctx.stats["fetch_ms"],
                    stage="fetch")
    if ctx.stats.get("rerank_ms") is not None:
        obs.observe("rerank.stage_ms", ctx.stats["rerank_ms"],
                    stage="rerank")


@register_op("fetch", "tiered.prepare")
def _build_tiered_prepare(node, binds, plan):
    """The host-gather half of the tiered rerank: shortlist sync +
    dedup + (mmap) read + upload dispatch, timed under its own span
    (the latency graft-flow overlaps on the streaming path)."""
    src = binds.source
    if src is None:
        raise PlanError(f"node {node.id!r}: fetch needs a bound "
                        f"rerank source (compile(source=...))")
    by_id = {n.id: n for n in plan.nodes}
    label = "host" if getattr(src, "kind", "") == "host" else "dataset"

    def run(ctx):
        _, ids1 = _candidate_inputs(ctx, node, by_id)[0]
        with obs.span("ivf_pq.fetch", source=label) as sf:
            prepared = src.prepare(ctx.queries, ids1)
        # fetch is HOST work (no device compute to sync on): wall ms
        ctx.stats["fetch_ms"] = getattr(sf, "ms", None)
        ctx.stats["shortlist"] = ids1
        return prepared
    return run


@register_op("rerank", "tiered.score")
def _build_tiered_score(node, binds, plan):
    """Exact rerank from the bound RerankSource over a prepared
    shortlist (HostArraySource hot-cache path or DeviceSource full
    upload — bitwise-identical scoring either way)."""
    src = binds.source
    if src is None:
        raise PlanError(f"node {node.id!r}: rerank source not bound")
    index = binds.index
    k = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}
    label = "host" if getattr(src, "kind", "") == "host" else "dataset"

    def run(ctx):
        prepared = None
        for s in node.inputs:
            if by_id[s].stage == "fetch":
                prepared = ctx.values[s]
        with obs.span("ivf_pq.rerank", source=label) as s2:
            d, ids, fetch = src.score(prepared, int(k), index.metric)
            if obs.enabled():
                s2.sync(ids)
        ctx.stats["rerank_ms"] = getattr(s2, "device_ms", None)
        _emit_rerank_obs(ctx, int(ctx.queries.shape[0]), label,
                         int(src.row_bytes), fetch_info=fetch)
        return d, ids
    return run


@register_op("rerank", "ivf_pq.cache")
def _build_cache_rerank(node, binds, plan):
    """Decode the slot shortlist from the i8/i4 residual cache at f32
    and rank exactly; slots resolve to global ids by one flat gather
    (the billion-scale source: the dataset is never HBM-resident)."""
    from raft_tpu.neighbors import ivf_pq

    index = binds.index
    k = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}
    rot = index.rot_dim
    row_bytes = (rot // 2 if index.cache_kind == "i4" else rot) + 4

    def run(ctx):
        _, slots = _candidate_inputs(ctx, node, by_id)[0]
        with obs.span("ivf_pq.rerank", source="cache") as s2:
            d, s = ivf_pq._refine_slots(
                jnp.asarray(ctx.queries), slots, int(k),
                int(index.metric), index.recon_cache,
                index.cache_scales, index.centers_rot, index.rotation,
                jnp.float32(index.recon_scale))
            ids = jnp.where(
                s >= 0, index.indices.reshape(-1)[jnp.maximum(s, 0)], -1)
            if obs.enabled():
                s2.sync(ids)
        ctx.stats["rerank_ms"] = getattr(s2, "device_ms", None)
        _emit_rerank_obs(ctx, int(ctx.queries.shape[0]), "cache",
                         row_bytes)
        return d, ids
    return run


@register_op("rerank", "ivf_pq.codes")
def _build_codes_rerank(node, binds, plan):
    """Re-score the slot shortlist at full PQ fidelity from the packed
    codes — the rabitq pipeline's rerank when the index kept them
    (1-bit first stage, PQ-exact second)."""
    from raft_tpu.neighbors import ivf_pq

    index = binds.index
    k = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}
    row_bytes = ivf_pq.packed_words(index.pq_dim, index.pq_bits) * 4

    def run(ctx):
        _, slots = _candidate_inputs(ctx, node, by_id)[0]
        with obs.span("ivf_pq.rerank", source="codes") as s2:
            d, s = ivf_pq._refine_slots_codes(
                jnp.asarray(ctx.queries), slots, int(k),
                int(index.metric), index.codes, index.pq_centers,
                index.centers_rot, int(index.codebook_kind),
                int(index.pq_dim), int(index.pq_bits),
                rotation=index.rotation)
            ids = jnp.where(
                s >= 0, index.indices.reshape(-1)[jnp.maximum(s, 0)], -1)
            if obs.enabled():
                s2.sync(ids)
        ctx.stats["rerank_ms"] = getattr(s2, "device_ms", None)
        _emit_rerank_obs(ctx, int(ctx.queries.shape[0]), "codes",
                         row_bytes)
        return d, ids
    return run


@register_op("rerank", "exact.device")
def _build_exact_device_rerank(node, binds, plan):
    """Serve's raw-refine tail: exact re-rank of an id shortlist
    against the generation's device-resident raw rows
    (neighbors.refine — the full-upload fast path)."""
    from raft_tpu.neighbors.refine import refine

    raw = binds.raw_dev
    if raw is None:
        raise PlanError(f"node {node.id!r}: exact.device rerank needs "
                        f"compile(raw_dev=...)")
    index = binds.index
    k = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}
    metric = index.metric

    def run(ctx):
        _, ids = _candidate_inputs(ctx, node, by_id)[0]
        return refine(raw, ctx.queries, ids, int(k), metric)
    return run


# ---------------------------------------------------------------------------
# merge / score_fuse stages
# ---------------------------------------------------------------------------

@register_op("merge", "topk")
def _build_merge_topk(node, binds, plan):
    from raft_tpu.distance.types import is_min_close
    from raft_tpu.neighbors.common import merge_topk

    k = _resolve_width(node, binds)
    select_min = bool(binds.extra.get("select_min",
                                      is_min_close(binds.index.metric)))
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        legs = _candidate_inputs(ctx, node, by_id)
        d = jnp.concatenate([leg[0] for leg in legs], axis=1)
        i = jnp.concatenate([leg[1].astype(jnp.int32) for leg in legs],
                            axis=1)
        return merge_topk(d, i, int(k), select_min)
    return run


@register_op("score_fuse", "weighted")
def _build_score_fuse(node, binds, plan):
    """Weight-fuse a dense leg with a sparse lexical leg over the
    UNION of their candidates: each leg's candidates are re-scored
    exactly on the OTHER leg (dense rows by gather+dot, sparse rows
    from the index's padded ELL sidecar), duplicates are masked out of
    the second leg, and both legs emerge carrying the same fused
    score ``w_dense * dense + w_sparse * sparse`` — ready for one
    ``merge_topk`` (neighbors.hybrid, ISSUE 20 / ROADMAP 6(a))."""
    from raft_tpu.neighbors import hybrid

    index = binds.index
    w_dense = float(node.params.get("w_dense", index.w_dense))
    w_sparse = float(node.params.get("w_sparse", index.w_sparse))
    by_id = {n.id: n for n in plan.nodes}
    order = [s for s in node.inputs
             if by_id[s].stage in CANDIDATE_STAGES]

    def run(ctx):
        (dd, di) = ctx.values[order[0]]
        (sd, si) = ctx.values[order[1]]
        qd, qs = hybrid.split_queries(index, ctx.queries)
        return hybrid._fuse_rescore(
            qd, qs, index.dense, index.ell_cols, index.ell_vals,
            dd, di, sd, si, jnp.float32(w_dense), jnp.float32(w_sparse))
    return run


@register_op("scan", "hybrid.dense")
def _build_hybrid_dense(node, binds, plan):
    """The hybrid plan's dense leg: brute-force top-c over the dense
    columns (the index's internal brute_force sub-index, so the tuned
    scan kernels and the prefilter path are the same ones every other
    dense search uses)."""
    from raft_tpu.neighbors import brute_force, hybrid

    index = binds.index
    width = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        qd, _ = hybrid.split_queries(index, ctx.queries)
        return brute_force.search(index.dense_bf, qd, width,
                                  prefilter=_filter_input(ctx, node,
                                                          by_id))
    return run


@register_op("scan", "sparse.brute_force")
def _build_hybrid_sparse(node, binds, plan):
    """The hybrid plan's sparse lexical leg: blockwise brute force
    over the CSR document matrix (raft_tpu/sparse), densifying one
    row block at a time — the docs stay sparse at rest."""
    from raft_tpu.neighbors import hybrid
    from raft_tpu.sparse import neighbors as sparse_neighbors

    index = binds.index
    width = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        _, qs = hybrid.split_queries(index, ctx.queries)
        return sparse_neighbors.brute_force_knn_dense_queries(
            qs, index.docs, width,
            prefilter=_filter_input(ctx, node, by_id))
    return run


# ---------------------------------------------------------------------------
# sharded (comms) ops: the worker-local pre-merge subplan + the
# collective merge executed inside shard_map, and the router tail
# ---------------------------------------------------------------------------

@register_op("scan", "identity")
def _build_identity(node, binds, plan):
    """Seed node for a split tail plan: stands for the candidates the
    head already produced (the router hands them in per call as
    ``extra={"candidates": (d, ids)}``)."""
    def run(ctx):
        try:
            cand = ctx.extra["candidates"]
        except KeyError:
            raise PlanError(
                f"node {node.id!r}: identity seed needs "
                f"extra={{'candidates': (d, ids)}} at call time"
            ) from None
        # the merged shortlist IS the rerank tail's shortlist — stash
        # it so _emit_rerank_obs counts the real rows moved
        ctx.stats["shortlist"] = cand[1]
        return cand
    return run


@register_op("scan", "ivf_pq.local")
def _build_ivf_pq_local(node, binds, plan):
    """Worker-local first stage inside shard_map: the 15-tuple operand
    pack arrives per shard through the call (ctx.arrays), the statics
    were bound at compile — one _pq_search, exactly the hand-wired
    local() body."""
    from raft_tpu.neighbors import ivf_pq

    st = binds.extra
    width = _resolve_width(node, binds)

    def run(ctx):
        return ivf_pq._pq_search(
            ctx.arrays, int(width), st["n_probes"], st["metric"],
            st["group"], st["bucket_batch"], st["codebook_kind"], 0,
            st["compute_dtype"], st["local_recall_target"],
            st["merge_recall_target"], st["lut"], st["internal"],
            st["pq_dim"], st["pq_bits"], "xla")
    return run


@register_op("rerank", "ivf_pq.cache.local")
def _build_cache_local_rerank(node, binds, plan):
    """Per-shard cache-decoded exact rerank inside shard_map; slots
    resolve against the SHARD-local indices block handed through
    ctx.extra."""
    from raft_tpu.neighbors import ivf_pq

    st = binds.extra
    k = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        _, slots = _candidate_inputs(ctx, node, by_id)[0]
        d, s = ivf_pq._refine_slots(
            ctx.queries, slots, int(k), st["metric"],
            ctx.extra["cache"], ctx.extra["scales"],
            ctx.arrays[2], ctx.arrays[3],
            jnp.float32(st["recon_scale"]))
        indices = ctx.extra["indices"]
        i = jnp.where(s >= 0, indices.reshape(-1)[jnp.maximum(s, 0)], -1)
        return d, i
    return run


@register_op("merge", "collective.topk")
def _build_collective_merge(node, binds, plan):
    """The cross-shard merge: all-gather each shard's top-k over the
    mesh axis and keep the global best — the node every sharded plan
    splits at (workers run everything upstream, the router everything
    downstream)."""
    from raft_tpu.neighbors.common import merge_topk

    st = binds.extra
    k = _resolve_width(node, binds)
    axis = st["axis_name"]
    select_min = bool(st["select_min"])
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        d, i = _candidate_inputs(ctx, node, by_id)[0]
        # fault-injection / partial-coverage masking is the CALLER's
        # concern (comms/sharded owns the dead-rank bookkeeping): an
        # optional per-call hook runs just before the collective so a
        # dead shard's rows sink at the merge
        hook = ctx.extra.get("pre_merge")
        if hook is not None:
            d, i = hook(d, i)
        with jax.named_scope("sharded.merge"):
            gd = jax.lax.all_gather(d, axis, axis=1, tiled=True)
            gi = jax.lax.all_gather(i, axis, axis=1, tiled=True)
            return merge_topk(gd, gi, int(k), select_min)
    return run


@register_op("rerank", "tiered.rerank")
def _build_tiered_rerank_tail(node, binds, plan):
    """Router-side tiered rerank over an already-merged id shortlist
    (the sharded tail: only the merged shortlist's rows are read,
    router-side of the collective). The source comes per call as
    ``extra={"source": ...}`` and the metric among the compile statics,
    so that a caller may hold the compiled tail across calls without
    holding a call's rows."""
    metric = binds.extra["metric"]
    k = _resolve_width(node, binds)
    by_id = {n.id: n for n in plan.nodes}

    def run(ctx):
        _, ids = _candidate_inputs(ctx, node, by_id)[0]
        return ctx.extra["source"].rerank(ctx.queries, ids, int(k), metric)
    return run


# ---------------------------------------------------------------------------
# the compiled program
# ---------------------------------------------------------------------------

class CompiledPlan:
    """One executable search program: topologically ordered node
    executors over shared compile bindings.  Stateless per call —
    safe to share across serving/shadow threads; trace caches belong
    to the underlying jitted entry points, never to this object."""

    __slots__ = ("plan", "binds", "_order", "_runs", "output")

    def __init__(self, plan: Plan, binds: _Binds):
        self.plan = plan
        self.binds = binds
        self._order = validate(plan)
        self.output = plan.output
        self._runs = []
        for node in self._order:
            builder = OPS.get((node.stage, node.op))
            if builder is None:
                raise PlanError(
                    f"plan {plan.name!r}: no executor for "
                    f"({node.stage!r}, {node.op!r}) — register one "
                    f"with plan.register_op (docs/plans.md §5)")
            self._runs.append((node.id, builder(node, binds, plan)))

    @property
    def k(self) -> int:
        return int(self.binds.k)

    @property
    def rung(self):
        return self.binds.rung

    def __call__(self, queries, prefilter=None, *, arrays=None,
                 extra=None, stats=None):
        ctx = _Ctx(queries, prefilter, arrays, extra)
        with obs.span("plan.execute", plan=self.plan.name,
                      k=int(self.binds.k)):
            for node_id, run in self._runs:
                ctx.values[node_id] = run(ctx)
        if stats is not None:
            stats.update(ctx.stats)
        return ctx.values[self.output]


def compile_plan(plan: Plan, index, bucket: Optional[int] = None,
                 k: Optional[int] = None, rung=None, *,
                 search_params=None, refine_ratio: int = 1,
                 source=None, raw_dev=None, memo=None,
                 **extra) -> CompiledPlan:
    """Bind ``plan`` to ``index`` at one (bucket, k, rung) point and
    return the executable program (exported as ``plan.compile``).

    ``rung`` follows serve's trace-key-is-the-value discipline: an int
    replaces only ``n_probes`` in ``search_params`` (idempotent with a
    caller that already resolved it — the top rung compiles the exact
    program ``rung=None`` does), and the ``"exact"`` oracle rung pins
    exhaustive probing (``n_probes = n_lists``).  ``bucket`` is
    warmup metadata: executors never read it — shape stability comes
    from the caller padding queries to the bucket ladder, exactly like
    the hand-wired dispatch.  ``source``/``raw_dev`` bind the rerank
    tier; ``memo`` (a dict) shares derived device arrays — e.g. the
    slot-substituted indices — across one handle's compiled
    variants."""
    if k is None:
        raise PlanError("compile needs k")
    sp = search_params
    if rung is not None and sp is not None and hasattr(sp, "n_probes"):
        n_lists = int(index.n_lists)
        n_probes = n_lists if rung == "exact" else int(rung)
        sp = dataclasses.replace(sp, n_probes=n_probes)
    binds = _Binds(index=index, k=int(k), bucket=bucket, rung=rung,
                   search_params=sp, refine_ratio=int(refine_ratio),
                   source=source, raw_dev=raw_dev,
                   memo=memo if memo is not None else {}, extra=extra)
    return CompiledPlan(plan, binds)
