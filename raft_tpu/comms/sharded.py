"""Sharded (multi-chip) algorithms over a device mesh.

The reference's multi-GPU model (SURVEY.md §2.18): each rank holds an index
shard; queries are replicated; per-shard top-k results are merged. Consumers
wire it with raft-dask + NCCL. Here the whole pattern is one ``shard_map``:
the dataset is sharded over the mesh axis, each device runs the local
search, and the shard top-ks are all-gathered and merged on-device over ICI.

Graceful shard degradation (docs/resilience.md): the searches accept
``partial_ok=True`` — a shard whose local result is invalid (NaN, or a
rank named by an injected ``shard@rank:R`` fault) is masked to the
worst-possible sentinel before ``merge_topk``, and the call returns the
merged results plus a replicated coverage fraction instead of raising
(the reference's ``knn_merge_parts`` multi-rank model tolerates exactly
this per-rank variation). Detection runs when ``partial_ok=True`` OR a
shard fault is injected; in the latter case ``partial_ok=False`` raises
:class:`raft_tpu.resilience.ShardDropoutError` on any dropout. Without
either, the plain path is compiled unchanged (no validity scan, no
coverage collective) — a real NaN shard then propagates exactly as it
did pre-resilience; callers that want NaN *detection* opt in with
``partial_ok=True`` and check ``coverage < 1``.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from raft_tpu import obs
from raft_tpu import plan as plan_mod
from raft_tpu.distance.types import DistanceType, is_min_close, resolve_metric
from raft_tpu.neighbors import brute_force
from raft_tpu.neighbors.common import merge_topk
from raft_tpu.resilience import ShardDropoutError, faultinject


def _dead_rank_array() -> jax.Array:
    """Injected-dead ranks as a replicated input array (NOT baked into
    the trace, so jit caches stay valid across changing fault plans)."""
    bad = sorted(faultinject.dead_ranks())
    return jnp.asarray(bad if bad else [-1], jnp.int32)


def _mask_invalid(d, i, rank, bad_ranks, select_min):
    """Shard-local validity, PER QUERY ROW: a row is dropped when its
    shard's rank is fault-injected dead (all rows) or its local top-k
    carries NaN (the real-fault signature: a wedged collective / corrupt
    block scores NaN). Row-granular on purpose — queries are replicated,
    so one NaN *query* poisons the same row on every shard, and a
    whole-shard verdict would sentinel all S shards over one bad input
    row. Invalid rows score the worst-possible sentinel with ids -1, so
    the cross-shard merge ranks every surviving candidate ahead of
    them."""
    dead = jnp.any(rank == bad_ranks)
    row_ok = jnp.logical_not(dead | jnp.any(jnp.isnan(d), axis=1))  # [m]
    sent = jnp.asarray(jnp.inf if select_min else -jnp.inf, d.dtype)
    d = jnp.where(row_ok[:, None], d, sent)
    i = jnp.where(row_ok[:, None], i, jnp.asarray(-1, i.dtype))
    return d, i, row_ok


def _coverage(valid, axis_name) -> jax.Array:
    """Replicated surviving fraction over shards x query rows: a fully
    dead shard of S costs 1/S; a single poisoned query row (invalid on
    every shard, since queries are replicated) costs 1/m."""
    flags = jax.lax.all_gather(valid.astype(jnp.float32), axis_name)
    return jnp.mean(flags)


def _record_full_coverage(what: str) -> None:
    """The healthy-path twin of :func:`_finish_partial`'s gauge: the
    plain (no validity scan) path serves full coverage by construction,
    and recording ``shard_coverage{what} = 1`` there lets a dashboard
    distinguish "healthy S/S shards" from "metric never emitted" —
    previously the series only ever carried degraded values."""
    if obs.enabled():
        obs.gauge("shard_coverage", 1.0, what=what)


def _finish_partial(out, partial_ok: bool, what: str):
    """Host-side tail of a partial-capable search: hand back (d, i,
    coverage) under ``partial_ok``, else raise on any dropout.

    With obs enabled the replicated coverage fraction is recorded as the
    ``shard_coverage{what}`` gauge (plus ``shard_dropouts_total`` when it
    dips below 1) — note the gauge read forces a host sync of the
    coverage scalar, which the bare ``partial_ok=True`` path otherwise
    defers to the caller."""
    d, i, cov = out
    if obs.enabled():
        c = float(np.asarray(cov))
        obs.gauge("shard_coverage", c, what=what)
        if c < 1.0:
            obs.counter("shard_dropouts_total", what=what)
            obs.event("shard_dropout", what=what, coverage=c)
    if partial_ok:
        return d, i, cov
    # fault-detection path without the partial opt-in: refuse to return
    # silently-degraded results
    if float(np.asarray(cov)) < 1.0:
        raise ShardDropoutError(
            f"{what}: shard coverage {float(np.asarray(cov)):.3f} < 1 "
            "(a shard's local result was invalid); pass partial_ok=True "
            "to accept partial results plus a coverage fraction"
        )
    return d, i


# the programs sharded_ivf_pq_search and sharded_ivf_pq_build hold, the
# last _HELD_MAX of them, each keyed on everything it is traced from
_HELD_MAX = 8
_held: "collections.OrderedDict" = collections.OrderedDict()
_held_lock = threading.Lock()


def _shapes(*trees) -> tuple:
    """Tree structure (with an index's static fields), shapes and dtypes
    of ``trees``: what a jitted program is traced at."""
    leaves, tree = jax.tree_util.tree_flatten(trees)
    return tree, tuple((tuple(a.shape), str(a.dtype)) for a in leaves)


def _hold(key, make):
    """``make()``'s value for ``key``: made on the first call with the
    key, then held, so that a repeat call traces nothing. Counts
    ``sharded.program_hits`` / ``sharded.program_misses``."""
    with _held_lock:
        val = _held.get(key)
        if val is not None:
            _held.move_to_end(key)
    if val is not None:
        obs.counter("sharded.program_hits")
        return val
    obs.counter("sharded.program_misses")
    val = make()
    with _held_lock:
        _held[key] = val
        while len(_held) > _HELD_MAX:
            _held.popitem(last=False)
    return val


def sharded_knn(
    queries,
    dataset,
    k: int,
    mesh: Mesh,
    axis_name: str = "shard",
    metric="sqeuclidean",
    metric_arg: float = 2.0,
    partial_ok: bool = False,
) -> Tuple[jax.Array, ...]:
    """Exact KNN with the dataset row-sharded over ``mesh[axis_name]``.

    Dataset rows need NOT divide the axis size: non-divisible ``n`` is
    auto-padded with sentinel rows whose distances mask to
    worst-possible and whose ids mask to -1 inside the local search, so
    they can only surface when ``k`` exceeds the real row count
    ("pad upstream" was a robustness foot-gun). Queries are replicated;
    each shard computes a local top-k with *global* ids (rank offset
    added), then shard results are all-gathered and merged — the
    knn_merge_parts-over-NCCL pattern
    (detail/knn_merge_parts.cuh + raft-dask) as a single XLA program.

    ``partial_ok=True`` returns ``(dists, ids, coverage)`` with invalid
    shards (NaN local results, injected dead ranks) masked out of the
    merge — see the module docstring.
    """
    metric = resolve_metric(metric)
    queries = jnp.asarray(queries)
    dataset = jnp.asarray(dataset)
    n = dataset.shape[0]
    nshards = mesh.shape[axis_name]
    if n % nshards != 0:
        padded = -(-n // nshards) * nshards
        dataset = jnp.concatenate(
            [dataset,
             jnp.zeros((padded - n,) + dataset.shape[1:], dataset.dtype)],
            axis=0,
        )
    n_pad = dataset.shape[0] - n
    shard_rows = dataset.shape[0] // nshards
    select_min = is_min_close(metric)
    partial = partial_ok or faultinject.has_shard_faults()
    # zero-filled pad rows DO score (a query near the origin ranks them
    # well under L2), so the local top-k is widened by the pad count —
    # at most n_pad real candidates can be displaced before the mask
    # turns every pad row into the worst-possible sentinel
    k_local = int(min(k + n_pad, shard_rows)) if n_pad else int(k)

    # a scan tile that divides the shard: a padded copy of the shard
    # would double its device memory
    tile = next(t for t in range(min(shard_rows, 8192), 0, -1)
                if shard_rows % t == 0)

    def local(q, db_shard, *rest):
        rank = jax.lax.axis_index(axis_name)
        d, i = brute_force._search(
            q, db_shard, None, None, None, k_local, int(metric),
            float(metric_arg), int(tile),
        )
        i = i + (rank * shard_rows).astype(i.dtype)
        if n_pad:
            pad = i >= n
            d = jnp.where(pad, jnp.asarray(
                jnp.inf if select_min else -jnp.inf, d.dtype), d)
            i = jnp.where(pad, jnp.asarray(-1, i.dtype), i)
        if partial:
            d, i, valid = _mask_invalid(d, i, rank, rest[0], select_min)
        # gather all shards' candidates onto every device, merge locally
        gd = jax.lax.all_gather(d, axis_name, axis=1, tiled=True)  # [m, S*k]
        gi = jax.lax.all_gather(i, axis_name, axis=1, tiled=True)
        md, mi = merge_topk(gd, gi, k, select_min)
        if partial:
            return md, mi, _coverage(valid, axis_name)
        return md, mi

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis_name, None)) + ((P(),) if partial else ()),
        out_specs=(P(), P()) + ((P(),) if partial else ()),
        check_vma=False,
    )
    args = (queries, dataset) + ((_dead_rank_array(),) if partial else ())
    with obs.entry_span("search", "sharded_knn",
                        queries=int(queries.shape[0]), k=int(k),
                        shards=int(nshards)):
        out = jax.jit(fn)(*args)
    if partial:
        return _finish_partial(out, partial_ok, "sharded_knn")
    _record_full_coverage("sharded_knn")
    return out


def sharded_ivf_search(
    search_params,
    index,
    queries,
    k: int,
    mesh: Mesh,
    axis_name: str = "shard",
    partial_ok: bool = False,
) -> Tuple[jax.Array, ...]:
    """Approximate KNN with the IVF index's *lists* sharded over the mesh.

    The reference's large-index multi-GPU model: each rank owns an index
    shard and runs the same search; per-rank top-ks are merged
    (raft-dask + detail/knn_merge_parts.cuh:140). Here each device holds
    ``n_lists / n_shards`` lists (centers, storage blocks, norms all
    sharded on the list axis), probes ``n_probes / n_shards`` of them, and
    the per-shard top-ks are all-gathered + merged over ICI.

    Stored ids are global dataset row ids, so no rank offset is needed.

    ``partial_ok=True`` returns ``(dists, ids, coverage)`` with invalid
    shards masked out of the merge (module docstring).
    """
    from raft_tpu.neighbors import ivf_flat

    queries = jnp.asarray(queries)
    C = index.n_lists
    nshards = mesh.shape[axis_name]
    if C % nshards != 0:
        raise ValueError(f"n_lists {C} not divisible by mesh axis {nshards}")
    local_lists = C // nshards
    n_probes = max(1, min(int(search_params.n_probes) // nshards, local_lists))
    cap = index.storage.shape[1]
    if k > n_probes * cap:
        raise ValueError(
            f"k={k} exceeds the per-shard candidate pool "
            f"(n_probes/shard={n_probes} x cap={cap}); raise n_probes to at "
            f"least {nshards * -(-k // max(cap, 1))} for a {nshards}-way mesh"
        )
    select_min = is_min_close(index.metric)
    metric = int(index.metric)
    group = ivf_flat.adaptive_query_group(
        int(queries.shape[0]), n_probes, index.n_lists,
        int(search_params.query_group),
    )
    bucket_batch = int(search_params.bucket_batch)

    has_norms = index.data_norms is not None
    partial = partial_ok or faultinject.has_shard_faults()

    def local(q, centers, storage, indices, list_sizes, *rest):
        rest = list(rest)
        norms = rest.pop(0) if has_norms else None
        bad = rest.pop(0) if partial else None
        rank = jax.lax.axis_index(axis_name)
        # graft-lint: allow-hand-wired-pipeline deliberate single-stage fast path: one collective per-shard scan + merge, no multi-stage tail
        d, i = ivf_flat._ivf_search(
            q, centers, storage, indices, list_sizes,
            int(k), n_probes, metric, group, bucket_batch,
            str(search_params.compute_dtype),
            float(search_params.local_recall_target),
            float(search_params.merge_recall_target),
            norms, None,
        )
        if partial:
            d, i, valid = _mask_invalid(d, i, rank, bad, select_min)
        gd = jax.lax.all_gather(d, axis_name, axis=1, tiled=True)  # [m, S*k]
        gi = jax.lax.all_gather(i, axis_name, axis=1, tiled=True)
        md, mi = merge_topk(gd, gi, k, select_min)
        if partial:
            return md, mi, _coverage(valid, axis_name)
        return md, mi

    args = [queries, index.centers, index.storage, index.indices, index.list_sizes]
    in_specs = [P(), P(axis_name, None), P(axis_name, None, None),
                P(axis_name, None), P(axis_name)]
    if has_norms:
        args.append(index.data_norms)
        in_specs.append(P(axis_name, None))
    if partial:
        args.append(_dead_rank_array())
        in_specs.append(P())

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()) + ((P(),) if partial else ()),
        check_vma=False,
    )
    with obs.entry_span("search", "sharded_ivf",
                        queries=int(queries.shape[0]), k=int(k),
                        shards=int(nshards)):
        out = jax.jit(fn)(*args)
    if partial:
        return _finish_partial(out, partial_ok, "sharded_ivf_search")
    _record_full_coverage("sharded_ivf_search")
    return out


def sharded_ivf_pq_search(
    search_params,
    index,
    queries,
    k: int,
    mesh: Mesh,
    axis_name: str = "shard",
    refine_ratio: int = 1,
    partial_ok: bool = False,
    rerank_source=None,
) -> Tuple[jax.Array, ...]:
    """Approximate KNN with the IVF-PQ index's *lists* sharded over the
    mesh — the DEEP-1B-scale model (the reference fits DEEP-1B in 24 GiB
    per GPU via PQ and shards across GPUs via comms,
    docs/source/using_raft_comms.rst): each device owns
    ``n_lists / n_shards`` lists (centers, packed codes, norms, int8
    cache all sharded on the list axis), probes its share, and the
    per-shard top-ks are all-gathered + merged over ICI.

    PER_CLUSTER codebooks shard with their lists; PER_SUBSPACE codebooks
    and the rotation are replicated. Stored ids are global dataset row
    ids, so no rank offset is needed.

    ``refine_ratio > 1`` adds a PER-SHARD exact re-rank from the residual
    cache before the cross-shard merge (the reference's refine_ratio
    pattern, bench/ann raft_ivf_pq_wrapper.h, with the dataset read
    replaced by on-chip cache decode — detail/refine_host-inl.hpp's role
    at a scale where the f32 dataset cannot be resident): each shard
    searches ``k * refine_ratio`` candidates over slot-substituted
    indices, decodes those slots from ITS OWN cache shard at f32, ranks
    exactly, and only the refined top-k rides the all-gather. Requires
    the index to carry a residual cache.

    ``rerank_source`` (the tiered-memory shape, docs/serving.md §12)
    reranks from HOST-resident originals INSTEAD of the per-shard
    cache: a :class:`raft_tpu.neighbors.tiered.RerankSource` (or host
    numpy/memmap array — wrapped per call). The shards then merge
    their FIRST-stage top-``k*refine_ratio`` candidates, and the host
    source fetches only the merged shortlist's unique rows for the
    exact final ranking — no residual cache required, and
    ``partial_ok`` composes (an uncovered shard's ``-1`` rows stay
    invalid through the rerank; coverage passes through unchanged).

    A ``rerank_source`` whose rows are sharded by row over a mesh axis
    (``tiered.RowShardedSource``) is read where it lies: each chip
    scores the merged candidates it holds, and one all-reduce joins the
    scores (device stage ``sharded.rerank``; host span
    ``sharded.rerank`` around the tail's dispatch).

    The jitted program and its compiled plans are held per mesh, axis,
    argument shapes and statics (:func:`_hold`): a repeat call traces
    nothing.

    ``partial_ok=True`` returns ``(dists, ids, coverage)`` with invalid
    shards masked out of the merge (module docstring).
    """
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.ivf_flat import adaptive_query_group

    queries = jnp.asarray(queries)
    C = index.n_lists
    nshards = mesh.shape[axis_name]
    if C % nshards != 0:
        raise ValueError(f"n_lists {C} not divisible by mesh axis {nshards}")
    local_lists = C // nshards
    n_probes = max(1, min(int(search_params.n_probes) // nshards, local_lists))
    if index.codes.ndim != 3:
        raise ValueError(
            "flat-codes (100M-scale streamed) indexes are single-device "
            "only for now: sharding needs per-device [C, cap, nw] blocks"
        )
    cap = index.indices.shape[1]
    if k > n_probes * cap:
        raise ValueError(
            f"k={k} exceeds the per-shard candidate pool "
            f"(n_probes/shard={n_probes} x cap={cap}); raise n_probes to at "
            f"least {nshards * -(-k // max(cap, 1))} for a {nshards}-way mesh"
        )
    select_min = is_min_close(index.metric)
    metric = int(index.metric)
    group = adaptive_query_group(
        int(queries.shape[0]), n_probes, index.n_lists,
        int(search_params.query_group),
    )
    bucket_batch = int(search_params.bucket_batch)
    per_cluster = int(index.codebook_kind) == ivf_pq.codebook_gen.PER_CLUSTER
    has_cache = index.recon_cache is not None
    has_fac = index.cache_kind == "rabitq"
    lut = ivf_pq._norm_dtype_knob(search_params.lut_dtype)
    if lut == "i8" and index.cache_kind not in ("i8", "i4"):
        # mirror ivf_pq.search(): a pq4 code cache is not the i8 LUT path
        raise ValueError("lut_dtype='i8' needs the decoded-residual cache")
    if lut == "auto" and not has_cache:
        lut = "f32"
    internal = ivf_pq._norm_dtype_knob(search_params.internal_distance_dtype)

    refine_ratio = int(refine_ratio)
    src = None
    if rerank_source is not None:
        from raft_tpu.neighbors import tiered

        src = tiered.as_source(rerank_source)
    cache_refine = (refine_ratio > 1 and src is None
                    and index.cache_kind in ("i4", "i8"))
    # rabitq shards as first-stage subplan + ROUTER-side rerank: the
    # 1-bit scan returns GLOBAL slots (shard offset applied in-trace),
    # the merged slot shortlist re-scores at full PQ fidelity from the
    # full index's packed codes once, host-side of the collective
    codes_refine = (refine_ratio > 1 and src is None and has_fac)
    if refine_ratio > 1 and src is None and not (cache_refine
                                                 or codes_refine):
        raise ValueError(
            "refine_ratio > 1 needs the decoded-RESIDUAL cache (i8/i4; "
            "build with cache_decoded=True within the cache budget) or "
            "a host rerank_source= (neighbors.tiered) — a pq4 code "
            "cache carries no fidelity beyond the scan itself"
        )
    if codes_refine and int(index.codes.shape[-1]) == 0:
        raise ValueError(
            "sharded rabitq refine re-scores the merged shortlist from "
            "the packed PQ codes — build with keep_codes=True, or pass "
            "a host rerank_source= (neighbors.tiered)"
        )
    k_search = k * refine_ratio
    if k_search > n_probes * cap:
        raise ValueError(
            f"k*refine_ratio={k_search} exceeds the per-shard candidate "
            f"pool (n_probes/shard={n_probes} x cap={cap})"
        )
    # with a router-side rerank tail (host source or rabitq codes) the
    # shards merge their FIRST-stage shortlists at full k_search width;
    # the exact rerank happens once on the merged candidates
    k_merge = k_search if (src is not None or codes_refine) else k

    has_scales = has_cache and index.cache_scales is not None
    partial = partial_ok or faultinject.has_shard_faults()
    tail_kind = ("tiered" if src is not None
                 else "codes" if codes_refine else None)

    args = [queries, index.centers, index.centers_rot, index.rotation,
            index.pq_centers, index.codes, index.indices, index.list_sizes,
            index.rec_norms]
    if has_cache:
        args.append(index.recon_cache)
    if has_scales:
        args.append(index.cache_scales)        # [C, rot] per-list scales
    if has_scales or has_fac:
        args.append(index.cache_qnorms if index.cache_qnorms is not None
                    else index.rec_norms)
    if has_fac:
        args.append(index.cache_fac)           # [C, cap] discriminator
    if partial:
        args.append(_dead_rank_array())

    statics = dict(
        k=int(k), k_search=int(k_search), k_merge=int(k_merge),
        n_probes=n_probes, group=group, bucket_batch=bucket_batch,
        lut=lut, internal=internal,
        codebook_kind=int(index.codebook_kind), per_cluster=per_cluster,
        cache_refine=cache_refine, codes_refine=codes_refine,
        tail_kind=tail_kind, partial=partial, metric=metric,
        select_min=select_min,
        compute_dtype=str(search_params.compute_dtype),
        local_recall_target=float(search_params.local_recall_target),
        merge_recall_target=float(search_params.merge_recall_target),
        pq_dim=int(index.pq_dim), pq_bits=int(index.pq_bits),
        recon_scale=float(index.recon_scale), refine_ratio=refine_ratio,
        has_cache=has_cache, has_scales=has_scales, has_fac=has_fac,
        local_slots=local_lists * cap)
    key = ("ivf_pq_search", mesh, axis_name, _shapes(args),
           tuple(sorted(statics.items())))
    fn, tail_plan, tail_cp = _hold(key, lambda: _ivf_pq_search_program(
        mesh, axis_name, **statics))
    if tail_kind == "codes":
        # the rabitq tail decodes from the full index: bound per call
        tail_cp = plan_mod.compile(tail_plan, index, k=int(k))
    with obs.entry_span("search", "sharded_ivf_pq",
                        queries=int(queries.shape[0]), k=int(k),
                        shards=int(nshards), refine_ratio=refine_ratio):
        out = fn(*args)
        if tail_cp is not None:
            # router-side rerank over the MERGED shortlist (tiered
            # fetch of unique rows, rows read on the chips that hold
            # them, or rabitq slot decode from the packed codes);
            # uncovered shards' -1 rows stay invalid and sink at the
            # exact ranking
            md, mi = out[0], out[1]
            with obs.span("sharded.rerank", kc=int(mi.shape[-1])):
                rd, ri = tail_cp(queries, extra={"candidates": (md, mi),
                                                 "source": src})
            out = (rd, ri) + tuple(out[2:])
    if partial:
        return _finish_partial(out, partial_ok, "sharded_ivf_pq_search")
    _record_full_coverage("sharded_ivf_pq_search")
    return out


def _ivf_pq_search_program(mesh, axis_name, *, k, k_search, k_merge,
                           n_probes, group, bucket_batch, lut, internal,
                           codebook_kind, per_cluster, cache_refine,
                           codes_refine, tail_kind, partial, metric,
                           select_min, compute_dtype, local_recall_target,
                           merge_recall_target, pq_dim, pq_bits,
                           recon_scale, refine_ratio, has_cache,
                           has_scales, has_fac, local_slots):
    """The jitted list-sharded program of :func:`sharded_ivf_pq_search`,
    its router tail plan, and that plan compiled where it binds nothing
    of a call (the tiered tail takes its source per call; the rabitq
    codes tail binds the index, so the caller compiles it). Made from
    the call's statics alone, so that it can be held across calls."""
    from raft_tpu.neighbors import ivf_pq

    # the pipeline as DATA (raft_tpu.plan): the pre-merge subplan runs
    # per worker inside shard_map, the rerank tail (if any) once on the
    # router — split_at_merge cuts at the collective
    p = plan_mod.sharded_ivf_pq_plan(
        k, k_search, k_merge, local_rerank=cache_refine, tail=tail_kind)
    head_plan, tail_plan = plan_mod.split_at_merge(p)
    head_cp = plan_mod.compile(
        head_plan, None, k=k, refine_ratio=refine_ratio,
        n_probes=n_probes, metric=metric, group=group,
        bucket_batch=bucket_batch, codebook_kind=codebook_kind,
        compute_dtype=compute_dtype,
        local_recall_target=local_recall_target,
        merge_recall_target=merge_recall_target,
        lut=lut, internal=internal, pq_dim=pq_dim, pq_bits=pq_bits,
        recon_scale=recon_scale, axis_name=axis_name,
        select_min=select_min)
    tail_cp = (plan_mod.compile(tail_plan, None, k=k, metric=metric)
               if tail_kind == "tiered" else None)

    def local(q, centers, centers_rot, rotation, pq_centers, codes,
              indices, list_sizes, rec_norms, *rest):
        rest = list(rest)
        cache = rest.pop(0) if has_cache else None
        scales = rest.pop(0) if has_scales else None
        qnorms = rest.pop(0) if (has_scales or has_fac) else None
        fac = rest.pop(0) if has_fac else None
        bad = rest.pop(0) if partial else None
        rank = jax.lax.axis_index(axis_name)
        if cache_refine:
            # per-shard rerank decodes from ITS OWN cache: LOCAL slots
            search_ids = ivf_pq._slot_indices(indices)
        elif codes_refine:
            # router rerank decodes from the FULL index: local slots
            # lift to global flat slots by the shard's block offset
            s = ivf_pq._slot_indices(indices)
            search_ids = jnp.where(s >= 0, s + rank * local_slots, -1)
        else:
            search_ids = indices
        arrays = (q, centers, centers_rot, rotation, pq_centers, codes,
                  search_ids, list_sizes, rec_norms, None, cache,
                  jnp.float32(recon_scale), scales, qnorms, fac)
        extra = {"indices": indices, "cache": cache, "scales": scales}
        if partial:
            cov = {}

            def pre_merge(d, i):
                d, i, valid = _mask_invalid(d, i, rank, bad, select_min)
                cov["valid"] = valid
                return d, i

            extra["pre_merge"] = pre_merge
        md, mi = head_cp(q, arrays=arrays, extra=extra)
        if partial:
            return md, mi, _coverage(cov["valid"], axis_name)
        return md, mi

    in_specs = [
        P(),                          # queries replicated
        P(axis_name, None),           # centers
        P(axis_name, None),           # centers_rot
        P(),                          # rotation replicated
        P(axis_name, None, None) if per_cluster else P(),
        P(axis_name, None, None),     # packed codes
        P(axis_name, None),           # indices
        P(axis_name),                 # list_sizes
        P(axis_name, None),           # rec_norms
    ]
    if has_cache:
        in_specs.append(P(axis_name, None, None))
    if has_scales:
        in_specs.append(P(axis_name, None))
    if has_scales or has_fac:
        in_specs.append(P(axis_name, None))
    if has_fac:
        in_specs.append(P(axis_name, None))
    if partial:
        in_specs.append(P())
    fn = jax.jit(shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()) + ((P(),) if partial else ()),
        check_vma=False,
    ))
    return fn, tail_plan, tail_cp


def sharded_ivf_pq_build(
    params,
    dataset,
    mesh: Mesh,
    axis_name: str = "shard",
):
    """Sharded IVF-PQ build: quantizers (coarse centers, rotation, PQ
    codebooks) are trained ONCE on a subsample, then each device encodes
    ITS row shard under ``shard_map`` — the FLOP-heavy stage (coarse
    assignment + per-subspace argmin) scales linearly over the mesh, the
    reference's multi-GPU build split (raft-dask builds per-worker parts
    against shared quantizers). Each device then packs the lists it owns
    in ``sharded_ivf_pq_search`` (its C/S contiguous lists) from the
    all-gathered codes, so the list arrays come back list-sharded and no
    device ever holds all of them; at DEEP-1B scale the all-gather of
    the codes would become an all-to-all. The norms and the int8
    decoded-residual cache are built on each device from its own lists
    and come back list-sharded too. The three device programs are held
    across builds of the same shapes (:func:`_hold`).

    Returns a regular ``ivf_pq.Index`` with GLOBAL row ids; pass it to
    ``sharded_ivf_pq_search`` to search list-sharded over the mesh.
    """
    from raft_tpu.neighbors import ivf_pq

    n, dim = dataset.shape
    nshards = mesh.shape[axis_name]
    if n % nshards != 0:
        raise ValueError(f"dataset rows {n} not divisible by mesh axis {nshards}")
    if int(params.n_lists) % nshards != 0:
        raise ValueError(f"n_lists {params.n_lists} not divisible by mesh "
                         f"axis {nshards}")
    # row shards straight onto their devices: the dataset never sits
    # whole on one chip (a no-op when it is already placed so)
    dataset = jax.device_put(dataset, NamedSharding(mesh, P(axis_name, None)))

    frac = float(params.kmeans_trainset_fraction)
    if 0 < frac < 1.0 and int(n * frac) >= int(params.n_lists):
        trainset = dataset[:: max(int(1.0 / frac), 1)]
    else:
        trainset = dataset
    quant = ivf_pq._quantizer_index(params, trainset, dim)

    rows = n // nshards
    # each device encodes its shard in row chunks, so the encode's
    # transients (rotated rows, residuals) stay at a chunk's size
    chunk = next(c for c in range(min(rows, 1 << 20), 0, -1)
                 if rows % c == 0)
    labels, packed = _hold(
        ("ivf_pq_encode", mesh, axis_name, _shapes(dataset, quant), chunk),
        lambda: _encode_program(mesh, axis_name, rows, chunk))(dataset,
                                                                quant)

    from raft_tpu.neighbors.ivf_flat import _aligned_cap

    counts = np.bincount(np.asarray(labels), minlength=quant.n_lists)
    cap = _aligned_cap(int(counts.max()))
    lists = quant.n_lists // nshards
    codes_packed, indices, list_sizes = _hold(
        ("ivf_pq_pack", mesh, axis_name, _shapes(labels, packed), lists,
         cap),
        lambda: _pack_program(mesh, axis_name, n, lists, cap))(labels,
                                                               packed)
    # each device scores its own lists: a scan over the list axis of the
    # list-sharded codes would gather them whole onto every device (4 x
    # v5e at 40M rows: 9.6 GB, RESOURCE_EXHAUSTED)
    per_cluster = (int(params.codebook_kind)
                   == ivf_pq.codebook_gen.PER_CLUSTER)
    books = P(axis_name) if per_cluster else P()
    statics = (int(params.codebook_kind), quant.pq_dim, int(params.pq_bits))
    rec_norms = _hold(
        ("ivf_pq_rec_norms", mesh, axis_name,
         _shapes(codes_packed, quant.pq_centers), statics),
        lambda: jax.jit(shard_map(
            lambda codes, bk: ivf_pq._rec_norms(codes, bk, *statics),
            mesh=mesh, in_specs=(P(axis_name), books),
            out_specs=P(axis_name), check_vma=False,
        )))(codes_packed, quant.pq_centers)
    index = dataclasses.replace(quant, codes=codes_packed, indices=indices,
                                list_sizes=list_sizes, rec_norms=rec_norms)
    if ivf_pq._resolve_cache_kind(index) != "i8":
        return ivf_pq._attach_cache(index)
    # the int8 cache, built the same way list by list on each device; its
    # scale is the bound of the whole codebook, so every device's agrees
    scale = ivf_pq._recon_scale(quant.pq_centers)
    cache = _hold(
        ("ivf_pq_cache", mesh, axis_name,
         _shapes(codes_packed, quant.pq_centers), statics),
        lambda: jax.jit(shard_map(
            lambda codes, bk, sc: ivf_pq._recon_cache_lists(
                codes, bk, sc, *statics),
            mesh=mesh, in_specs=(P(axis_name), books, P()),
            out_specs=P(axis_name), check_vma=False,
        )))(codes_packed, quant.pq_centers, scale)
    return dataclasses.replace(
        index, recon_cache=cache, recon_scale=float(scale),
        cache_scales=None, cache_qnorms=None, cache_fac=None)


def _encode_program(mesh, axis_name, rows: int, chunk: int):
    """Each device labels and PQ-encodes its row shard against the
    quantizers (an argument, replicated), ``chunk`` rows at a time: a
    chunk is sliced from the shard as it lies (a reshape of the whole
    shard would copy it to another layout first)."""
    from raft_tpu.neighbors import ivf_pq

    def local_encode(part, quant):
        def encode(blk):
            return ivf_pq.encode(quant, blk)

        def step(c, out):
            blk = jax.lax.dynamic_slice_in_dim(part, c * chunk, chunk, 0)
            return tuple(jax.lax.dynamic_update_slice_in_dim(
                o, r, c * chunk, 0) for o, r in zip(out, encode(blk)))

        shapes = jax.eval_shape(encode, part[:chunk])
        init = tuple(jnp.zeros((rows,) + sd.shape[1:], sd.dtype)
                     for sd in shapes)
        return jax.lax.fori_loop(0, rows // chunk, step, init)

    return jax.jit(shard_map(
        local_encode,
        mesh=mesh,
        in_specs=(P(axis_name, None), P()),
        out_specs=(P(axis_name), P(axis_name, None)),
        check_vma=False,
    ))


def _pack_program(mesh, axis_name, n: int, lists: int, cap: int):
    """Each device packs the ``lists`` lists it owns from the
    all-gathered labels and codes."""
    from raft_tpu.neighbors.ivf_flat import _pack_lists

    def local_pack(lab, codes):
        # rows of other devices' lists get label ``lists``: dropped
        lab = jax.lax.all_gather(lab, axis_name, tiled=True)
        codes = jax.lax.all_gather(codes, axis_name, tiled=True)
        lo = jax.lax.axis_index(axis_name) * lists
        mine = (lab >= lo) & (lab < lo + lists)
        return _pack_lists(codes, jnp.where(mine, lab - lo, lists),
                           jnp.arange(n, dtype=jnp.int32), lists, cap)

    return jax.jit(shard_map(
        local_pack,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name, None)),
        out_specs=(P(axis_name),) * 3,
        check_vma=False,
    ))


def sharded_cagra_build(
    params,
    dataset,
    mesh: Mesh,
    axis_name: str = "shard",
):
    """Row-sharded CAGRA: each shard builds an independent graph over its
    dataset partition — the raft-dask per-worker-index model (each Dask
    worker builds/owns an ANN index over its partition; queries broadcast,
    results merged). Returns a ``cagra.Index`` whose arrays carry a
    leading shard axis ([S, rows, ...]) with LOCAL graph ids.

    The per-shard builds run sequentially on the default device (the
    build pipeline is host-orchestrated); the stacked result is laid out
    for ``sharded_cagra_search``'s shard_map.
    """
    from raft_tpu.neighbors import cagra

    import dataclasses

    dataset = jnp.asarray(dataset)
    n = dataset.shape[0]
    nshards = mesh.shape[axis_name]
    if n % nshards != 0:
        raise ValueError(f"dataset rows {n} not divisible by mesh axis {nshards}")
    rows = n // nshards
    # per-shard inline packing happens below with a GLOBAL dequant scale
    # (per-shard scales would diverge and the stacked Index carries one).
    # Eligibility is budgeted on the PER-SHARD residency (max_rows=rows):
    # search-time HBM holds one shard's table under shard_map, so an
    # S-way mesh keeps the fused beam kernel at S times the single-chip
    # scale (the build still materializes the stacked pack host-side —
    # transient, not the search-time bound)
    want_inline = bool(params.inline_codes)
    params = dataclasses.replace(params, inline_codes=False)
    subs = []
    for s in range(nshards):
        subs.append(cagra.build(params, dataset[s * rows:(s + 1) * rows]))
    graphs = jnp.stack([s.graph for s in subs])          # [S, rows, deg]
    datasets = jnp.stack([s.dataset for s in subs])      # [S, rows, d]
    norms = (jnp.stack([s.data_norms for s in subs])
             if subs[0].data_norms is not None else None)
    out = cagra.Index(dataset=datasets, graph=graphs,
                      metric=subs[0].metric, data_norms=norms)
    d = dataset.shape[1]
    deg = graphs.shape[2]
    need_norms = out.metric != DistanceType.InnerProduct
    if want_inline and cagra._inline_eligible(n, d, deg, need_norms,
                                              max_rows=rows):
        scale = cagra._code_scale(dataset)
        packs, codes = [], []
        for s in subs:
            p_, c_, _ = cagra._pack_tables(
                s.dataset, s.graph, need_norms, scale=scale)
            packs.append(p_)
            codes.append(c_)
        out = dataclasses.replace(
            out, nbr_pack=jnp.stack(packs),              # [S, rows, W]
            flat_codes=jnp.stack(codes),                 # [S, rows, d] i8
            code_scale=float(scale),
        )
    return out


def sharded_cagra_search(
    search_params,
    index,
    queries,
    k: int,
    mesh: Mesh,
    axis_name: str = "shard",
) -> Tuple[jax.Array, jax.Array]:
    """Beam search over a row-sharded CAGRA index (from
    ``sharded_cagra_build``): queries are replicated, every device runs
    the beam search on its own sub-graph, local ids get the shard's row
    offset, and the per-shard top-ks are all-gathered + merged over ICI
    (the knn_merge_parts-over-comms pattern,
    detail/knn_merge_parts.cuh:140).

    When the index carries the stacked inline layout (sharded_cagra_build
    with inline_codes=True), each shard runs the FUSED Pallas beam kernel
    on its own sub-graph — the same kernel as single-chip search, with
    the per-shard packed table and int8 codes threaded through shard_map
    (local itopk per shard, merged over ICI; the reference's multi-GPU
    CAGRA similarly runs its single-CTA kernel per GPU and merges).
    ``scan_impl`` resolution matches single-device search: "auto" picks
    the kernel on TPU, the exact scattered-gather path elsewhere;
    "pallas_interpret" forces the kernel in interpret mode (CPU-mesh
    parity tests / dryrun)."""
    from raft_tpu.neighbors import cagra

    queries = jnp.asarray(queries)
    nshards = mesh.shape[axis_name]
    S, rows, _ = index.dataset.shape
    if S != nshards:
        raise ValueError(f"index has {S} shards, mesh axis has {nshards}")
    select_min = is_min_close(index.metric)
    itopk, width, iters, n_seeds = cagra.search_plan(search_params, k)
    has_norms = index.data_norms is not None
    dtype = str(getattr(search_params, "compute_dtype", "auto"))
    requested = str(getattr(search_params, "scan_impl", "auto"))
    # same resolver + validation as single-device cagra.search
    impl = cagra._resolve_beam_impl(requested, index, dtype)
    fused = impl.startswith("pallas")
    if fused and index.nbr_pack is None:
        raise ValueError(
            "scan_impl=%r needs the stacked inline layout (build with "
            "sharded_cagra_build inline_codes=True)" % impl)
    if fused and dtype != "auto":
        raise ValueError(
            "scan_impl=%r scores int8 traversal distances; compute_dtype "
            "must stay 'auto' (got %r)" % (impl, dtype))

    def local(q, ds, graph, *rest):
        rank = jax.lax.axis_index(axis_name)
        rest = list(rest)
        norms = rest.pop(0)[0] if has_norms else None
        if fused:
            pack = rest.pop(0)[0]                        # [rows, W]
            codes = rest.pop(0)[0]                       # [rows, d] i8
            # graft-lint: allow-hand-wired-pipeline cagra's beam loop compiles as one scan node (ROADMAP 8(b)); the sharded variant calls the kernel arm directly
            d, i = cagra._beam_search_pallas(
                q, ds[0], graph[0], norms, pack, codes,
                jnp.float32(index.code_scale), int(k), itopk, width,
                iters, int(index.metric), n_seeds,
                impl == "pallas_interpret",
            )
        else:
            # graft-lint: allow-hand-wired-pipeline cagra's beam loop compiles as one scan node (ROADMAP 8(b)); the sharded variant calls the kernel arm directly
            d, i = cagra._beam_search(
                q, ds[0], graph[0], norms, int(k), itopk, width, iters,
                int(index.metric), "f32" if dtype == "auto" else dtype,
                n_seeds,
            )
        i = jnp.where(i >= 0, i + (rank * rows).astype(i.dtype), -1)
        gd = jax.lax.all_gather(d, axis_name, axis=1, tiled=True)
        gi = jax.lax.all_gather(i, axis_name, axis=1, tiled=True)
        return merge_topk(gd, gi, k, select_min)

    args = [queries, index.dataset, index.graph]
    in_specs = [P(), P(axis_name, None, None), P(axis_name, None, None)]
    if has_norms:
        args.append(index.data_norms)
        in_specs.append(P(axis_name, None))
    if fused:
        args.append(index.nbr_pack)
        in_specs.append(P(axis_name, None, None))
        args.append(index.flat_codes)
        in_specs.append(P(axis_name, None, None))

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    with obs.entry_span("search", "sharded_cagra",
                        queries=int(queries.shape[0]), k=int(k),
                        shards=int(nshards)):
        return jax.jit(fn)(*args)


def sharded_ivf_build(
    params,
    dataset,
    mesh: Mesh,
    axis_name: str = "shard",
):
    """Sharded IVF-Flat build: coarse centers are trained ONCE on a
    subsample (the reference trains on a fraction anyway,
    kmeans_trainset_fraction), then every shard packs ITS dataset rows
    into the shared list structure — the per-shard extend +
    shared-centers pattern of the reference's multi-GPU builds. Returns
    an ``ivf_flat.Index`` whose list arrays carry a leading shard axis
    ([S, n_lists, cap, ...]) with GLOBAL row ids, consumable by
    ``sharded_ivf_row_search``."""
    from raft_tpu.neighbors import ivf_flat

    dataset = jnp.asarray(dataset)
    n = dataset.shape[0]
    nshards = mesh.shape[axis_name]
    if n % nshards != 0:
        raise ValueError(f"dataset rows {n} not divisible by mesh axis {nshards}")
    rows = n // nshards
    subs = []
    for s in range(nshards):
        part = dataset[s * rows:(s + 1) * rows]
        ids = jnp.arange(s * rows, (s + 1) * rows, dtype=jnp.int32)
        if s == 0:
            sub = ivf_flat.build(params, part, row_ids=ids)
            empty = ivf_flat.Index(
                centers=sub.centers,
                storage=jnp.zeros((sub.n_lists, 0) + sub.storage.shape[2:],
                                  sub.storage.dtype),
                indices=jnp.zeros((sub.n_lists, 0), jnp.int32),
                list_sizes=jnp.zeros((sub.n_lists,), jnp.int32),
                metric=sub.metric, metric_arg=sub.metric_arg,
                data_norms=(jnp.zeros((sub.n_lists, 0), jnp.float32)
                            if sub.data_norms is not None else None),
            )
        else:
            # every later shard packs its rows against shard-0's centers
            # (shared coarse quantizer -> identical bucketing everywhere)
            sub = ivf_flat.extend(empty, part, ids)
        subs.append(sub)
    cap = max(s.storage.shape[1] for s in subs)

    def padcap(a, fill):
        return jnp.pad(a, [(0, 0), (0, cap - a.shape[1])] +
                       [(0, 0)] * (a.ndim - 2), constant_values=fill)

    storage = jnp.stack([padcap(s.storage, 0) for s in subs])
    indices = jnp.stack([padcap(s.indices, -1) for s in subs])
    sizes = jnp.stack([s.list_sizes for s in subs])
    centers = jnp.stack([s.centers for s in subs])
    norms = (jnp.stack([padcap(s.data_norms, 0) for s in subs])
             if subs[0].data_norms is not None else None)
    from raft_tpu.neighbors.ivf_flat import Index as FlatIndex

    return FlatIndex(centers=centers, storage=storage, indices=indices,
                     list_sizes=sizes, metric=subs[0].metric,
                     data_norms=norms)


def sharded_ivf_row_search(
    search_params,
    index,
    queries,
    k: int,
    mesh: Mesh,
    axis_name: str = "shard",
) -> Tuple[jax.Array, jax.Array]:
    """Search a row-sharded IVF-Flat index (from ``sharded_ivf_build``):
    every device probes its own full list structure (which holds only its
    dataset partition's rows) with the FULL n_probes, then shard top-ks
    are all-gathered + merged."""
    from raft_tpu.neighbors import ivf_flat

    queries = jnp.asarray(queries)
    nshards = mesh.shape[axis_name]
    S = index.centers.shape[0]
    if S != nshards:
        raise ValueError(f"index has {S} shards, mesh axis has {nshards}")
    C = index.centers.shape[1]
    n_probes = int(min(search_params.n_probes, C))
    select_min = is_min_close(index.metric)
    metric = int(index.metric)
    group = ivf_flat.adaptive_query_group(
        int(queries.shape[0]), n_probes, C, int(search_params.query_group),
    )
    has_norms = index.data_norms is not None

    def local(q, centers, storage, indices, list_sizes, *rest):
        norms = rest[0][0] if has_norms else None
        # graft-lint: allow-hand-wired-pipeline deliberate single-stage fast path: one collective per-shard scan + merge, no multi-stage tail
        d, i = ivf_flat._ivf_search(
            q, centers[0], storage[0], indices[0], list_sizes[0],
            int(k), n_probes, metric, group,
            int(search_params.bucket_batch),
            str(search_params.compute_dtype),
            float(search_params.local_recall_target),
            float(search_params.merge_recall_target),
            norms, None,
        )
        gd = jax.lax.all_gather(d, axis_name, axis=1, tiled=True)
        gi = jax.lax.all_gather(i, axis_name, axis=1, tiled=True)
        return merge_topk(gd, gi, k, select_min)

    args = [queries, index.centers, index.storage, index.indices,
            index.list_sizes]
    in_specs = [P(), P(axis_name, None, None), P(axis_name, None, None, None),
                P(axis_name, None, None), P(axis_name, None)]
    if has_norms:
        args.append(index.data_norms)
        in_specs.append(P(axis_name, None, None))

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    with obs.entry_span("search", "sharded_ivf_row",
                        queries=int(queries.shape[0]), k=int(k),
                        shards=int(nshards)):
        return jax.jit(fn)(*args)


def sharded_pairwise_distance(
    x,
    y,
    mesh: Mesh,
    axis_name: str = "shard",
    metric="sqeuclidean",
    metric_arg: float = 2.0,
) -> jax.Array:
    """Pairwise distance with x row-sharded over the mesh: each device
    computes its row block against replicated y; the result stays sharded
    (the caller sees one logical [m, n] array)."""
    from raft_tpu.distance.pairwise import _pairwise

    metric = resolve_metric(metric)
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    nshards = mesh.shape[axis_name]
    if x.shape[0] % nshards != 0:
        raise ValueError(f"x rows {x.shape[0]} not divisible by mesh axis {nshards}")

    def local(xs, yr):
        return _pairwise(xs, yr, int(metric), float(metric_arg), None, None)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis_name, None), P()),
        out_specs=P(axis_name, None),
        check_vma=False,
    )
    return jax.jit(fn)(x, y)
