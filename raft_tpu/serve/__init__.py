"""graft-serve: the online serving engine (ISSUE 5; docs/serving.md).

Everything before this package is a library: you hold the index, you
call search, you own the batch shapes. ``raft_tpu.serve`` makes it a
*service* — the piece FusionANNS (PAPERS.md) shows the end-to-end win
lives in, and the piece TPU-KNN's peak-FLOP/s numbers quietly assume
(fixed, padded batch shapes):

* **dynamic micro-batching** (:mod:`raft_tpu.serve.batcher`) —
  concurrent ``submit(query, k)`` calls coalesce into padded batches
  drawn from a fixed power-of-two bucket ladder, warmed at startup so
  steady-state serving never traces (the GL007 zero-recompile bar);
  bounded-queue backpressure rejects with :class:`Overloaded`
  (classified transient through ``resilience``);
* **versioned hot-swap** (:mod:`raft_tpu.serve.registry`) — named
  indexes advance through refcounted generations: background build/load,
  one atomic swap, in-flight batches finish on the generation they
  pinned, the old one frees when its last pin drains;
* **tombstone mutation** (:mod:`raft_tpu.serve.mutation`) —
  ``delete``/``upsert`` as a keep-mask composed into the existing
  filtered-search paths of all four index types, upserts served from a
  brute-force side buffer merged via ``merge_topk`` until a background
  ``extend`` + swap compacts them in;
* the engine (:mod:`raft_tpu.serve.engine`) threading it through
  ``obs`` (queue depth, fill ratio, rejects, swaps, per-bucket
  latency), ``resilience.run`` (classified retry; OOM downshifts the
  bucket ceiling), and ``tuning`` (measured bucket choice, learned
  row budgets);
* **multi-host fabric** (:mod:`raft_tpu.serve.fabric`, ISSUE 6) — the
  cluster tier: N worker processes own index shards
  (:mod:`raft_tpu.comms.procgroup`), a router fans each micro-batch to
  shard owners with health-tracked circuit breaking, hedged retries,
  per-row coverage on degraded answers, and a two-phase cross-host
  hot-swap barrier over the registry (docs/serving.md §10);
* **self-healing control plane** (:mod:`raft_tpu.serve.controller`,
  ISSUE 18) — graft-helm closes the cluster loops the fabric leaves to
  an operator: p2c replica load-balancing feeds a controller that
  rebalances shards off workers whose circuits stay open past the
  tuning budget and autoscales the worker set on saturated-stage
  signals with cooldown/hysteresis (docs/serving.md §10);
* **online quality control** (:mod:`raft_tpu.serve.quality`, ISSUE 19)
  — graft-gauge samples answered live queries onto a best-effort
  shadow lane, re-runs them through the generation-pinned exhaustive
  oracle, exports windowed Wilson-interval recall estimates
  (``serve.recall_estimate{index,rung}``), and closes the loop:
  bounded ``AdaptivePolicy`` retunes under the stated recall band and
  probation rollback of a degrading hot-swap (docs/serving.md §14).
"""

from raft_tpu.serve.adaptive import AdaptivePolicy, probe_ladder
from raft_tpu.serve.controller import HelmController, HelmParams
from raft_tpu.serve.batcher import (
    Batch,
    MicroBatcher,
    Overloaded,
    Request,
    bucket_ladder,
    choose_bucket,
)
from raft_tpu.serve.engine import ServeParams, Server
from raft_tpu.serve.fabric import (
    Fabric,
    FabricParams,
    FabricSwapError,
    WorkerHealth,
)
from raft_tpu.serve.mutation import MutableState
from raft_tpu.serve.quality import QualityMonitor, wilson_interval
from raft_tpu.serve.registry import Generation, Registry

# the jitted hot-path entry points whose trace caches must stay FLAT in
# steady-state serving — the serve-side extension of
# obs.metrics._TRACKED_JITS; tests/test_serve.py asserts zero growth
# across a mixed-size post-warmup stream with trace_cache_sizes()
TRACKED_JITS = (
    ("raft_tpu.neighbors.brute_force", "_search"),
    ("raft_tpu.neighbors.ivf_flat", "_ivf_search"),
    ("raft_tpu.neighbors.ivf_flat", "_build_slot_keep"),
    ("raft_tpu.neighbors.ivf_flat", "_coarse_margins"),
    ("raft_tpu.neighbors.ivf_pq", "_pq_search"),
    ("raft_tpu.neighbors.cagra", "_beam_search"),
    ("raft_tpu.neighbors.cagra", "_beam_search_pallas"),
    ("raft_tpu.neighbors.refine", "_refine"),
    ("raft_tpu.neighbors.tiered", "_score_fetched"),
    ("raft_tpu.neighbors.tiered", "_score_fetched_hot"),
    ("raft_tpu.neighbors.tiered", "_promote_scatter"),
    ("raft_tpu.serve.engine", "_merge_with_side"),
    ("raft_tpu.neighbors.hybrid", "_fuse_rescore"),
    ("raft_tpu.sparse.neighbors", "_score_block_dense_q"),
    ("raft_tpu.matrix.select_k", "_select_k"),
    ("raft_tpu.matrix.select_k", "_tournament_topk"),
)


def trace_cache_sizes() -> dict:
    """Per-function jit trace-cache entry counts for the serving hot
    paths (the GL007 trace-counting hook, serving edition). Compare
    before/after a traffic window: any growth means a shape escaped the
    bucket/k ladder."""
    import importlib

    out = {}
    for mod_name, fn_name in TRACKED_JITS:
        try:
            fn = getattr(importlib.import_module(mod_name), fn_name, None)
        except ImportError:
            continue
        size_of = getattr(fn, "_cache_size", None)
        if size_of is None:
            continue
        try:
            out[f"{mod_name.rsplit('.', 1)[-1]}.{fn_name}"] = int(size_of())
        except Exception:  # noqa: BLE001 — private jax API probe; a missing gauge is the degraded answer
            continue
    return out


def total_trace_count() -> int:
    """Sum of :func:`trace_cache_sizes` — the single number the
    trace-stability acceptance test pins."""
    return sum(trace_cache_sizes().values())


__all__ = [
    "AdaptivePolicy", "Batch", "Fabric", "FabricParams",
    "FabricSwapError", "Generation", "HelmController", "HelmParams",
    "MicroBatcher", "MutableState", "Overloaded", "QualityMonitor",
    "Registry",
    "Request", "ServeParams", "Server", "TRACKED_JITS", "WorkerHealth",
    "bucket_ladder", "choose_bucket", "probe_ladder",
    "total_trace_count", "trace_cache_sizes", "wilson_interval",
]
