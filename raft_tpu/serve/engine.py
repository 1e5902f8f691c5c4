"""The serving engine: Server, per-index serving units, warmed dispatch.

This ties the serving pieces together (docs/serving.md §1):

    submit ──► MicroBatcher (bucket ladder, backpressure)
                   │ Batch
                   ▼
            dispatch  ──pin──►  Registry generation (hot-swap)
                   │                    │ handle
                   ▼                    ▼
        resilience.run( main filtered search + side-buffer search
                        + merge_topk )  ◄── MutableState (tombstones)
                   │
                   ▼
            futures resolved with host (distances, external ids)

Trace discipline: every device-facing shape is drawn from a finite set —
query rows from the bucket ladder, k from the k-ladder (powers of two
plus the ``max_k`` top rung),
filter words from the mutation state's power-of-two filter-capacity
rung (:meth:`MutableState.filter_capacity` — so per-upsert id growth
does not change the kernels' static ``filter_nbits``), side-buffer rows
from its power-of-two capacity — and :meth:`Server.warmup` drives each
combination once at publish time, so steady-state serving dispatches
only cached executables (the GL007 zero-recompile requirement; the
`test_serve` suite asserts it with the same trace-counting hook).

Failure discipline: batch dispatch runs under
:func:`raft_tpu.resilience.run` (classified retry for transient /
dead-backend); an OOM-classified failure downshifts the batcher's
bucket ceiling (recorded via ``tuning.record_budget`` so later servers
in the process start safe), splits the batch, and re-dispatches — the
serving instance of the resilience OOM ladder.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import obs, tuning
from raft_tpu.analysis import lockwatch
from raft_tpu.obs import config as _obs_config
from raft_tpu.obs import trace as obs_trace
from raft_tpu.core import pipeline as _pipeline
from raft_tpu.core.bitset import Bitset
from raft_tpu.distance.types import is_min_close, resolve_metric
from raft_tpu.neighbors import brute_force, cagra, hybrid, ivf_flat, ivf_pq
from raft_tpu.neighbors.common import BitsetFilter, merge_topk
from raft_tpu.resilience import errors as _rerrors
from raft_tpu.resilience import faultinject
from raft_tpu.serve import adaptive as _adaptive
from raft_tpu.serve.batcher import (
    Batch,
    MicroBatcher,
    Overloaded,
    Request,
    choose_bucket,
    pad_rows,
)
from raft_tpu.serve.mutation import MutableState
from raft_tpu.serve.quality import QualityMonitor
from raft_tpu.serve.registry import Registry

ALGOS = ("brute_force", "ivf_flat", "ivf_pq", "cagra", "hybrid")

# the refine over-fetch a rabitq-cache index is served at when the
# caller left refine_ratio defaulted — ONE home: _Handle.pipeline_rr
# feeds dispatch AND warmup, which must agree or warmup traces the
# wrong shortlist-width rungs and steady state silently recompiles
RABITQ_DEFAULT_REFINE_RATIO = 4

# latency histogram edges tuned for ms-scale online serving
_LAT_BUCKETS = (0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 5000)

# difficulty-margin histogram edges: the policy thresholds live in the
# low decades (floor ~0.02, easy ~0.20), so the mass needs resolution
# there (docs/serving.md §13)
_MARGIN_BUCKETS = (0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.35, 0.5, 1.0)


@dataclasses.dataclass
class ServeParams:
    """Serving knobs (docs/serving.md §6)."""

    max_batch_rows: int = 256       # bucket-ladder top (rounded to pow2)
    max_wait_ms: float = 2.0        # linger before dispatching a partial batch
    max_queue_rows: int = 4096      # admission bound -> Overloaded past it
    max_k: int = 128                # k-ladder top (requests cap here)
    side_capacity: int = 64         # initial upsert side-buffer capacity (pow2)
    compact_threshold: int = 512    # side rows that trigger background
    #                                 compaction (0 = manual compact() only)
    warmup: bool = True             # trace the ladder at publish time
    dispatch_retries: int = 2       # classified transient/dead retries
    retry_backoff_s: float = 0.05
    request_timeout_s: float = 120.0  # Server.search() convenience bound
    # tiered-memory rerank (docs/serving.md §12, ISSUE 12): keep the
    # raw originals HOST-resident and fetch only unique shortlist rows
    # per batch through neighbors.tiered, instead of uploading the
    # whole dataset per generation (ivf_pq with refine_ratio > 1 or a
    # rabitq cache). hot_rows=None draws the HBM hot-row budget from
    # tuning.budget("tiered_hot_rows").
    tiered_rerank: bool = False
    tiered_hot_rows: Optional[int] = None
    # bounded result cache in front of dispatch: repeated queries
    # (Zipf traffic) answered without touching the engine, keyed on
    # (query bytes, k) x generation x mutation epoch so hot-swap and
    # delete/upsert invalidate correctly. 0 = off.
    result_cache_entries: int = 0
    # SLO-aware adaptive execution (ISSUE 14, docs/serving.md §13):
    # per-query difficulty (coarse centroid-distance margin) chooses a
    # pow2 probe rung for ivf_flat/ivf_pq; the resolved n_probes (the
    # old exhaustive pin) becomes the ladder's CEILING. Ambiguous
    # queries escape to the top rung — bitwise-identical to the
    # non-adaptive path — so correctness-first deployments lose
    # nothing by leaving this off (the default).
    adaptive_probes: bool = False
    # default per-request SLO deadline (ms from submit); per-call
    # submit(deadline_ms=...) overrides. None = no deadline.
    deadline_ms: Optional[float] = None
    # what to do with a request whose slack no longer covers the
    # measured service estimate: "downshift" drops it one probe rung at
    # a time (adaptive indexes only; sheds when the floor rung still
    # misses), "shed" fails it immediately with
    # Overloaded(reason="deadline") — both counted in
    # serve.deadline_shed_total{action}
    deadline_action: str = "downshift"
    # multi-tenant admission: per-index pending-row quotas atop the
    # shared max_queue_rows backpressure ({index_name: rows}); and an
    # optional server-wide pending-row bound across all indexes. Both
    # reject with Overloaded(reason="quota") (transient).
    admission_quotas: Optional[Dict[str, int]] = None
    max_total_queue_rows: Optional[int] = None
    # graft-gauge online quality control (ISSUE 19, docs/serving.md
    # §14): sample this fraction of answered live requests onto the
    # batcher's best-effort shadow lane, re-run them through the
    # generation-pinned exhaustive oracle, and export windowed
    # Wilson-interval recall estimates. 0 disables the whole subsystem
    # (the delivery hook is then a single attribute read).
    quality_sample_rate: float = 0.0
    # stated recall floor the closed loop defends; None draws from
    # tuning.budget("serve_recall_band_bp") (default 9000 = 0.90)
    quality_band: Optional[float] = None
    quality_window: int = 128        # samples per estimate window
    quality_min_samples: int = 24    # no verdicts below this many
    # actuators: margin/refine retune (bounded, with hysteresis) and
    # post-swap probation rollback
    quality_retune: bool = True
    quality_rollback: bool = True
    quality_max_retunes: int = 8
    # shadow-lane row bound (drop-oldest past it; never backpressures
    # live admission)
    shadow_queue_rows: int = 256
    # graft-flow dispatch pipelining (docs/serving.md §12): the batcher
    # thread stops at ASYNC dispatch and hands the in-flight batch (a
    # ticket holding its pinned generation) to a per-index completion
    # thread that syncs + delivers, so batch N+1's host work — padding,
    # H2D upload, and the tiered rerank gather — overlaps batch N's
    # device time. The value bounds tickets in flight (backpressure
    # blocks the batcher past it); 0 forces the classic synchronous
    # dispatch, bitwise-identical results either way. None draws from
    # tuning.budget("pipeline_depth") (default 2).
    pipeline_depth: Optional[int] = None


class _Handle:
    """One generation's immutable serving state: the index, its searcher
    configuration, and the (shared, mutable) tombstone overlay."""

    __slots__ = ("algo", "index", "state", "search_params",
                 "user_search_params", "build_params",
                 "refine_ratio", "metric", "select_min", "dtype", "dim",
                 "rows", "raw_dataset", "_raw_dev", "_side_cache",
                 "tiered_source", "adaptive", "_plan_cache",
                 "_plan_memo")

    def __init__(self, algo: str, index, state: MutableState,
                 search_params, build_params, refine_ratio: int,
                 raw_dataset: Optional[np.ndarray],
                 user_search_params=None, tiered_source=None,
                 adaptive=None):
        self.algo = algo
        self.index = index
        self.state = state
        self.search_params = search_params
        # the params the CALLER supplied (None when defaulted): a swap
        # inherits these, not the resolved ones — the serving defaults
        # (n_probes = n_lists, now the adaptive ladder's exhaustive
        # CEILING) must be re-derived against the NEW index, or a
        # bigger successor silently serves the old index's probe count
        # — and the whole probe-rung ladder, not just the ceiling,
        # re-derives with it (ISSUE 14)
        self.user_search_params = user_search_params
        self.build_params = build_params
        self.refine_ratio = int(refine_ratio)
        self.metric = _index_metric(algo, index)
        self.select_min = is_min_close(self.metric)
        self.rows = _index_rows(algo, index)
        self.dim = _index_dim(algo, index)
        self.dtype = np.dtype(np.float32)
        self.raw_dataset = raw_dataset
        self._raw_dev = None                  # device copy, cached lazily
        self._side_cache: Optional[Tuple[int, object, object]] = None
        # tiered rerank source (ISSUE 12): when set, the ivf_pq refine
        # paths fetch only unique shortlist rows from the HOST raw
        # store instead of device-uploading it wholesale (raw_dev).
        # Per-generation on purpose — a swap/compaction gets a FRESH
        # hot-row cache, so stale rows can never serve after a content
        # change.
        self.tiered_source = tiered_source
        # SLO-aware adaptive policy (ISSUE 14): per-generation, like
        # everything shape-bearing — its ladder tops at THIS index's
        # resolved n_probes ceiling, so a swap re-derives the whole
        # ladder (not just the ceiling) against the successor index
        self.adaptive = adaptive
        # compiled query plans (ISSUE 20): one CompiledPlan per
        # (k, rung, n_probes, refine_ratio) point, built lazily and by
        # warmup — per GENERATION, so a swap/compaction recompiles
        # against the successor index by construction. The memo shares
        # derived device arrays (the slot-substituted indices block)
        # across this handle's variants.
        self._plan_cache: Dict[tuple, object] = {}
        self._plan_memo: Dict[str, object] = {}

    def pipeline_rr(self) -> int:
        """The refine_ratio the multi-stage pipeline dispatches at:
        the caller's when set, else the rabitq serving default. Used
        by BOTH dispatch and warmup — they must agree, or warmup
        traces the wrong shortlist-width rungs and steady-state
        serving recompiles per batch."""
        return (self.refine_ratio if self.refine_ratio > 1
                else RABITQ_DEFAULT_REFINE_RATIO)

    def margins(self, qdev) -> jax.Array:
        """Per-query difficulty margins from the coarse quantizer (the
        adaptive policy's input); only called when ``adaptive`` is
        set. One jitted shape per query bucket — warmup traces it."""
        mod = ivf_flat if self.algo == "ivf_flat" else ivf_pq
        return mod.coarse_margins(self.index, qdev,
                                  p=self.adaptive.margin_p)

    def rung_params(self, rung: Optional[int]):
        """(search_params, rabitq refine_ratio) for a probe rung.

        ``rung=None`` (the non-adaptive path, and the escape hatch's
        target when it equals the ceiling) returns the resolved params
        verbatim. A rung override replaces only ``n_probes`` — the
        trace key is the VALUE, so the top rung dispatches the exact
        program the non-adaptive path compiled (bitwise escape
        hatch). A rung on a NON-adaptive ivf handle is the shadow
        oracle's full-probe override (ISSUE 19) — same replace, same
        trace-key-is-the-value discipline."""
        if rung is None:
            return self.search_params, self.pipeline_rr()
        if rung == "exact":
            # ROADMAP 9(a): the shadow oracle's exact-tier rung —
            # exhaustive probing with the shortlist re-ranked from the
            # exact tier. n_probes carries the VALUE (n_lists), so the
            # trace-key-is-the-value discipline holds: an adaptive and
            # a non-adaptive handle compile the same program here.
            sp = dataclasses.replace(self.search_params,
                                     n_probes=int(self.index.n_lists))
            return sp, self.pipeline_rr()
        if self.adaptive is None:
            if self.algo in ("ivf_flat", "ivf_pq"):
                sp = dataclasses.replace(self.search_params,
                                         n_probes=int(rung))
                return sp, self.pipeline_rr()
            return self.search_params, self.pipeline_rr()
        pol = self.adaptive
        idx = pol.ladder.index(rung) if rung in pol.ladder \
            else len(pol.ladder) - 1
        sp = dataclasses.replace(self.search_params, n_probes=int(rung))
        return sp, pol.refine_for(idx)

    def oracle_rung(self) -> Optional[int]:
        """The shadow oracle's ground-truth rung (graft-gauge, ISSUE
        19): the index's FULL probe count when the serving ceiling sits
        below it, else None (the resolved exhaustive program already IS
        the top tier). The distinction matters for the under-trained-
        swap failure mode: a generation configured with a crippled
        ``n_probes`` would otherwise be its own oracle and score its
        own degraded answers as perfect. ivf_flat at ``n_lists`` probes
        is exact over the filtered index whatever the training quality;
        ivf_pq's refined pipeline reranks its shortlist with exact
        distances — both outrank any ceiling a bad swap can configure.
        brute_force/cagra have no probe axis to escalate.

        ROADMAP 9(a) bias fix: when the generation carries an EXACT
        tier (a tiered ``RerankSource`` or the raw row store), the
        oracle is the exact-rerank plan at exhaustive probing (the
        ``"exact"`` rung) — not the same quantizer's exhaustive rung. A
        quantized oracle scores its own quantization error as ground
        truth: the candidates IT mis-ranks look "matched" when serving
        mis-ranks them the same way, so recall over-estimates on
        ivf_pq/rabitq exactly where the estimate matters."""
        if self.algo not in ("ivf_flat", "ivf_pq"):
            return None
        n_lists = int(self.index.n_lists)
        cur = int(getattr(self.search_params, "n_probes", n_lists))
        if self.algo == "ivf_pq" and (
                getattr(self, "tiered_source", None) is not None
                or getattr(self, "raw_dataset", None) is not None):
            if n_lists > cur:
                return "exact"
            # ceiling already exhaustive: the exact tier still outranks
            # a quantized-only serving path (refine_ratio == 1); the
            # refined pipelines already ARE the exact-rerank program
            return "exact" if self.plan_variant(None) == "plain" else None
        return n_lists if n_lists > cur else None

    def raw_dev(self):
        """Device-resident raw row store (refine operand) — transferred
        once per generation, not per batch."""
        if self._raw_dev is None and self.raw_dataset is not None:
            self._raw_dev = jax.device_put(self.raw_dataset)
        return self._raw_dev

    # -- the per-algo search adapters -------------------------------------

    def search_main(self, qdev, k: int, filt: BitsetFilter,
                    rung: Optional[int] = None):
        """Search the main index through this generation's compiled
        query plan (ISSUE 20); ``rung`` (an adaptive probe-ladder
        value, or the shadow oracle's ``"exact"``) selects among the
        compiled plan variants — each overriding only ``n_probes``
        (and, on the rabitq pipeline, the per-rung refine_ratio), so
        the trace key stays the VALUE. ``rung=None`` is the
        exhaustive/non-adaptive path, byte-for-byte today's."""
        return self.compiled(int(k), rung)(qdev, prefilter=filt)

    def plan_variant(self, rung) -> str:
        """Which canonical serve plan (plan/canonical.py) this handle's
        configuration dispatches for ``rung`` — the same resolution
        order the hand-wired ``search_main`` branched through:
        tiered-source refined, rabitq refined (raw store else packed
        codes), raw-refine over-fetch, else the plain scan. The shadow
        oracle's ``"exact"`` rung is its own variant (same DAG as the
        tiered refined plan; the bias fix is in what the rung binds)."""
        if self.algo != "ivf_pq":
            return "plain"
        if rung == "exact":
            return "exact"
        kind = getattr(self.index, "cache_kind", "none")
        if self.tiered_source is not None and (
                kind == "rabitq" or self.refine_ratio > 1):
            # the tiered-memory shape (docs/serving.md §12): the raw
            # originals stay HOST-resident and the rerank stage fetches
            # only this batch's unique shortlist rows
            return "refined_tiered"
        if kind == "rabitq" and (
                self.raw_dataset is not None
                or int(self.index.codes.shape[-1]) > 0):
            # the rabitq rung IS a multi-stage pipeline: sign-bit first
            # stage + exact rerank. Rerank source: the generation's raw
            # row store when serving kept it, else the index's own PQ
            # codes.
            return ("refined_tiered" if self.raw_dataset is not None
                    else "refined_codes")
        if self.refine_ratio > 1 and self.raw_dataset is not None:
            return "raw_refine"
        return "plain"

    def compiled(self, k: int, rung=None):
        """The compiled plan for one (k, rung) point — cached per
        generation. The key carries the RESOLVED (n_probes,
        refine_ratio) pair, not just the rung, so a quality retune that
        moves a rung's refine ratio compiles a fresh program instead of
        serving a stale one (the hand-wired path re-resolved per call;
        the cache must not change that)."""
        sp, rr = self.rung_params(rung)
        key = (int(k), rung, getattr(sp, "n_probes", None), rr)
        cp = self._plan_cache.get(key)
        if cp is None:
            cp = self._compile_variant(int(k), rung, sp, rr)
            # benign publish race: concurrent threads compile identical
            # programs for the same key; last write wins
            self._plan_cache[key] = cp
        return cp

    def _compile_variant(self, k: int, rung, sp, rr: int):
        from raft_tpu import plan as _plan
        from raft_tpu.neighbors import tiered as _tiered

        variant = self.plan_variant(rung)
        p = _plan.serve_plan(self.algo, variant)
        source = None
        raw_dev = None
        refine_ratio = rr
        if variant in ("refined_tiered", "exact"):
            # the exact tier: the host tiered source when serving keeps
            # one, else the device-resident raw rows as a full-upload
            # source (bitwise-identical scoring either way)
            source = (self.tiered_source
                      if self.tiered_source is not None
                      else _tiered.as_source(self.raw_dev()))
        elif variant == "raw_refine":
            raw_dev = self.raw_dev()
            refine_ratio = self.refine_ratio
        extra = {"select_min": self.select_min}
        if self.algo == "hybrid":
            extra["fuse_expand"] = int(getattr(sp, "fuse_expand", 4))
        return _plan.compile(p, self.index, k=int(k), rung=rung,
                             search_params=sp,
                             refine_ratio=int(refine_ratio),
                             source=source, raw_dev=raw_dev,
                             memo=self._plan_memo, **extra)

    def side_index(self):
        """Brute-force index + device id map over the (padded) side
        buffer, cached per side-content seq — serving rebuilds it only
        when the side buffer's CONTENT changed (an upsert appended or a
        compaction shifted it), not on every mutation: a delete of base
        rows bumps the global ``seq`` for the tombstone bitsets but
        leaves the side vectors untouched, and must not force a
        brute-force rebuild + device re-upload here."""
        with self.state.lock:
            snap = self.side_snapshot_locked()
        return self.side_build(snap)

    def side_snapshot_locked(self) -> Optional[tuple]:
        """Cheap side-content snapshot; the caller must hold
        ``state.lock``. Split from :meth:`side_build` so the dispatcher
        can copy the side rows inside its consistency-pinned critical
        section but run the brute-force build + device upload AFTER
        releasing it — with the RLock held by the outer frame, doing
        both in :meth:`side_index` stalls every concurrent
        delete/upsert for the full build each side epoch."""
        st = self.state
        if st.side_cap == 0:
            return None
        hit = self._side_cache
        if hit is not None and hit[0] == st.side_seq:
            return hit                     # (seq, idx, ids_dev) — built
        return (st.side_seq, st.side_vecs.copy(), st.side_int.copy())

    def side_build(self, snap: Optional[tuple]):
        """Materialize a :meth:`side_snapshot_locked` result (lock-free
        for the expensive part)."""
        if snap is None:
            return None, None
        seq, a, b = snap
        if not isinstance(a, np.ndarray):  # cache hit: already built
            return a, b
        if self.algo == "hybrid":
            # column weights fold the fuse into the side scan: a plain
            # IP over weighted rows IS the fused score, so side hits
            # merge against main-index hits on the same scale
            a = a * hybrid.side_scale(self.index)[None, :]
        idx = brute_force.build(a, metric=self.metric)
        ids_dev = jax.device_put(b.astype(np.int32))
        with self.state.lock:
            self._side_cache = (seq, idx, ids_dev)
        return idx, ids_dev

    def k_ladder(self, max_k: int) -> Tuple[int, ...]:
        """k rungs this generation can serve: powers of two below
        ``max_k`` plus ``max_k`` itself as the top rung — submit admits
        any ``k <= max_k``, so the ladder must always have a rung that
        covers it (a pow2-only ladder under e.g. ``max_k=100`` would
        top out at 64 and fail every admitted k in (64, 100] at
        delivery). Each rung is capped by the index size (brute force
        rejects k > n)."""
        out: List[int] = []
        b = 1
        while b < max_k:
            out.append(min(b, self.rows))
            b <<= 1
        out.append(min(max_k, self.rows))
        return tuple(sorted(set(out)))

    def k_pad(self, k: int, max_k: int) -> int:
        ladder = self.k_ladder(max_k)
        for rung in ladder:
            if rung >= k:
                return rung
        return ladder[-1]


def _index_rows(algo: str, index) -> int:
    if algo == "cagra":
        return int(index.dataset.shape[0])
    return int(index.size)


def _index_dim(algo: str, index) -> int:
    return int(index.dim)


def _index_metric(algo: str, index):
    return resolve_metric(index.metric)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _merge_with_side(d, i, sd, sp, side_int, k: int, select_min: bool):
    """Merge the main index's top-k with the side-buffer's: side result
    POSITIONS resolve to internal ids through the device id map, then one
    ``merge_topk`` keeps the global best-k. Invalid side slots (-1 /
    filtered) ride at the sentinel distance and sink."""
    si = jnp.where(
        sp >= 0,
        side_int[jnp.clip(sp, 0, side_int.shape[0] - 1)],
        jnp.int32(-1),
    )
    cd = jnp.concatenate([d, sd.astype(d.dtype)], axis=1)
    ci = jnp.concatenate([i.astype(jnp.int32), si], axis=1)
    return merge_topk(cd, ci, k, select_min)


class _ResultCache:
    """Bounded LRU result cache in front of dispatch (ISSUE 12,
    docs/serving.md §12): repeated queries — the Zipf head of real
    traffic — answered from host memory without touching the engine.

    Entries are keyed on ``(query bytes, k)`` and stamped with the
    ``(generation, mutation seq)`` pair they were computed under; a
    lookup only hits when BOTH still match the serving state, so a
    hot-swap (new generation) or a delete/upsert (seq bump)
    invalidates every stale answer implicitly. Stale entries are
    evicted on touch; capacity evicts least-recently-used."""

    def __init__(self, entries: int):
        self.entries = int(entries)
        from collections import OrderedDict

        self._od: "OrderedDict" = OrderedDict()
        self._lock = lockwatch.make_lock("serve.result_cache")

    def get(self, key, gen: int, epoch: int):
        with self._lock:
            v = self._od.get(key)
            if v is None:
                return None
            if v[0] != gen or v[1] != epoch:
                del self._od[key]          # stale: swap or mutation
                return None
            self._od.move_to_end(key)
            return v[2]

    def put(self, key, gen: int, epoch: int, value) -> None:
        with self._lock:
            self._od[key] = (gen, epoch, value)
            self._od.move_to_end(key)
            while len(self._od) > self.entries:
                self._od.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)


class _IndexServing:
    """One named index's serving unit: batcher + mutation overlay +
    dispatch/warmup logic against the shared registry."""

    def __init__(self, server: "Server", name: str):
        self.server = server
        self.name = name
        self.params = server.params
        self.registry = server.registry
        # effective warmup choice for THIS index: _install overwrites it
        # with the per-call override, and every later implicit warmup
        # (upsert re-warm, compaction, swap) honors it — a user who
        # opted out at create_index must not eat a full ladder compile
        # on their first growing upsert
        self.warmup_enabled = self.params.warmup
        # non-blocking acquire = atomic test-and-set: exactly one
        # compaction runs per index (released by the background thread).
        # A handoff FLAG, not a critical-section lock — see
        # lockwatch.make_flag_lock for why the sanitizer exempts it
        self.compacting = lockwatch.make_flag_lock("serve.compacting")
        self.result_cache = (_ResultCache(self.params.result_cache_entries)
                             if self.params.result_cache_entries > 0
                             else None)
        self.batcher = MicroBatcher(
            self._dispatch,
            max_batch_rows=self.params.max_batch_rows,
            max_wait_ms=self.params.max_wait_ms,
            max_queue_rows=self.params.max_queue_rows,
            shadow_queue_rows=self.params.shadow_queue_rows,
            name=name,
        )
        # graft-gauge (ISSUE 19): None when disabled, so the delivery
        # hook costs exactly one attribute read
        self.quality = (QualityMonitor(self, name)
                        if self.params.quality_sample_rate > 0 else None)
        # an OOM survivor recorded by an earlier server in this process
        # clamps the starting ceiling (same contract as the streaming
        # paths' budget names)
        ceiling = tuning.budget("serve_batch_rows",
                                self.batcher.max_batch_rows)
        if ceiling < self.batcher.max_batch_rows:
            self.batcher.set_ceiling(ceiling)
        # graft-flow dispatch pipeline (docs/serving.md §12): bounded
        # ticket queue between the batcher thread (async dispatch) and
        # a completion thread (sync + deliver). Each ticket carries its
        # OWN pinned generation — a hot swap or compaction can publish
        # a new generation while the ticket is in flight and the old
        # one stays alive until the ticket's release, exactly the
        # invalidation contract the synchronous path had.
        self.pipeline_depth = _pipeline.resolve_depth(
            self.params.pipeline_depth)
        self._pipe_cv = lockwatch.make_condition(
            lockwatch.make_lock("serve.pipeline"))
        self._pipe_q: collections.deque = collections.deque()
        self._pipe_thread: Optional[threading.Thread] = None
        self._pipe_stop = False

    # -- dispatch ----------------------------------------------------------

    def _pin_consistent(self):
        """Pin the current generation AND acquire its mutation lock such
        that (generation, mutation state) are a consistent pair — a
        compaction commits its side-buffer shift and publishes the
        extended generation under the same lock, so observing one
        without the other would drop the compacted rows for one batch."""
        for _ in range(8):
            gen = self.registry.pin(self.name)
            st = gen.handle.state
            # graft-lint: allow-unbalanced-acquire ownership transfer: _enqueue's finally releases st.lock
            st.lock.acquire()
            if self.registry.get(self.name) is gen:
                return gen, st
            st.lock.release()
            gen.release()
        # a swap storm: serve from the latest pin anyway (its handle and
        # state are still a valid pair for a non-compaction swap)
        gen = self.registry.pin(self.name)
        st = gen.handle.state
        # graft-lint: allow-unbalanced-acquire ownership transfer: _enqueue's finally releases st.lock
        st.lock.acquire()
        return gen, st

    # -- SLO-aware partition: deadline shed/downshift + split-by-rung ------

    def _partition(self, batch: Batch) -> List[Batch]:
        """Pre-dispatch policy pass (ISSUE 14, docs/serving.md §13):

        1. shed requests whose deadline can no longer be met (counted
           in ``serve.deadline_shed_total{action="shed"}``, failed with
           ``Overloaded(reason="deadline")`` — transient: the client's
           correct move is to re-budget and retry);
        2. on an adaptive handle, estimate each request's difficulty
           from the coarse margins and split the batch by chosen probe
           rung (the split-by-rung analog of the batcher's
           filter-homogeneous grouping); deadline pressure downshifts
           a request's rung before shedding it when
           ``deadline_action="downshift"``.

        Rung decisions happen on a PINNED generation but the pin drops
        before dispatch; a swap landing in between is safe — dispatch
        clamps ``n_probes`` to the new index's ``n_lists`` exactly like
        the non-adaptive path does.
        """
        if not batch.requests:
            return []
        gen = self.registry.pin(self.name)
        try:
            h: _Handle = gen.handle
            now = time.monotonic()
            live = self._shed_missed(batch, h, now)
            if not live:
                return []
            if batch.rung is not None or h.adaptive is None:
                # already rung-partitioned (a later part re-gated while
                # it queued behind its siblings, or an OOM re-split) /
                # non-adaptive: shed-only pass
                if len(live) == len(batch.requests):
                    return [batch]
                return [self._sub_batch(batch, live, rung=batch.rung)]
            return self._split_by_rung(h, batch, live, now)
        finally:
            gen.release()

    def _shed_missed(self, batch: Batch, h: "_Handle",
                     now: float) -> List[Request]:
        """Drop requests that would certainly miss their SLO: expired
        deadlines always; predicted misses (slack below the bucket's
        measured p95 service time) when ``deadline_action="shed"`` or
        when no adaptive ladder exists to downshift instead. Returns
        the surviving requests."""
        est_ms = None
        head_ms = _adaptive.deadline_headroom_ms()
        live: List[Request] = []
        for r in batch.requests:
            if r.future.done() or r.deadline is None:
                live.append(r)
                continue
            slack_ms = (r.deadline - now) * 1e3
            if (slack_ms > 0 and batch.rung is None
                    and h.adaptive is not None):
                # the rung assignment handles pressure (either mode:
                # _deadline_adjust downshifts or sheds at the rung the
                # policy actually chose, not the exhaustive estimate)
                live.append(r)
                continue
            if est_ms is None:
                est_ms = self.batcher.service_p95_ms(batch.bucket,
                                                     batch.rung)
            if slack_ms <= 0 or slack_ms < est_ms + head_ms:
                self._shed(r, slack_ms)
            else:
                live.append(r)
        return live

    def _shed(self, r: Request, slack_ms: float) -> None:
        obs.counter("serve.deadline_shed_total", index=self.name,
                    action="shed")
        obs_trace.finish(r.trace, status="rejected", reason="deadline",
                         deadline_slack_ms=round(slack_ms, 3))
        exc = Overloaded(
            f"serve[{self.name}]: deadline "
            f"(slack {slack_ms:.1f} ms cannot cover the measured "
            "service estimate)", reason="deadline")
        _rerrors.classify(exc)
        if not r.future.done():
            r.future.set_exception(exc)

    def _split_by_rung(self, h: "_Handle", batch: Batch,
                       live: List[Request], now: float) -> List[Batch]:
        """Assign each request a probe rung from its coarse margin and
        regroup the batch rung-homogeneously. The margins run at the
        batch's already-formed bucket shape (warmed), so the estimate
        itself adds no retrace."""
        pol = h.adaptive
        q = np.concatenate([r.queries for r in live], axis=0)
        q = pad_rows(np.ascontiguousarray(q, dtype=h.dtype), batch.bucket)
        # graft-lint: allow-host-sync rung choice regroups the batch on the host — the margins must land here before dispatch
        margins = np.asarray(h.margins(jax.device_put(q)))
        kq = h.k_pad(batch.k_max, self.params.max_k)
        groups: Dict[int, List[Request]] = {}
        row = 0
        for r in live:
            m = float(margins[row:row + r.rows].min())
            row += r.rows
            obs.observe("serve.difficulty_margin", m,
                        buckets=_MARGIN_BUCKETS, index=self.name)
            idx = pol.choose_idx(m, kq)
            if r.deadline is not None:
                idx = self._deadline_adjust(r, pol, idx, kq,
                                            batch.bucket, now)
                if idx is None:
                    continue
            groups.setdefault(idx, []).append(r)
        out: List[Batch] = []
        for idx in sorted(groups):
            rung = pol.rung(idx)
            obs.counter("serve.probe_rung", len(groups[idx]),
                        index=self.name, rung=str(rung))
            out.append(self._sub_batch(batch, groups[idx], rung=rung))
        return out

    def _deadline_adjust(self, r: Request, pol, idx: int, kq: int,
                         bucket: int, now: float) -> Optional[int]:
        """Fit a deadline request's rung to its slack: with
        ``deadline_action="downshift"``, drop one rung at a time while
        the (bucket, rung) service estimate exceeds the remaining
        budget, shedding when even the floor rung cannot make it; with
        ``"shed"``, never trade recall — shed as soon as the
        margin-chosen rung's estimate misses. Returns the adjusted
        ladder index, or None if the request was shed."""
        slack_ms = (r.deadline - now) * 1e3
        budget = slack_ms - _adaptive.deadline_headroom_ms()
        floor = pol.min_idx(kq)
        shifted = False
        if self.params.deadline_action == "downshift":
            while (idx > floor and
                   self.batcher.service_p95_ms(bucket, pol.rung(idx))
                   > budget):
                idx -= 1
                shifted = True
        if (slack_ms <= 0 or
                self.batcher.service_p95_ms(bucket, pol.rung(idx))
                > budget):
            self._shed(r, slack_ms)
            return None
        if shifted:
            obs.counter("serve.deadline_shed_total", index=self.name,
                        action="downshift")
        return idx

    def _sub_batch(self, batch: Batch, requests: List[Request],
                   rung: Optional[int]) -> Batch:
        rows = sum(r.rows for r in requests)
        return Batch(
            requests=requests, rows=rows,
            bucket=choose_bucket(self.batcher.ladder, rows,
                                 ceiling=self.batcher.ceiling),
            prefilter=batch.prefilter, seq=batch.seq,
            linger_ms=batch.linger_ms, t_drain=batch.t_drain, rung=rung,
        )

    def _dispatch(self, batch: Batch) -> None:
        """Batcher callback: deadline shed + adaptive rung partition,
        then resilience-wrapped dispatch + OOM ladder per part. Each
        part retries/splits independently — a failure in one rung's
        sub-batch must not re-dispatch requests another rung already
        delivered."""
        if batch.shadow:
            self._dispatch_shadow(batch)
            return
        for i, part in enumerate(self._partition(batch)):
            if i:
                # later parts queued behind their siblings' device time:
                # re-gate so work whose budget the earlier parts burned
                # is shed instead of served certainly-late
                regated = self._partition(part)
                if not regated:
                    continue
                part = regated[0]
            self._dispatch_part(part)

    def _dispatch_part(self, batch: Batch, force_sync: bool = False) -> None:
        try:
            _rerrors.run(
                functools.partial(self._dispatch_once,
                                  force_sync=force_sync),
                batch,
                retries=self.params.dispatch_retries,
                backoff_s=self.params.retry_backoff_s,
            )
        except BaseException as e:  # noqa: BLE001 — classified right below
            kind = _rerrors.classify(e)
            if kind == _rerrors.OOM and len(batch.requests) > 1:
                self._downshift_and_split(batch, force_sync=force_sync)
                return
            if kind == _rerrors.OOM:
                # single request: record the learned ceiling anyway
                self._downshift(max(batch.bucket // 2, 1))
            for r in batch.requests:
                obs_trace.finish(r.trace, status="error", kind=kind,
                                 error=type(e).__name__)
                if not r.future.done():
                    r.future.set_exception(e)

    def _dispatch_shadow(self, batch: Batch) -> None:
        """graft-gauge's oracle re-run (ISSUE 19; docs/serving.md §14):
        answer each shadow sample EXHAUSTIVELY on the generation that
        served it, then hand the truth to the quality monitor for
        scoring.

        Trace discipline: the re-run is :meth:`_Handle.oracle_rung` —
        the resolved exhaustive program when the ceiling is already the
        full probe count, else the full-probe override warmup traced
        alongside the ladder — over the same padded buckets and
        k-ladder rungs as live dispatch, so a shadow batch can NEVER
        mint a new XLA trace. It runs synchronously on the batcher thread, which is
        idle by construction (the shadow lane only drains when both
        live lanes are empty); a failure is counted and swallowed —
        quality sampling must never take serving down with it."""
        mon = self.quality
        try:
            if mon is None:
                return
            # group by pinned generation: a hot-swap between two
            # samples' deliveries means one shadow batch can carry
            # samples from two generations, each of which must be
            # scored against ITS OWN index
            groups: List[Tuple[object, List[Request]]] = []
            for r in batch.requests:
                gen = r.shadow.gen
                if groups and groups[-1][0] is gen:
                    groups[-1][1].append(r)
                else:
                    groups.append((gen, [r]))
            for gen, reqs in groups:
                h: _Handle = gen.handle
                if h is None:      # impossible while pinned; belt+braces
                    continue
                st = h.state
                with st.lock:
                    if batch.prefilter is None:
                        main_bits = st.tombstone_bits()
                        side_bits = st.side_keep_bits()
                    else:
                        main_bits, side_bits = st.compose_user_filter(
                            batch.prefilter)
                    side_snap = h.side_snapshot_locked()
                side_idx, side_ids = h.side_build(side_snap)
                rows = sum(r.rows for r in reqs)
                sub = Batch(
                    requests=reqs, rows=rows,
                    bucket=choose_bucket(self.batcher.ladder, rows,
                                         ceiling=self.batcher.ceiling),
                    prefilter=batch.prefilter, seq=batch.seq,
                    rung=h.oracle_rung(), shadow=True)
                with obs.span("serve.shadow_batch", index=self.name,
                              bucket=sub.bucket, rows=rows,
                              generation=gen.version):
                    d, i = self._run_search(h, sub, main_bits,
                                            side_bits, side_idx,
                                            side_ids)
                    jax.block_until_ready((d, i))
                d = np.asarray(d)
                i = np.asarray(i)
                ext = st.translate_out(i.astype(np.int64)) \
                    if st.has_translation else i
                sent = np.inf if h.select_min else -np.inf
                ext = np.where(d == sent, np.asarray(-1, ext.dtype),
                               ext)
                mon.score_batch(sub, ext)
        except BaseException as e:  # noqa: BLE001 — quality is advisory: classify + count, never fail serving
            _rerrors.classify(e)
            obs.counter("serve.shadow_errors_total", index=self.name,
                        error=type(e).__name__)
        finally:
            for r in batch.requests:
                if r.shadow is not None:
                    r.shadow.gen.release()

    def _downshift(self, new_ceiling: int) -> None:
        new_ceiling = max(int(new_ceiling), self.batcher.ladder[0])
        # atomic monotone clamp: two concurrent OOM downshifts used to
        # race the ceiling read and the shallower one could win
        self.batcher.lower_ceiling(new_ceiling)
        tuning.record_budget("serve_batch_rows", new_ceiling)
        obs.counter("oom_ladder_downshifts", path="serve")
        obs.event("serve_downshift", index=self.name, ceiling=new_ceiling)

    def _downshift_and_split(self, batch: Batch,
                             force_sync: bool = False) -> None:
        """The serving OOM ladder: halve the bucket ceiling and re-dispatch
        the batch as two ladder-shaped halves (requests are the atomic
        unit — row-independent searches make the split result-identical)."""
        self._downshift(batch.bucket // 2)
        for r in batch.requests:
            # a retry stage, not a finish: the split halves re-dispatch
            # and each member trace completes at its half's delivery
            obs_trace.stage(r.trace, "retry", status="retry",
                            reason="oom_split", bucket=batch.bucket)
        half_rows = batch.rows // 2
        left: List = []
        rows = 0
        for r in batch.requests:
            if left and rows + r.rows > half_rows:
                break
            left.append(r)
            rows += r.rows
        right = batch.requests[len(left):]
        for part in (left, right):
            if not part:
                continue
            # rung rides along: the halves must re-dispatch at the rung
            # the policy already chose, not re-partition (the member
            # futures' policy decisions are final)
            self._dispatch_part(
                self._sub_batch(batch, part, rung=batch.rung),
                force_sync=force_sync)

    def _dispatch_once(self, batch: Batch,
                       force_sync: bool = False) -> None:
        pipelined = self.pipeline_depth > 0 and not force_sync
        with obs.span("serve.dispatch", index=self.name,
                      bucket=batch.bucket, rows=batch.rows,
                      rung=batch.rung, pipelined=pipelined) as sp:
            gen, h, d, i, t0 = self._enqueue(batch, sp)
        # a batch's life tiles into its requests' wait (to the drain),
        # dispatch, device wait and deliver, each recorded at its end
        t_enq = time.monotonic()
        if _obs_config.ENABLED:
            obs.observe("serve.dispatch_ms", (t_enq - batch.t_drain) * 1e3,
                        buckets=_LAT_BUCKETS, index=self.name,
                        bucket=str(batch.bucket))
        handed_off = False
        try:
            if pipelined:
                # graft-flow handoff: the ticket owns the pin from here;
                # the completion thread syncs, records service time, and
                # delivers while this (batcher) thread pads + uploads +
                # gathers for the NEXT batch. XLA's async dispatch means
                # the device is already running this batch.
                self._pipe_put((batch, gen, h, d, i, t0, t_enq))
                handed_off = True
                return
            t_ready = self._await(batch, d, i, t_enq)
            latency_ms = (time.perf_counter() - t0) * 1e3
            # feed the deadline machinery's service estimate (the
            # batcher's linger slack test and _shed_missed read the
            # p95, keyed per rung — rungs differ by multiples)
            self.batcher.note_service_ms(batch.bucket, latency_ms,
                                         rung=batch.rung)
            self._deliver(batch, gen, h, d, i, latency_ms, t_ready)
        finally:
            if not handed_off:
                gen.release()

    def _enqueue(self, batch: Batch, sp):
        """The host half of a dispatch, under the ``serve.dispatch``
        span ``sp``: pin the generation, snapshot the mutation overlay,
        build the side buffer, and enqueue the search on the device
        without waiting for it. Returns ``(gen, handle, d, i, t0)`` with
        the pin owned by the caller (released here only on failure);
        ``t0`` is where ``serve.batch_latency_ms`` starts."""
        gen, st = self._pin_consistent()
        try:
            h: _Handle = gen.handle
            try:
                # snapshot the mutation overlay while (generation, state)
                # are verified consistent; the device arrays captured here
                # are immutable, so the search itself runs lock-free
                if batch.prefilter is None:
                    main_bits = st.tombstone_bits()
                    side_bits = st.side_keep_bits()
                else:
                    main_bits, side_bits = st.compose_user_filter(
                        batch.prefilter)
                # snapshot only — the brute-force build + upload run
                # below, after the mutation lock drops
                side_snap = h.side_snapshot_locked()
            finally:
                st.lock.release()
            side_idx, side_ids = h.side_build(side_snap)
            t0 = time.perf_counter()
            sp.set(generation=gen.version)
            # fault point: where a real device failure would surface.
            # Deliberately BEFORE the async handoff — injected faults
            # strike here on the batcher thread, inside resilience.run,
            # so retry and OOM-ladder semantics are byte-for-byte those
            # of the synchronous path at any pipeline depth.
            faultinject.check(stage="serve.dispatch", chunk=batch.seq)
            d, i = self._run_search(
                h, batch, main_bits, side_bits, side_idx, side_ids)
            sp.set(k_pad=int(d.shape[1]))
        except BaseException:
            gen.release()
            raise
        return gen, h, d, i, t0

    def _await(self, batch: Batch, d, i, t_enq: float) -> float:
        """Block until the batch's outputs are ready, under the
        ``serve.await`` span; its device wait (``serve.device_wait_ms``)
        runs from the enqueue at ``t_enq``. Returns the ready time."""
        with obs.span("serve.await", index=self.name, bucket=batch.bucket):
            jax.block_until_ready((d, i))
        t_ready = time.monotonic()
        if _obs_config.ENABLED:
            obs.observe("serve.device_wait_ms", (t_ready - t_enq) * 1e3,
                        buckets=_LAT_BUCKETS, index=self.name,
                        bucket=str(batch.bucket))
        return t_ready

    # -- graft-flow completion pipeline (docs/serving.md §12) --------------

    def _pipe_put(self, ticket) -> None:
        """Enqueue an in-flight batch for the completion thread, blocking
        while ``pipeline_depth`` tickets are already outstanding — the
        backpressure that bounds device-queue depth (and pinned
        generations) exactly as the synchronous path did with one."""
        t0 = time.perf_counter()
        with self._pipe_cv:
            while (len(self._pipe_q) >= self.pipeline_depth
                   and not self._pipe_stop):
                self._pipe_cv.wait(0.05)
            waited_ms = (time.perf_counter() - t0) * 1e3
            if waited_ms >= 0.05:
                obs.observe("pipeline.stall_ms", waited_ms,
                            path="serve.dispatch")
            if self._pipe_stop:
                # close raced the dispatch: complete inline — the ticket
                # must never be dropped (its futures and pin would leak)
                pass
            else:
                self._pipe_q.append(ticket)
                obs.gauge("pipeline.occupancy", float(len(self._pipe_q)),
                          path="serve.dispatch")
                if self._pipe_thread is None or not self._pipe_thread.is_alive():
                    self._pipe_thread = threading.Thread(
                        target=self._complete_loop, daemon=True,
                        name=f"serve-pipe-{self.name}")
                    self._pipe_thread.start()
                self._pipe_cv.notify_all()
                return
        self._complete_ticket(ticket)

    def _complete_loop(self) -> None:
        while True:
            with self._pipe_cv:
                while not self._pipe_q and not self._pipe_stop:
                    self._pipe_cv.wait(0.05)
                if not self._pipe_q:
                    return                # stop + drained
                ticket = self._pipe_q.popleft()
                self._pipe_cv.notify_all()
            self._complete_ticket(ticket)

    def _complete_ticket(self, ticket) -> None:
        """Sync one in-flight batch and deliver it, releasing the
        ticket's generation pin. Error recovery mirrors
        ``_dispatch_part``'s classification: a REAL device failure that
        surfaces at the wait (injected faults never reach here — they
        strike pre-dispatch) re-dispatches the batch in FORCED-SYNC
        mode, so resilience.run's retry budget and the OOM
        split-ladder apply without this thread ever re-entering its own
        queue (the self-deadlock a recursive enqueue would be)."""
        batch, gen, h, d, i, t0, t_enq = ticket
        try:
            try:
                t_ready = self._await(batch, d, i, t_enq)
            except BaseException as e:  # noqa: BLE001 — classified below
                kind = _rerrors.classify(e)
                if kind in (_rerrors.TRANSIENT, _rerrors.DEAD,
                            _rerrors.OOM):
                    for r in batch.requests:
                        obs_trace.stage(r.trace, "retry", status="retry",
                                        reason="pipeline_sync", kind=kind)
                    self._dispatch_part(batch, force_sync=True)
                    return
                for r in batch.requests:
                    obs_trace.finish(r.trace, status="error", kind=kind,
                                     error=type(e).__name__)
                    if not r.future.done():
                        r.future.set_exception(e)
                return
            latency_ms = (time.perf_counter() - t0) * 1e3
            self.batcher.note_service_ms(batch.bucket, latency_ms,
                                         rung=batch.rung)
            self._deliver(batch, gen, h, d, i, latency_ms, t_ready)
        except BaseException as e:  # noqa: BLE001 — must not kill the loop
            kind = _rerrors.classify(e)
            for r in batch.requests:
                if not r.future.done():
                    obs_trace.finish(r.trace, status="error", kind=kind,
                                     error=type(e).__name__)
                    r.future.set_exception(e)
        finally:
            gen.release()

    def close_pipeline(self, timeout_s: float = 30.0) -> None:
        """Drain outstanding tickets and join the completion thread.
        Called after the batcher closes (no new tickets can arrive);
        every queued ticket is still completed — futures resolve, pins
        release — before the thread exits."""
        with self._pipe_cv:
            self._pipe_stop = True
            self._pipe_cv.notify_all()
            t = self._pipe_thread
        if t is not None and t.is_alive():
            t.join(timeout=timeout_s)
        # a thread that never started (or died): drain inline
        while True:
            with self._pipe_cv:
                if not self._pipe_q:
                    break
                ticket = self._pipe_q.popleft()
            self._complete_ticket(ticket)

    def _run_search(self, h: _Handle, batch: Batch, main_bits: Bitset,
                    side_bits: Optional[Bitset], side_idx, side_ids):
        """The shape-stable search core (shared verbatim by warmup): pad
        rows on the HOST up to the bucket, search the main index under
        the composed keep-mask, then merge the side buffer's exact
        results."""
        q = np.concatenate([r.queries for r in batch.requests], axis=0) \
            if batch.requests else np.zeros((0, h.dim), h.dtype)
        q = pad_rows(np.ascontiguousarray(q, dtype=h.dtype), batch.bucket)
        qdev = jax.device_put(q)
        kq = h.k_pad(batch.k_max, self.params.max_k)
        d, i = h.search_main(qdev, kq, BitsetFilter(main_bits),
                             rung=batch.rung)
        if side_idx is not None:
            k_side = min(kq, side_idx.size)
            # graft-lint: allow-hand-wired-pipeline deliberate single-stage fast path: the side upsert buffer is a small exact scan merged after the main compiled plan, not a pipeline
            sd, sp = brute_force.search(
                side_idx, qdev, k_side,
                prefilter=None if side_bits is None
                else BitsetFilter(side_bits))
            d, i = _merge_with_side(d, i, sd, sp, side_ids, kq,
                                    h.select_min)
        return d, i

    def _deliver(self, batch: Batch, gen, h: _Handle, d, i,
                 latency_ms: float, t_ready: float) -> None:
        """Fetch the batch's outputs and resolve each member's future,
        under the ``serve.deliver`` span; the deliver interval
        (``serve.deliver_ms``) runs from ``t_ready`` to the last future
        resolved."""
        with obs.span("serve.deliver", index=self.name,
                      bucket=batch.bucket, rows=batch.rows):
            d = np.asarray(d)
            i = np.asarray(i)
            row = 0
            ext = h.state.translate_out(i.astype(np.int64)) \
                if h.state.has_translation else i
            # a slot at the sentinel distance is a filtered-out (tombstoned)
            # or padding candidate that survived top-k only because fewer
            # than k live rows existed: brute_force._search and the side
            # merge keep such slots' REAL ids (ivf_* map them to -1 in the
            # kernel), so mask them here rather than hand a deleted row's id
            # to the client
            sent = np.inf if h.select_min else -np.inf
            ext = np.where(d == sent, np.asarray(-1, ext.dtype), ext)
            now = time.monotonic()
            for r in batch.requests:
                rd = d[row:row + r.rows, :r.k]
                ri = ext[row:row + r.rows, :r.k]
                row += r.rows
                r.future.generation = gen.version
                # remaining SLO budget at delivery: negative = a miss (the
                # request was served late rather than shed — counted so the
                # SLO harness can tell the two apart)
                slack_ms = None
                if r.deadline is not None:
                    slack_ms = round((r.deadline - now) * 1e3, 3)
                    if slack_ms < 0:
                        obs.counter("serve.deadline_miss_total",
                                    index=self.name)
                # the shared device work, attributed to every member trace:
                # batch_seq is the span LINK (one batch serves many traces),
                # linger_ms the batching policy's share of the wait; rung /
                # deadline_slack_ms are the waterfall's SLO columns
                # (obs_report.py renders them per stage)
                obs_trace.stage(r.trace, "batch_search", ms=latency_ms,
                                bucket=batch.bucket, batch_seq=batch.seq,
                                linger_ms=round(batch.linger_ms, 3),
                                rung=batch.rung,
                                deadline_slack_ms=slack_ms,
                                generation=gen.version)
                if r.future.done():
                    obs_trace.finish(r.trace, status="error",
                                     error="already_done")
                    continue
                if rd.shape[1] < r.k:
                    # a swap shrank the index below this request's k after
                    # admission: fail loudly, never hand back fewer columns
                    # than asked
                    obs_trace.finish(r.trace, status="failed",
                                     error="k_exceeds_rows")
                    r.future.set_exception(ValueError(
                        f"k={r.k} exceeds index rows={h.rows} after swap"))
                else:
                    obs_trace.finish(r.trace, status="ok",
                                     generation=gen.version)
                    r.future.set_result((rd, ri))
        if _obs_config.ENABLED:
            obs.observe("serve.deliver_ms",
                        (time.monotonic() - t_ready) * 1e3,
                        buckets=_LAT_BUCKETS, index=self.name,
                        bucket=str(batch.bucket))
        obs.counter("serve.queries_total", batch.rows, index=self.name)
        obs.observe("serve.batch_latency_ms", latency_ms,
                    buckets=_LAT_BUCKETS, index=self.name,
                    bucket=str(batch.bucket))
        # graft-gauge sampling (ISSUE 19) — AFTER the futures resolved,
        # so the client's latency never includes it. Disabled: one
        # attribute read. Obs off: one module-attribute read (offer is
        # never entered).
        mon = self.quality
        if mon is not None and _obs_config.ENABLED:
            mon.offer(batch, gen, h, ext)

    # -- warmup ------------------------------------------------------------

    def warmup_handle(self, h: _Handle) -> int:
        """Trace every (bucket, k-rung[, probe-rung]) combination
        through the REAL dispatch core so steady-state serving never
        compiles — the adaptive ladder (ISSUE 14) adds the probe-rung
        axis, and the margin estimator itself is traced once per
        bucket. Returns the number of shapes warmed."""
        with obs.span("serve.warmup", index=self.name):
            st = h.state
            with st.lock:
                main_bits = st.tombstone_bits()
                side_bits = st.side_keep_bits()
            side_idx, side_ids = h.side_index()
            warmed = 0
            oom = False
            # rung=None is today's exhaustive program, and the ladder's
            # TOP rung dispatches the identical trace (same n_probes
            # value -> same program, the bitwise escape hatch) — skip
            # it outright so warmup pays for each distinct program
            # once, not the most expensive one twice per (bucket, k)
            rungs: List[Optional[int]] = [None]
            if h.adaptive is not None:
                rungs += list(h.adaptive.ladder[:-1])
            # graft-gauge (ISSUE 19): the shadow oracle's full-probe
            # override is one more program per (bucket, k) — warmed
            # here so a quality re-run can never retrace in steady
            # state (the distinct-VALUE trace key rule: when the
            # ceiling already equals n_lists, oracle_rung() is None
            # and the exhaustive program above covers it)
            orung = h.oracle_rung()
            if self.quality is not None and orung is not None:
                rungs.append(orung)
            for bucket in self.batcher.ladder:
                if oom:
                    break
                q = np.zeros((bucket, h.dim), h.dtype)
                if h.adaptive is not None:
                    # the difficulty estimator's own trace (per bucket)
                    jax.block_until_ready(
                        h.margins(jax.device_put(q)))
                for kq in h.k_ladder(self.params.max_k):
                    for rung in rungs:
                        if oom:
                            break
                        fake = Batch(requests=[], rows=bucket,
                                     bucket=bucket, prefilter=None,
                                     rung=rung)
                        fake.requests = [_warm_request(q, kq)]
                        try:
                            out = self._run_search(h, fake, main_bits,
                                                   side_bits, side_idx,
                                                   side_ids)
                            jax.block_until_ready(out)
                            warmed += 1
                            if (h.tiered_source is not None
                                    and h.algo == "ivf_pq"
                                    and (h.refine_ratio > 1 or getattr(
                                        h.index, "cache_kind", "none")
                                        == "rabitq"
                                        or rung == "exact")):
                                # tiered rerank: the fetched-block rung
                                # is data-dependent (unique shortlist
                                # rows), so trace the whole pow2 rung
                                # ladder for this (bucket, k, rung) —
                                # steady state then never compiles
                                # whatever the miss mix is
                                sp_r, rr_r = h.rung_params(rung)
                                kc = ivf_pq.refined_shortlist_width(
                                    sp_r, h.index, kq, rr_r)
                                h.tiered_source.warm(bucket, kc, kq,
                                                     h.metric)
                        except ValueError as e:
                            # a rung this index cannot serve (e.g. k
                            # beyond the probed candidate pool) fails
                            # identically at dispatch — nothing to
                            # warm, but a silently skipped rung voids
                            # the zero-recompile guarantee for that
                            # shape, so leave a signal naming which
                            # one and why
                            obs.counter("serve.warmup_skipped",
                                        index=self.name)
                            obs.event("serve_warmup_rung_skipped",
                                      index=self.name, bucket=bucket,
                                      k=kq, rung=rung, error=str(e))
                            continue
                        except Exception as e:  # noqa: BLE001 — only the classified-OOM kind is handled; the rest re-raise
                            if _rerrors.classify(e) != _rerrors.OOM:
                                raise
                            # device OOM tracing this rung: at dispatch
                            # the ladder would halve the ceiling and
                            # keep serving — do the same here, so a
                            # server whose top bucket doesn't fit
                            # still comes up serving the buckets that
                            # do (larger rungs can only OOM harder)
                            self._downshift(bucket // 2)
                            obs.event("serve_warmup_oom",
                                      index=self.name, bucket=bucket,
                                      k=kq)
                            oom = True
                            break
            obs.counter("serve.warmup_shapes", warmed, index=self.name)
            return warmed


def _warm_request(q: np.ndarray, k: int) -> Request:
    return Request(queries=q, k=k, prefilter=None, future=Future())


class Server:
    """The online serving engine (ISSUE 5 tentpole; docs/serving.md).

    One ``Server`` hosts any number of named indexes, each with its own
    micro-batcher, versioned generations, and tombstone overlay::

        srv = serve.Server()
        srv.create_index("vectors", dataset, algo="ivf_flat")
        fut = srv.submit(queries, k=10, index="vectors")
        dists, ids = fut.result()
        srv.delete([3, 17], index="vectors")
        srv.swap("vectors", dataset=new_dataset)     # background + atomic
        srv.close()
    """

    def __init__(self, params: Optional[ServeParams] = None):
        self.params = params or ServeParams()
        self.registry = Registry()
        self._servings: Dict[str, _IndexServing] = {}
        # graft-race sanitizer node "serve.engine"
        self._lock = lockwatch.make_lock("serve.engine")
        self._closed = False

    # -- index lifecycle ---------------------------------------------------

    def create_index(self, name: str, dataset, algo: str = "brute_force",
                     build_params=None, search_params=None,
                     ids=None, refine_ratio: int = 1,
                     warmup: Optional[bool] = None):
        """Build ``algo`` over ``dataset`` in-process and publish it as
        generation 1 of ``name`` (warming the trace ladder first unless
        disabled). ``ids`` optionally names rows with external ids
        (default: row positions)."""
        with obs.span("serve.create_index", index=name, algo=algo):
            dataset = np.ascontiguousarray(np.asarray(dataset),
                                           dtype=np.float32)
            index = _build_index(algo, dataset, build_params)
            return self._install(name, algo, index, dataset, build_params,
                                 search_params, ids, refine_ratio, warmup)

    def add_index(self, name: str, index, algo: str, dataset=None,
                  build_params=None, search_params=None, ids=None,
                  refine_ratio: int = 1, warmup: Optional[bool] = None):
        """Publish a prebuilt index object under ``name``."""
        with obs.span("serve.add_index", index=name, algo=algo):
            ds = None if dataset is None else np.ascontiguousarray(
                np.asarray(dataset), dtype=np.float32)
            return self._install(name, algo, index, ds, build_params,
                                 search_params, ids, refine_ratio, warmup)

    def load_index(self, name: str, path: str, algo: str,
                   search_params=None, refine_ratio: int = 1,
                   warmup: Optional[bool] = None):
        """Load a ``core/serialize`` snapshot and publish it — the
        cold-start / cross-process half of the hot-swap protocol."""
        with obs.span("serve.load_index", index=name, algo=algo):
            index = _ALGO_MODULES[algo].load(path)
            return self._install(name, algo, index, None, None,
                                 search_params, None, refine_ratio, warmup)

    def _install(self, name, algo, index, dataset, build_params,
                 search_params, ids, refine_ratio, warmup):
        if algo not in ALGOS:
            raise ValueError(f"algo must be one of {ALGOS}, got {algo!r}")
        rows = _index_rows(algo, index)
        dim = _index_dim(algo, index)
        state = MutableState(
            rows, dim, np.float32, ext_ids=ids,
            side_capacity=self.params.side_capacity,
        )
        raw = _raw_dataset(algo, index, dataset)
        sp = _default_search_params(algo, index, search_params)
        h = _Handle(algo, index, state, sp,
                    build_params, refine_ratio, raw,
                    user_search_params=search_params,
                    tiered_source=self._make_tiered(algo, raw),
                    adaptive=self._make_adaptive(algo, index, sp,
                                                 refine_ratio))
        with self._lock:
            # checked under the SAME lock that registers the serving: a
            # close() racing the unlocked gap would snapshot _servings
            # without this entry and leave its batcher thread running
            # forever
            if self._closed:
                raise RuntimeError("server is closed")
            serving = self._servings.get(name)
            if serving is None:
                serving = _IndexServing(self, name)
                self._servings[name] = serving
        serving.warmup_enabled = warmup if warmup is not None \
            else self.params.warmup
        if serving.warmup_enabled:
            serving.warmup_handle(h)
        gen = self._publish_guarded(name, h)
        return gen.version

    def _make_tiered(self, algo: str, raw: Optional[np.ndarray]):
        """A per-generation tiered rerank source over the host raw row
        store (None unless ``tiered_rerank`` is on and this algo can
        use it). Fresh per generation: compaction/swap content changes
        must not serve a predecessor's hot rows."""
        if (not self.params.tiered_rerank or algo != "ivf_pq"
                or raw is None):
            return None
        from raft_tpu.neighbors import tiered

        return tiered.HostArraySource(
            raw, hot_rows=self.params.tiered_hot_rows)

    def _make_adaptive(self, algo: str, index, search_params,
                       refine_ratio: int):
        """Build the per-generation adaptive policy (ISSUE 14;
        docs/serving.md §13) — None unless ``adaptive_probes`` is on
        and the algo has a coarse quantizer to read margins from.

        The ladder's CEILING is the generation's resolved ``n_probes``:
        the ``_default_search_params`` pin (``n_probes = n_lists``) is
        thereby demoted from "the" probe count to the exhaustive top
        rung, and an explicit user ``n_probes`` caps the ladder at the
        user's own budget. Derived per generation, so a swap re-derives
        the whole LADDER against the new index — not just the ceiling
        (the regression test pins top-rung == new ``n_lists``)."""
        if (not self.params.adaptive_probes
                or algo not in _adaptive.ADAPTIVE_ALGOS):
            return None
        ceiling = int(min(int(search_params.n_probes), index.n_lists))
        if ceiling < 2:
            return None              # a 1-list index has nothing to adapt
        if algo == "ivf_flat":
            list_cap = int(index.storage.shape[1])
        else:
            list_cap = int(index.indices.shape[1])
        rr = (int(refine_ratio) if int(refine_ratio) > 1
              else RABITQ_DEFAULT_REFINE_RATIO
              if getattr(index, "cache_kind", "none") == "rabitq" else 1)
        return _adaptive.AdaptivePolicy.build(ceiling, list_cap,
                                              refine_ratio=rr)

    def _publish_guarded(self, name: str, h: "_Handle"):
        """Publish under the server lock: a background build finishing
        after :meth:`close` must not resurrect the name — a generation
        published then would hold its device arrays with nothing left to
        retire it."""
        with self._lock:
            if self._closed:
                raise RuntimeError("server is closed")
            serving = self._servings.get(name)
            mon = serving.quality if serving is not None else None
            # graft-gauge swap probation (ISSUE 19): pin + baseline the
            # outgoing generation BEFORE publish retires it — the
            # rollback path needs its handle alive until the successor
            # proves itself. Deliberately NOT hooked into compaction's
            # direct registry.publish: a compaction folds the same
            # content, so its predecessor is no quality baseline.
            if mon is not None:
                mon.before_publish()
            gen = self.registry.publish(name, h)
            if mon is not None:
                mon.after_publish(gen)
            return gen

    # -- the data plane ----------------------------------------------------

    def submit(self, queries, k: int, *, index: str = "default",
               prefilter=None,
               deadline_ms: Optional[float] = None) -> Future:
        """Enqueue a search; returns a Future resolving to host
        ``(distances [rows, k], external ids [rows, k])``. ``queries``
        is one query ``[dim]`` or a block ``[rows, dim]`` answered
        together. ``deadline_ms`` (or ``ServeParams.deadline_ms``)
        attaches an SLO deadline: the request rides the batcher's
        priority lane, skips linger when its slack runs out, and is
        shed with ``Overloaded(reason="deadline")`` — or downshifted a
        probe rung — when it would certainly miss (docs/serving.md
        §13). Raises :class:`Overloaded` when the bounded queue (or a
        per-index admission quota) is full (classified transient —
        back off and retry)."""
        with obs.span("serve.request", index=index):
            q = np.asarray(queries, dtype=np.float32)
            if q.ndim == 1:
                q = q[None, :]
            if q.ndim != 2:
                raise ValueError(f"queries must be [dim] or [rows, dim], "
                                 f"got shape {q.shape}")
            if not 0 < int(k) <= self.params.max_k:
                raise ValueError(
                    f"k={k} outside (0, max_k={self.params.max_k}]")
            serving = self._serving(index)
            gen = self.registry.get(index)
            handle = gen.handle if gen is not None else None
            # closed is read AFTER the registry lookup: a close() that
            # drained the registry between the two must surface as the
            # fatal `closed` rejection (batcher-side), never as a
            # transient not_ready a well-behaved client would retry
            # against a permanently closed server
            with self._lock:
                closed = self._closed
            if handle is None and not closed:
                # create_index/add_index registers the serving BEFORE its
                # first publish, and warmup can hold that window open for
                # minutes — a request admitted now would skip the k/dim
                # door checks below and fail later with the dispatcher's
                # internal KeyError instead of a retryable rejection
                obs.counter("serve.rejects_total", index=index,
                            reason="not_ready")
                exc = Overloaded(
                    f"serve[{index}]: not_ready "
                    "(first generation still building/warming)",
                    reason="not_ready",
                )
                _rerrors.classify(exc)
                raise exc
            if handle is not None and int(k) > handle.rows:
                # the k-ladder caps at the index size, so this request
                # would be silently truncated at delivery — reject it at
                # the door instead
                raise ValueError(
                    f"k={k} exceeds index rows={handle.rows}")
            if handle is not None and q.shape[1] != handle.dim:
                # a wrong-width query would fail the whole coalesced
                # batch at dispatch (np.concatenate), taking innocent
                # requests down with it — reject it at the door
                raise ValueError(
                    f"query dim {q.shape[1]} != index dim {handle.dim}")
            self._check_quota(serving, index, int(q.shape[0]))
            if deadline_ms is None:
                deadline_ms = self.params.deadline_ms
            deadline = (time.monotonic() + float(deadline_ms) / 1e3
                        if deadline_ms is not None else None)
            if (serving.result_cache is not None and prefilter is None
                    and handle is not None):
                return self._submit_cached(serving, handle, gen, q,
                                           int(k), index,
                                           deadline=deadline)
            return serving.batcher.submit(q, int(k), prefilter=prefilter,
                                          deadline=deadline)

    def _check_quota(self, serving: "_IndexServing", index: str,
                     rows: int) -> None:
        """Multi-tenant admission (docs/serving.md §13): per-index
        pending-row quotas and the server-wide total bound, both atop
        the batcher's own max_queue_rows backpressure. Advisory
        check-then-act (the hard bound stays the batcher's bounded
        queue): two racing submits can both pass a nearly-full quota —
        by at most one batch's rows, which the hard bound still caps."""
        p = self.params
        quota = (p.admission_quotas or {}).get(index)
        if quota is not None and \
                serving.batcher.depth_rows() + rows > int(quota):
            self._reject_quota(index, rows, f"index quota {quota}")
        if p.max_total_queue_rows is not None:
            with self._lock:
                servings = list(self._servings.values())
            total = sum(s.batcher.depth_rows() for s in servings)
            if total + rows > int(p.max_total_queue_rows):
                self._reject_quota(
                    index, rows,
                    f"server-wide quota {p.max_total_queue_rows}")

    def _reject_quota(self, index: str, rows: int, detail: str) -> None:
        obs.counter("serve.rejects_total", index=index, reason="quota")
        exc = Overloaded(
            f"serve[{index}]: quota ({rows} rows would exceed {detail})",
            reason="quota")
        _rerrors.classify(exc)
        raise exc

    def _submit_cached(self, serving: "_IndexServing", handle: "_Handle",
                       gen, q: np.ndarray, k: int, index: str,
                       deadline: Optional[float] = None) -> Future:
        """The result-cache front (docs/serving.md §12): answer a
        repeated (query, k) from host memory when nothing changed since
        it was computed; otherwise submit and install the answer once
        it delivers — only if the serving state is STILL the one the
        key was stamped with (a swap or mutation racing the in-flight
        request must not be cached under the older stamp)."""
        cache = serving.result_cache
        key = (q.tobytes(), k)
        with handle.state.lock:
            epoch = handle.state.seq
        gen_v = gen.version
        hit = cache.get(key, gen_v, epoch)
        if hit is not None:
            obs.counter("serve.result_cache_hits_total", index=index)
            fut: Future = Future()
            fut.generation = gen_v
            # hand back COPIES: a caller mutating its result in place
            # must not poison every later hit
            fut.set_result((hit[0].copy(), hit[1].copy()))
            return fut
        obs.counter("serve.result_cache_misses_total", index=index)
        fut = serving.batcher.submit(q, k, prefilter=None,
                                     deadline=deadline)

        def _install(f: Future) -> None:
            if f.exception() is not None:
                return
            if getattr(f, "generation", None) != gen_v:
                return                    # answered by a newer swap
            try:
                cur = self.registry.get(index)
                if cur is None or cur.version != gen_v:
                    return
                st = cur.handle.state
                with st.lock:
                    if st.seq != epoch:
                        return            # a mutation landed in flight
            except Exception:  # noqa: BLE001 — cache-insert probe only; a torn-down registry just skips the insert
                return
            d, i = f.result()
            cache.put(key, gen_v, epoch, (d.copy(), i.copy()))

        fut.add_done_callback(_install)
        return fut

    def search(self, queries, k: int, *, index: str = "default",
               prefilter=None, timeout_s: Optional[float] = None,
               deadline_ms: Optional[float] = None):
        """Blocking convenience over :meth:`submit`."""
        with obs.span("serve.search", index=index):
            fut = self.submit(queries, k, index=index, prefilter=prefilter,
                              deadline_ms=deadline_ms)
            return fut.result(timeout=timeout_s
                              if timeout_s is not None
                              else self.params.request_timeout_s)

    # -- mutation ----------------------------------------------------------

    def delete(self, ids, *, index: str = "default") -> int:
        """Tombstone rows by external id; takes effect on the next batch
        (the keep-mask composes with any user prefilter). Returns the
        number of rows that were live."""
        with obs.span("serve.delete", index=index):
            self._serving(index)
            # pin: a concurrent swap retiring the generation must not
            # drain its handle out from under the mutation
            gen = self._pin(index)
            try:
                st = gen.handle.state
                n = st.delete(ids)
                obs.counter("serve.deletes_total", n, index=index)
                obs.gauge("serve.tombstoned_rows", st.deleted_rows(),
                          index=index)
                return n
            finally:
                gen.release()

    def upsert(self, vectors, ids, *, index: str = "default") -> int:
        """Insert-or-replace vectors under external ``ids``: old rows are
        tombstoned, new rows land in the brute-force side buffer (merged
        into every search) until compaction folds them into the main
        index. Returns the side-buffer occupancy."""
        with obs.span("serve.upsert", index=index):
            serving = self._serving(index)
            # pin: a concurrent swap retiring the generation must not
            # drain its handle out from under the mutation
            gen = self._pin(index)
            try:
                h: _Handle = gen.handle
                v = np.asarray(vectors)
                n_rows = 1 if v.ndim == 1 else int(v.shape[0])
                side_rows, grew = h.state.upsert(v, ids)
                obs.counter("serve.upserts_total", n_rows, index=index)
                obs.gauge("serve.side_rows", side_rows, index=index)
                if grew and serving.warmup_enabled:
                    # a traced shape grew (side capacity, or the filter
                    # capacity rung crossed a pow2 boundary): re-warm so
                    # serving goes back to zero-compile steady state
                    serving.warmup_handle(h)
            finally:
                gen.release()
            if (self.params.compact_threshold
                    and side_rows >= self.params.compact_threshold):
                self.compact(index=index)
            return side_rows

    def compact(self, *, index: str = "default",
                wait: bool = False) -> Optional[Future]:
        """Fold the side buffer into the main index: background
        ``extend`` (or full rebuild for graph indexes) + warmup + atomic
        swap; the tombstone mask carries over (deleted rows stay
        tombstoned inside the extended index until the next full swap).
        No-op when the side buffer is empty."""
        with obs.span("serve.compact", index=index):
            serving = self._serving(index)
            if not serving.compacting.acquire(blocking=False):
                return None
            fut: Future = Future()

            def _run():
                try:
                    fut.set_result(self._compact_sync(serving))
                except BaseException as e:  # noqa: BLE001 — handed to the future; classified by resilience inside
                    _rerrors.classify(e)
                    fut.set_exception(e)
                finally:
                    serving.compacting.release()

            t = threading.Thread(target=_run, daemon=True,
                                 name=f"raft-tpu-serve-compact-{index}")
            t.start()
            if wait:
                fut.result()
            return fut

    def _compact_sync(self, serving: _IndexServing) -> int:
        name = serving.name
        gen = self._pin(name)
        try:
            h: _Handle = gen.handle
            st = h.state
            ticket = st.begin_compaction()
            if ticket is None:
                return self.registry.version(name)
            with obs.span("serve.compact_build", index=name,
                          rows=ticket.count):
                new_index, new_raw = _extend_index(
                    h, ticket.vectors, ticket.int_ids)
                # extend keeps n_lists, so the resolved params stay
                # valid; the raw user params ride along for later swaps
                new_h = _Handle(h.algo, new_index, st, h.search_params,
                                h.build_params, h.refine_ratio, new_raw,
                                user_search_params=h.user_search_params,
                                tiered_source=self._make_tiered(
                                    h.algo, new_raw),
                                adaptive=self._make_adaptive(
                                    h.algo, new_index, h.search_params,
                                    h.refine_ratio))
                if serving.warmup_enabled:
                    serving.warmup_handle(new_h)
                # commit + publish under the mutation lock: a dispatcher
                # pins (generation, state) as a consistent pair, so the
                # side-buffer shift and the extended index appear
                # atomically. self._lock nests inside (never the reverse
                # order anywhere), serializing against close().
                with st.lock, self._lock:
                    if self._closed:
                        obs.event("compaction_aborted", index=name,
                                  reason="server_closed")
                        return self.registry.version(name)
                    if self.registry.get(name) is not gen:
                        # a content swap superseded the generation this
                        # extend was built from — publishing would revert
                        # it to pre-swap data. Abort; the swap reset the
                        # overlay, so the snapshot is moot.
                        obs.event("compaction_aborted", index=name,
                                  reason="superseded_by_swap")
                        return self.registry.version(name)
                    st.commit_compaction(ticket)
                    v = self.registry.publish(name, new_h).version
                obs.counter("serve.compactions_total", index=name)
                return v
        finally:
            gen.release()

    # -- hot swap ----------------------------------------------------------

    def swap(self, name: str = "default", *, dataset=None, prebuilt=None,
             path=None, algo: Optional[str] = None, build_params=None,
             search_params=None, ids=None,
             refine_ratio: Optional[int] = None,
             wait: bool = False) -> Future:
        """Replace ``name``'s content with a freshly built/loaded index —
        in the background, then one atomic generation swap. In-flight
        batches finish on the old generation; it drains (and frees) when
        their pins drop. Exactly one of ``dataset`` (in-process build),
        ``prebuilt`` (an already-built index object), or ``path``
        (``core/serialize`` snapshot). The kwarg is ``prebuilt``, NOT
        ``index``, on purpose: every other Server method spells the
        index *name* ``index=``, so an ``index=`` here would make the
        habitual ``srv.swap(index="vectors")`` silently target
        "default" and hand the name string to the build thread.

        The mutable overlay RESETS with the new content (a swap is a
        wholesale replacement; use :meth:`compact` to fold mutations in
        instead)."""
        with obs.span("serve.swap", index=name):
            serving = self._serving(name)
            # pin for the handle read: an unpinned registry.get().handle
            # races a concurrent swap's drain (handle nulled) and raises
            # AttributeError after close() instead of KeyError. The local
            # `h` keeps the _Handle itself alive for the build thread.
            cur = self._pin(name)
            try:
                h: _Handle = cur.handle
            finally:
                cur.release()
            a = algo or h.algo
            fut: Future = Future()

            def _run():
                try:
                    if path is not None:
                        new_index = _ALGO_MODULES[a].load(path)
                        ds = None
                    elif prebuilt is not None:
                        new_index, ds = prebuilt, dataset
                    else:
                        ds = np.ascontiguousarray(np.asarray(dataset),
                                                  dtype=np.float32)
                        new_index = _build_index(
                            a, ds, build_params
                            if build_params is not None else h.build_params)
                    rows = _index_rows(a, new_index)
                    dim = _index_dim(a, new_index)
                    state = MutableState(
                        rows, dim, np.float32, ext_ids=ids,
                        side_capacity=self.params.side_capacity)
                    # inherit the caller's RAW params (not the resolved
                    # ones): defaulted n_probes = n_lists must be
                    # re-derived from the NEW index, or a swap to a
                    # bigger dataset silently clamps probing at the old
                    # index's n_lists and serves non-exhaustive results
                    # — and with adaptive_probes on, the whole probe
                    # LADDER re-derives from the re-resolved ceiling
                    # (not just the ceiling itself), so a bigger
                    # successor's top rung is its own n_lists
                    sp_user = (search_params if search_params is not None
                               else h.user_search_params
                               if a == h.algo else None)
                    new_raw = _raw_dataset(a, new_index, ds)
                    sp_new = _default_search_params(a, new_index, sp_user)
                    rr_new = (refine_ratio if refine_ratio is not None
                              else h.refine_ratio)
                    new_h = _Handle(
                        a, new_index, state, sp_new,
                        build_params if build_params is not None
                        else h.build_params,
                        rr_new,
                        new_raw,
                        user_search_params=sp_user,
                        tiered_source=self._make_tiered(a, new_raw),
                        adaptive=self._make_adaptive(a, new_index,
                                                     sp_new, rr_new))
                    if serving.warmup_enabled:
                        serving.warmup_handle(new_h)
                    gen = self._publish_guarded(name, new_h)
                    fut.set_result(gen.version)
                except BaseException as e:  # noqa: BLE001 — handed to the future; classified for obs/flight
                    _rerrors.classify(e)
                    fut.set_exception(e)

            t = threading.Thread(target=_run, daemon=True,
                                 name=f"raft-tpu-serve-swap-{name}")
            t.start()
            if wait:
                fut.result()
            return fut

    # -- introspection / lifecycle ----------------------------------------

    def warmup(self, index: str = "default") -> int:
        """(Re)trace the serving ladder for ``index``'s current
        generation; returns the number of shapes warmed."""
        with obs.span("serve.warmup_entry", index=index):
            serving = self._serving(index)
            # pinned: the generation cannot drain (and null its handle)
            # while the warmup sweep is tracing against it
            gen = self._pin(index)
            try:
                return serving.warmup_handle(gen.handle)
            finally:
                gen.release()

    def generation(self, index: str = "default") -> int:
        return self.registry.version(index)

    def stats(self, index: str = "default") -> dict:
        gen = self.registry.get(index)
        serving = self._servings.get(index)
        handle = gen.handle if gen is not None else None  # single read: a
        #                       concurrent drain nulls it between accesses
        st = handle.state if handle is not None else None
        return {
            "generation": self.registry.version(index),
            "queue_rows": serving.batcher.depth_rows() if serving else 0,
            "bucket_ceiling": serving.batcher.ceiling if serving else 0,
            "ladder": list(serving.batcher.ladder) if serving else [],
            "live_rows": st.live_rows() if st else 0,
            "tombstoned_rows": st.deleted_rows() if st else 0,
            "side_rows": st.side_rows_live() if st else 0,
            "generations_live": len(self.registry.live_generations()),
            "probe_ladder": (list(handle.adaptive.ladder)
                             if handle is not None
                             and handle.adaptive is not None else None),
            "quality": (serving.quality.stats()
                        if serving is not None
                        and serving.quality is not None else None),
        }

    def close(self, timeout_s: float = 30.0) -> None:
        """Stop admissions, drain every queue, retire every index."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            servings = list(self._servings.values())
        for s in servings:
            s.batcher.close(timeout_s=timeout_s)
        for s in servings:
            # after the batcher drains no new tickets can arrive; now
            # drain the graft-flow completion queue so every in-flight
            # batch resolves its futures and releases its pin
            s.close_pipeline(timeout_s=timeout_s)
        for s in servings:
            # shadow samples still queued at close are dropped, not
            # dispatched — their generation pins (and the probation
            # pin) must release or the retired generations never drain
            if s.quality is not None:
                s.quality.close(s.batcher.drain_shadow())
        for name in self.registry.names():
            self.registry.drop(name)

    def __enter__(self) -> "Server":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def _serving(self, name: str) -> _IndexServing:
        with self._lock:
            s = self._servings.get(name)
        if s is None:
            raise KeyError(
                f"no index named {name!r}; create_index/add_index first")
        return s

    def _pin(self, name: str):
        """Pin ``name``'s current generation, diagnosing a closed server
        correctly: close() drops every registry name, so a bare
        registry.pin after close raises KeyError claiming the index was
        never published — the truthful, fail-fast signal is 'server is
        closed' (the submit path's Overloaded(reason="closed")
        analog for the mutation/warmup entry points)."""
        try:
            return self.registry.pin(name)
        except KeyError:
            with self._lock:
                closed = self._closed
            if closed:
                raise RuntimeError("server is closed") from None
            raise


# ---------------------------------------------------------------------------
# per-algo construction adapters
# ---------------------------------------------------------------------------

_ALGO_MODULES = {
    "brute_force": brute_force,
    "ivf_flat": ivf_flat,
    "ivf_pq": ivf_pq,
    "cagra": cagra,
    "hybrid": hybrid,
}


def _build_index(algo: str, dataset: np.ndarray, build_params):
    if algo == "hybrid":
        if build_params is None:
            raise ValueError(
                "algo='hybrid' needs build_params=hybrid.IndexParams("
                "dense_dim=...) — the engine cannot guess where the "
                "dense columns end and the vocab begins")
        return hybrid.build(build_params, dataset)
    if algo == "brute_force":
        if build_params is None:
            return brute_force.build(dataset)
        return brute_force.build(dataset, metric=build_params.metric,
                                 metric_arg=build_params.metric_arg)
    if build_params is None:
        n = dataset.shape[0]
        if algo == "ivf_flat":
            build_params = ivf_flat.IndexParams(
                n_lists=max(1, min(64, n // 32)))
        elif algo == "ivf_pq":
            build_params = ivf_pq.IndexParams(
                n_lists=max(1, min(64, n // 32)))
        else:
            build_params = cagra.IndexParams()
    return _ALGO_MODULES[algo].build(build_params, dataset)


def _default_search_params(algo: str, index, search_params):
    if search_params is not None:
        return search_params
    if algo == "ivf_flat":
        # serving default: exhaustive probing — exact recall over the
        # tombstone-filtered index, the contract the correctness
        # acceptance tests pin. With ServeParams.adaptive_probes this
        # pin is the adaptive ladder's exhaustive CEILING, not the
        # per-query probe count: easy queries serve from lower rungs
        # and ambiguous ones escape back up to exactly this program
        # (ISSUE 14; docs/serving.md §13)
        return ivf_flat.SearchParams(n_probes=index.n_lists,
                                     compute_dtype="f32",
                                     local_recall_target=1.0)
    if algo == "ivf_pq":
        return ivf_pq.SearchParams(n_probes=index.n_lists,
                                   local_recall_target=1.0)
    if algo == "cagra":
        return cagra.SearchParams(itopk_size=128)
    if algo == "hybrid":
        return hybrid.SearchParams()
    return None


def _raw_dataset(algo: str, index, dataset: Optional[np.ndarray]):
    """The raw row store serving keeps for refine + graph rebuilds,
    indexed by internal id. brute_force/cagra carry it on the index."""
    if algo in ("brute_force", "cagra"):
        return np.asarray(index.dataset)
    return dataset


def _extend_index(h: _Handle, vectors: np.ndarray, int_ids: np.ndarray):
    """Compaction build: fold side rows into the main index. ivf_* use
    the module ``extend``; brute_force/cagra (positional ids) rebuild
    over the concatenated row store. Returns (new_index, new_raw)."""
    algo = h.algo
    if algo == "ivf_flat":
        new = ivf_flat.extend(h.index, vectors,
                              int_ids.astype(np.int32))
        raw = None if h.raw_dataset is None else np.concatenate(
            [h.raw_dataset, vectors], axis=0)
        return new, raw
    if algo == "ivf_pq":
        new = ivf_pq.extend(h.index, vectors, int_ids.astype(np.int32))
        raw = None if h.raw_dataset is None else np.concatenate(
            [h.raw_dataset, vectors], axis=0)
        return new, raw
    full = np.concatenate([np.asarray(h.raw_dataset), vectors], axis=0)
    if algo == "brute_force":
        return brute_force.build(full, metric=h.metric,
                                 metric_arg=h.index.metric_arg), full
    if algo == "hybrid":
        return hybrid.build(h.build_params, full), full
    params = h.build_params or cagra.IndexParams()
    return cagra.build(params, full), full
