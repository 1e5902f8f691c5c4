"""Tiered-memory rerank sources: host/mmap originals, shortlist-only
fetch, and Zipf-aware hot-row residency (ROADMAP item 3; ISSUE 12).

FusionANNS (PAPERS.md, arXiv:2409.16576) shows the billion-scale win is
a memory-hierarchy split: compressed codes stay accelerator-resident,
raw vectors live on host RAM / SSD, and only *shortlist* bytes ever
cross the link. This module is that split for the
``ivf_pq.search_refined`` pipeline:

* :class:`RerankSource` — one interface over every place the exact
  rerank stage can read originals from: a host numpy array or
  ``np.memmap`` file (:class:`HostArraySource`), an already
  device-resident array (:class:`DeviceSource`, the old full-upload
  fast path), with the index's own device cache/codes paths staying
  inside ``search_refined`` (they never fetch — the compressed rungs
  ARE resident).
* **Shortlist-only fetch** — per batch, the host source gathers only
  the **unique** valid shortlist rows, pads them to a power-of-two
  rung (so serve's zero-retrace warmup can enumerate every fetched
  block shape), uploads just those ``<= m*kc`` rows, and scores them
  with :func:`raft_tpu.neighbors.refine.score_gathered` — the SAME
  arithmetic as the full-upload path, so results are bitwise
  identical on the same shortlist while bytes-moved drops from
  ``n*d*itemsize`` to shortlist scale.
* **Hot-row residency** — real traffic is Zipf-skewed (JUNO's workload
  analysis, PAPERS.md), so a fixed-budget HBM hot-row cache
  (clock/second-chance; budget rows via ``tuning.budget`` knob
  ``tiered_hot_rows``) is consulted before the host gather: rows
  fetched repeatedly are promoted device-side FROM the already
  uploaded miss block (no second transfer), hits are served from HBM
  with zero link bytes, and evictions are counted.

Observability (docs/observability.md): ``tiered.hit_rate{tier=hbm|
host}``, ``tiered.hits_total{tier}``, ``tiered.lookups_total``,
``tiered.bytes_moved_total{link}``, ``tiered.evictions_total``,
``tiered.promotions_total`` — bytes-moved-per-query is the bench
column ROADMAP item 3 budgets against.

Thread model: host bookkeeping (hot-cache maps, counters) is guarded
by a lock; device work runs outside it. Concurrent ``rerank`` calls
are safe: a batch classifies hits and snapshots the hot block under
ONE lock hold (the map never references a row the snapshot lacks —
promotions reserve slots at plan time but only enter the map at a
compare-and-swap commit after their rows landed in an installed
block, and the scatter is undonated so an in-flight reader's
snapshot stays readable). Interleaved promoters can lose a commit —
costing a duplicate fetch later, never a wrong result.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu import obs, tuning
from raft_tpu.analysis import lockwatch
from raft_tpu.distance.types import DistanceType, resolve_metric
from raft_tpu.neighbors.refine import exact_scores as _exact_scores
from raft_tpu.neighbors.refine import score_gathered as _score_gathered
from raft_tpu.neighbors.refine import select_scored as _select_scored
from raft_tpu.utils.math import next_pow2

# tuning.budget knob: HBM hot-row cache capacity in ROWS (docs/
# dispatch_tuning.md). A site-captured table or a runtime
# record_budget ceiling overrides the default.
HOT_ROWS_BUDGET = "tiered_hot_rows"
DEFAULT_HOT_ROWS = 4096
# smallest fetched-block rung: bounds the warmup trace count (rungs
# per (m, c) shape = log2(next_pow2(m*c) / RUNG_FLOOR) + 1) without
# inflating small fetches beyond one tile's worth of rows
RUNG_FLOOR = 64
# fixed-width promotion scatter: at most this many rows enter the hot
# cache per batch, through ONE shape-stable (undonated — see
# _promote_scatter) scatter; promotion pressure beyond it carries over
# via the miss counts, and the hottest rows — highest miss counts — go
# first
PROMOTE_BATCH = 256


@dataclasses.dataclass
class FetchInfo:
    """What one shortlist fetch actually moved (the dedup-honest
    numbers behind ``rerank.bytes_fetched_total`` / ``tiered.*``)."""

    valid_slots: int = 0      # shortlist slots with a real candidate
    unique_rows: int = 0      # distinct row ids among them
    hbm_hits: int = 0         # served from the hot-row cache
    host_rows: int = 0        # gathered from the host/mmap source
    rung: int = 0             # padded upload rows (the link shape)
    bytes_link: int = 0       # bytes that crossed host->device
    bytes_rows: int = 0       # unique fetched-row payload (deduped)
    promotions: int = 0
    evictions: int = 0


class RerankSource:
    """One interface over every exact-rerank fidelity source. The
    contract: ``rerank(queries, candidate_ids, k, metric)`` re-scores
    global-id candidates exactly and returns host-of-jit ``(d, ids)``
    [m, k]; negative ids are invalid and sink to the sentinel."""

    kind = "abstract"
    dim: int = 0
    row_bytes: int = 0

    def rerank(self, queries, candidates, k: int, metric
               ) -> Tuple[jax.Array, jax.Array]:
        d, i, _ = self.rerank_info(queries, candidates, k, metric)
        return d, i

    def rerank_info(self, queries, candidates, k: int, metric
                    ) -> Tuple[jax.Array, jax.Array, FetchInfo]:
        raise NotImplementedError

    def prepare(self, queries, candidates):
        """The fetch half of a stage-split rerank: everything host-side
        — shortlist sync, dedup/classify, gather, device upload —
        packaged as an opaque handle for :meth:`score`. graft-flow's
        producers run this for batch N+1 while batch N scores; the
        default defers everything to ``score`` (device-resident sources
        have no host fetch to overlap)."""
        return (queries, candidates)

    def score(self, prepared, k: int, metric
              ) -> Tuple[jax.Array, jax.Array, FetchInfo]:
        """The device half: exact-score a :meth:`prepare` handle.
        ``score(prepare(q, c), k, metric)`` is always bitwise
        ``rerank_info(q, c, k, metric)`` — the split moves *when* the
        fetch happens, never what is computed."""
        queries, candidates = prepared
        return self.rerank_info(queries, candidates, k, metric)

    def warm(self, m: int, c: int, k: int, metric,
             query_dtype=jnp.float32) -> int:
        """Trace every device shape an [m, c] shortlist rerank at
        ``k`` can dispatch (serve's zero-retrace warmup hook).
        Returns the number of shapes traced."""
        return 0

    def stats(self) -> dict:
        return {}


class DeviceSource(RerankSource):
    """The pre-tiered fast path: the whole dataset device-resident,
    rerank is one gather + exact scoring (``neighbors.refine``'s
    arithmetic, through :func:`_score_fetched` with the dataset as the
    block). Right when the originals fit HBM next to the index — no
    fetch, no residency policy, nothing to warm beyond that program."""

    kind = "device"

    def __init__(self, dataset):
        self.dataset = (dataset if isinstance(dataset, jax.Array)
                        else jnp.asarray(dataset))
        if self.dataset.ndim != 2:
            raise ValueError(
                f"dataset must be [n, dim], got {self.dataset.shape}")
        self.dim = int(self.dataset.shape[1])
        self.row_bytes = self.dim * self.dataset.dtype.itemsize

    def rerank_info(self, queries, candidates, k, metric):
        candidates = jnp.asarray(candidates)
        d, i = _score_fetched(jnp.asarray(queries), self.dataset,
                              candidates, candidates, int(k),
                              int(resolve_metric(metric)))
        info = FetchInfo(rung=int(self.dataset.shape[0]))
        return d, i, info


class RowShardedSource(DeviceSource):
    """Device-resident rows sharded by row over one mesh axis (a
    ``jax.Array`` whose ``NamedSharding`` splits axis 0 and nothing
    else). Each chip scores the candidates whose rows it holds, from its
    own shard, and writes 0 for the rest; one ``psum`` over the axis
    joins the scores, and the top-k is taken on the joined scores. Only
    one chip holds each row, so the result is bitwise
    :class:`DeviceSource`'s over the same rows, and no chip ever reads
    another chip's rows."""

    def __init__(self, dataset, mesh, axis: str):
        super().__init__(dataset)
        self.mesh, self.axis = mesh, axis

    def rerank_info(self, queries, candidates, k, metric):
        d, i = _score_row_sharded(
            jnp.asarray(queries), self.dataset, jnp.asarray(candidates),
            int(k), int(resolve_metric(metric)), self.mesh, self.axis)
        return d, i, FetchInfo(rung=int(self.dataset.shape[0]))


class HostArraySource(RerankSource):
    """Host-resident originals (numpy array or ``np.memmap``): the
    rerank stage fetches only the unique shortlist rows per batch —
    the dataset itself never crosses the link. See the module
    docstring for the residency policy."""

    kind = "host"

    def __init__(self, dataset: np.ndarray,
                 hot_rows: Optional[int] = None,
                 promote_after: int = 2,
                 promote_batch: int = PROMOTE_BATCH):
        if not isinstance(dataset, np.ndarray):
            raise TypeError(
                "HostArraySource wants a host numpy array or np.memmap; "
                f"got {type(dataset).__name__} — pass device arrays to "
                "DeviceSource (the full-upload fast path) instead")
        if dataset.ndim != 2:
            raise ValueError(f"dataset must be [n, dim], got {dataset.shape}")
        self.dataset = dataset
        self.rows = int(dataset.shape[0])
        self.dim = int(dataset.shape[1])
        self.dtype = np.dtype(dataset.dtype)
        self.row_bytes = self.dim * self.dtype.itemsize
        if hot_rows is None:
            hot_rows = tuning.budget(HOT_ROWS_BUDGET, DEFAULT_HOT_ROWS)
        self.hot_capacity = max(min(int(hot_rows), self.rows), 0)
        self.promote_after = max(int(promote_after), 1)
        # the fixed promotion-scatter width (shape-stable per source)
        self.promote_batch = max(int(promote_batch), 1)
        self._lock = lockwatch.make_lock("tiered.source")
        # clock/second-chance residency state (guarded by _lock)
        self._slot_of: dict = {}                   # row id -> slot
        self._id_at = np.full(self.hot_capacity, -1, np.int64)
        self._ref = np.zeros(self.hot_capacity, bool)
        self._hand = 0
        self._used = 0
        self._miss_counts: dict = {}               # row id -> fetches seen
        self._hot_block: Optional[jax.Array] = None
        # per-rung device zero blocks: a fully-hot batch (no misses)
        # still needs a miss-block operand for the shape-stable scorer,
        # but it must not UPLOAD one — steady state at hit-rate ~1
        # would otherwise pay a pointless RUNG_FLOOR transfer per batch
        # and inflate bytes_moved (benign-race dict: worst case two
        # threads build the same zeros block once)
        self._zero_blocks: dict = {}
        # cumulative fetch accounting (stats()/tests; obs mirrors it)
        self._lookups = 0
        self._hbm_hits = 0
        self._host_rows = 0
        self._bytes_link = 0
        self._evictions = 0
        self._promotions = 0

    # -- residency bookkeeping (host-side, under _lock) -------------------

    def _classify_locked(self, uniq: np.ndarray):
        """Split sorted unique ids into hot hits (with slots) and
        misses; mark hit slots' reference bits (second chance)."""
        if self.hot_capacity == 0 or not self._slot_of:
            return np.full(uniq.size, -1, np.int64)
        slots = np.fromiter(
            (self._slot_of.get(int(i), -1) for i in uniq),
            np.int64, uniq.size)
        hit = slots >= 0
        if hit.any():
            self._ref[slots[hit]] = True
        return slots

    def _evict_slot_locked(self) -> int:
        """Clock hand: skip (and clear) referenced slots once, evict
        the first unreferenced one."""
        cap = self.hot_capacity
        for _ in range(2 * cap):
            h = self._hand
            self._hand = (h + 1) % cap
            if self._ref[h]:
                self._ref[h] = False
                continue
            old = int(self._id_at[h])
            if old >= 0:
                self._slot_of.pop(old, None)
                self._evictions += 1
            return h
        return self._hand  # unreachable: a full sweep clears every bit

    def _plan_promotions_locked(self, miss_ids: np.ndarray):
        """Count misses; rows past ``promote_after`` fetches get a hot
        slot (evicting via the clock when full). Returns (ids, slots)
        capped at ``promote_batch`` — overflow keeps its count and
        promotes on the next fetch.

        The plan only RESERVES: eviction victims leave the slot map
        here (nobody may hit a slot whose content is about to change),
        but the promoted ids are NOT mapped yet — that happens in
        :meth:`_commit_promotions_locked` once their rows have
        actually landed in a new hot block, so a concurrent classify
        can never hit a slot whose data is still in flight."""
        if self.hot_capacity == 0:
            return [], []
        eligible = []
        for i in miss_ids:
            i = int(i)
            c = self._miss_counts.get(i, 0) + 1
            self._miss_counts[i] = c
            if c >= self.promote_after:
                eligible.append((c, i))
        # hottest first: the promote_batch budget goes to the rows with
        # the most recorded fetches, so the Zipf head becomes resident
        # before the tail ever competes for slots
        eligible.sort(reverse=True)
        # keyed by SLOT: an eviction inside this same batch can hand a
        # slot out twice, and a scatter with duplicate destinations has
        # an unspecified winner — the superseded entry must leave the
        # plan, or the slot map can end up pointing at the loser's row
        plan: dict = {}
        for _, i in eligible[:self.promote_batch]:
            self._miss_counts.pop(i, None)
            if self._used < self.hot_capacity:
                slot = self._used
                self._used += 1
            else:
                slot = self._evict_slot_locked()
            self._id_at[slot] = -1        # pending: reserved, unmapped
            self._ref[slot] = True
            plan[slot] = i
        slots = list(plan.keys())
        ids = [plan[s] for s in slots]
        # crude aging: the miss-count map must not grow with the key
        # space — when it outruns the cache by 8x, start over (hot rows
        # already resident are unaffected; cold tails just re-count)
        if len(self._miss_counts) > max(8 * self.hot_capacity, 1 << 16):
            self._miss_counts.clear()
        return ids, slots

    def _commit_promotions_locked(self, old_blk, new_blk, ids, slots
                                  ) -> bool:
        """Install a promotion scatter's result — only if ``old_blk``
        is still the current block (compare-and-swap). A concurrent
        promoter that lost the race leaves its slots reserved-but-empty
        (the clock reclaims them) and its rows simply re-count toward
        the next promotion; a lost update can only cost a re-fetch,
        never serve a wrong row."""
        if self._hot_block is not old_blk:
            return False
        self._hot_block = new_blk
        for i, slot in zip(ids, slots):
            self._slot_of[i] = slot
            self._id_at[slot] = i
        self._promotions += len(ids)
        return True

    def _ensure_hot_block(self):
        if self.hot_capacity == 0:
            return None
        blk = self._hot_block
        if blk is None:
            blk = jnp.zeros((self.hot_capacity, self.dim), self.dtype)
            with self._lock:
                if self._hot_block is None:
                    self._hot_block = blk
                blk = self._hot_block
        return blk

    # -- the fetch ---------------------------------------------------------

    def _gather(self, ids_host: np.ndarray):
        """The shortlist-only fetch: dedupe, split hot/miss, gather
        misses from the host source padded to a pow2 rung, plan
        promotions. Returns device operands + :class:`FetchInfo`."""
        m, c = ids_host.shape
        valid = ids_host >= 0
        info = FetchInfo(valid_slots=int(np.count_nonzero(valid)))
        vids = ids_host[valid].astype(np.int64, copy=False)
        uniq = np.unique(vids)                     # sorted
        info.unique_rows = int(uniq.size)
        with self._lock:
            ev0 = self._evictions
            slots = self._classify_locked(uniq)
            hot_u = slots >= 0
            miss_ids = uniq[~hot_u]
            pro_ids, pro_slots = self._plan_promotions_locked(miss_ids)
            info.promotions = len(pro_ids)
            info.evictions = self._evictions - ev0
            # the block snapshot rides the SAME lock hold as the
            # classification: every slot the map just handed out holds
            # its row in THIS block, and (undonated) XLA buffers stay
            # live for in-flight readers even after a later commit
            # installs a successor
            blk = self._hot_block
        info.hbm_hits = int(np.count_nonzero(hot_u))
        info.host_rows = int(miss_ids.size)
        rung = max(next_pow2(max(info.host_rows, 1)),
                   min(RUNG_FLOOR, next_pow2(max(m * c, 1))))
        info.rung = rung
        if miss_ids.size:
            block = np.zeros((rung, self.dim), self.dtype)
            # sorted unique ids -> one ascending strided read; the
            # memmap-friendly access pattern refine_host also relies on
            block[:miss_ids.size] = self.dataset[miss_ids]
            miss_dev = jax.device_put(block)
            info.bytes_link = rung * self.row_bytes
        else:
            # fully hot: serve the scorer a cached device zeros block —
            # nothing crosses the link
            miss_dev = self._zero_blocks.get(rung)
            if miss_dev is None:
                miss_dev = jnp.zeros((rung, self.dim), self.dtype)
                self._zero_blocks[rung] = miss_dev
            info.bytes_link = 0
        info.bytes_rows = info.host_rows * self.row_bytes
        # per-unique-row position: hot rows index the resident block,
        # misses index the freshly fetched one (in sorted-miss order)
        upos = np.empty(uniq.size, np.int32)
        upos[hot_u] = slots[hot_u].astype(np.int32)
        upos[~hot_u] = np.arange(info.host_rows, dtype=np.int32)
        safe = np.where(valid, ids_host, uniq[0] if uniq.size else 0)
        j = np.searchsorted(uniq, safe) if uniq.size else np.zeros(
            (m, c), np.int64)
        pos = upos[j] if uniq.size else np.zeros((m, c), np.int32)
        is_hot = hot_u[j] if uniq.size else np.zeros((m, c), bool)
        pos_dev = jax.device_put(np.ascontiguousarray(pos, np.int32))
        hot_dev = jnp.asarray(is_hot)
        promote = None
        if pro_ids:
            src = np.searchsorted(miss_ids, np.asarray(pro_ids, np.int64))
            src = np.resize(src.astype(np.int32), self.promote_batch)
            dst = np.full(self.promote_batch, self.hot_capacity, np.int32)
            dst[:len(pro_slots)] = np.asarray(pro_slots, np.int32)
            promote = (jax.device_put(src), jax.device_put(dst),
                       pro_ids, pro_slots)
        self._record(info)
        return miss_dev, pos_dev, hot_dev, blk, promote, info

    def _record(self, info: FetchInfo) -> None:
        with self._lock:
            self._lookups += info.unique_rows
            self._hbm_hits += info.hbm_hits
            self._host_rows += info.host_rows
            self._bytes_link += info.bytes_link
            lookups, hits = self._lookups, self._hbm_hits
        obs.counter("tiered.lookups_total", info.unique_rows)
        obs.counter("tiered.hits_total", info.hbm_hits, tier="hbm")
        obs.counter("tiered.hits_total", info.host_rows, tier="host")
        obs.counter("tiered.bytes_moved_total", info.bytes_link,
                    link="host_to_device")
        if info.promotions:
            obs.counter("tiered.promotions_total", info.promotions)
        if info.evictions:
            obs.counter("tiered.evictions_total", info.evictions)
        if lookups:
            obs.gauge("tiered.hit_rate", hits / lookups, tier="hbm")
            obs.gauge("tiered.hit_rate", 1.0 - hits / lookups,
                      tier="host")

    # -- the rerank --------------------------------------------------------

    def prepare(self, queries, candidates):
        """The host fetch for one shortlist batch: sync the ids, dedupe
        + hot/miss classify, gather misses, upload. Runs on graft-flow
        producer threads: the lock discipline in :meth:`_gather` and
        the CAS promotion commit in :meth:`score` make an overlapped
        ``prepare(N+1)`` vs ``score(N)`` race-free — at worst a
        concurrent classify misses a just-promoted row and re-fetches
        it (module docstring), never a wrong result."""
        from raft_tpu.resilience import faultinject

        # the fetch-stage fault point: slow@stage:tiered.fetch models
        # host-tier gather latency (stage-scoped only — chunk faults
        # stay with the consuming dispatch)
        faultinject.check(stage="tiered.fetch", stage_only=True)
        # the structural host sync of the tiered pipeline: the
        # shortlist ids must reach the host to drive the gather — this
        # is the ONE device->host hop the architecture is built around
        ids_host = np.asarray(candidates)  # the sync IS the tier boundary
        if ids_host.ndim != 2:
            raise ValueError(f"candidates must be [m, c], got "
                             f"{ids_host.shape}")
        if self.hot_capacity:
            self._ensure_hot_block()       # device alloc OUTSIDE _lock
        gathered = self._gather(ids_host)
        q = queries if isinstance(queries, jax.Array) \
            else jnp.asarray(queries)
        # stage 1 hands us a device int32 array: reuse it rather than
        # re-uploading the ids we just pulled down for the gather
        if (isinstance(candidates, jax.Array)
                and candidates.dtype == jnp.int32):
            cand = candidates
        else:
            cand = jnp.asarray(ids_host.astype(np.int32, copy=False))
        return (q, cand, gathered)

    def score(self, prepared, k, metric):
        q, cand, (miss_dev, pos_dev, hot_mask, blk, promote,
                  info) = prepared
        metric = resolve_metric(metric)
        if self.hot_capacity:
            d, i = _score_fetched_hot(q, miss_dev, blk, pos_dev,
                                      hot_mask, cand, int(k),
                                      int(metric))
            if promote is not None:
                # promoted rows are a subset of THIS batch's miss
                # block: scatter device-to-device (no second upload,
                # and NOT donated — a concurrent reader's snapshot of
                # the old block must stay readable). The plan reserved
                # the slots; the map only learns the new ids at the
                # compare-and-swap commit below, once their rows exist
                # in an installed block.
                src_pos, dst_slot, pro_ids, pro_slots = promote
                new_blk = _promote_scatter(blk, miss_dev, src_pos,
                                           dst_slot)
                with self._lock:
                    self._commit_promotions_locked(blk, new_blk,
                                                   pro_ids, pro_slots)
        else:
            d, i = _score_fetched(q, miss_dev, pos_dev, cand, int(k),
                                  int(metric))
        return d, i, info

    def rerank_info(self, queries, candidates, k, metric):
        return self.score(self.prepare(queries, candidates), k, metric)

    # -- warmup / stats ----------------------------------------------------

    def rungs(self, max_unique: int):
        """Every fetched-block rung an ``max_unique``-row shortlist can
        produce (the pow2 ladder warmup must cover)."""
        top = next_pow2(max(int(max_unique), 1))
        r = min(RUNG_FLOOR, top)
        out = []
        while r < top:
            out.append(r)
            r <<= 1
        out.append(top)
        return out

    def warm(self, m: int, c: int, k: int, metric,
             query_dtype=jnp.float32) -> int:
        """Trace the scorer (and the promotion scatter) at every rung
        an [m, c] shortlist can fetch, so steady-state serving adds
        zero XLA traces (the GL007 bar — serve's warmup calls this per
        (bucket, k-rung) pair)."""
        metric = resolve_metric(metric)
        q = jnp.zeros((m, self.dim), query_dtype)
        cand = jnp.full((m, c), -1, jnp.int32)
        pos = jnp.zeros((m, c), jnp.int32)
        hot_mask = jnp.zeros((m, c), bool)
        blk = self._ensure_hot_block()
        traced = 0
        for rung in self.rungs(m * c):
            miss = jnp.zeros((rung, self.dim), self.dtype)
            if blk is not None:
                out = _score_fetched_hot(q, miss, blk, pos, hot_mask,
                                         cand, int(k), int(metric))
                src = jnp.zeros((self.promote_batch,), jnp.int32)
                dst = jnp.full((self.promote_batch,), self.hot_capacity,
                               jnp.int32)
                # trace only — every real dst is out of bounds, and
                # without donation the result needs no install
                out = (out, _promote_scatter(blk, miss, src, dst))
            else:
                out = _score_fetched(q, miss, pos, cand, int(k),
                                     int(metric))
            jax.block_until_ready(out)
            traced += 1
        return traced

    def stats(self) -> dict:
        with self._lock:
            lookups = self._lookups
            return {
                "lookups": lookups,
                "hbm_hits": self._hbm_hits,
                "host_rows": self._host_rows,
                "hit_rate_hbm": (self._hbm_hits / lookups) if lookups
                else 0.0,
                "bytes_moved": self._bytes_link,
                "evictions": self._evictions,
                "promotions": self._promotions,
                "hot_capacity": self.hot_capacity,
                "hot_used": self._used,
            }


# ---------------------------------------------------------------------------
# device kernels (shape-stable: traced per (m, c, rung); warm() covers
# the rung ladder so serving never compiles in steady state)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(4, 5))
def _score_fetched(queries, block, pos, candidates, k: int,
                   metric_val: int):
    """Exact scoring over the fetched miss block only (no hot cache):
    gather [m, c, d] candidate vectors by block position, then the
    shared :func:`refine.score_gathered` tail. Runs under the named
    scope ``ivf_pq.rerank``, as every rerank program does."""
    with jax.named_scope("ivf_pq.rerank"):
        metric = DistanceType(metric_val)
        compute = jnp.promote_types(queries.dtype, jnp.float32)
        q = queries.astype(compute)
        safe = jnp.clip(pos, 0, block.shape[0] - 1)
        cand_vecs = block[safe].astype(compute)
        return _score_gathered(q, cand_vecs, candidates, k, metric)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _score_fetched_hot(queries, block, hot_block, pos, is_hot,
                       candidates, k: int, metric_val: int):
    """Exact scoring over the two-tier candidate store: ``pos`` indexes
    the hot HBM block where ``is_hot``, the fetched miss block
    elsewhere. Two gathers + a select keep the cost O(m*c*d) — never
    O(hot_capacity) per batch."""
    with jax.named_scope("ivf_pq.rerank"):
        metric = DistanceType(metric_val)
        compute = jnp.promote_types(queries.dtype, jnp.float32)
        q = queries.astype(compute)
        vm = block[jnp.clip(pos, 0, block.shape[0] - 1)]
        vh = hot_block[jnp.clip(pos, 0, hot_block.shape[0] - 1)]
        cand_vecs = jnp.where(is_hot[..., None], vh, vm).astype(compute)
        return _score_gathered(q, cand_vecs, candidates, k, metric)


# the row-sharded rerank reads its rows a block at a time: a gather
# straight from a chip's shard first copies the whole shard to the
# layout it reads (narrow rows lie column-major on the TPU: 5.8 GB of
# temporaries at 10M x 96), a block of this many rows is copied instead
_SHARD_BLOCK_ROWS = 1 << 18
# candidates taken per step, at most, from the sorted owned candidates
_SHARD_WINDOW = 1 << 13
# (query, candidate) pairs scored per block
_SCORE_ROWS = 1 << 17


def _gather_held_rows(xs, rows, mine):
    """``xs[rows]`` where ``mine``, zero rows elsewhere, as
    ``[rows.size, w]`` (rows zero-padded to ``w``, whole lanes), with
    no copy of ``xs`` larger than one block.

    The held candidates are sorted by row; each step copies the block of
    ``_SHARD_BLOCK_ROWS`` rows that starts at the next unread candidate,
    gathers up to ``_SHARD_WINDOW`` sorted candidates that lie in it,
    and scatters them to their places. A step reads at least one
    candidate, so the loop ends; it runs about once per block that holds
    a candidate."""
    n_loc, dim = xs.shape
    total = rows.size
    block = min(_SHARD_BLOCK_ROWS, n_loc)
    window = min(_SHARD_WINDOW, total)
    align = min(1024, block)
    key = jnp.where(mine, rows, n_loc).reshape(total).astype(jnp.int32)
    key, pos = jax.lax.sort(
        (key, jnp.arange(total, dtype=jnp.int32)), num_keys=1)
    held = jnp.sum(mine, dtype=jnp.int32)
    # a window read past the last candidate finds the padding
    key = jnp.concatenate([key, jnp.full((window,), n_loc, jnp.int32)])
    pos = jnp.concatenate([pos, jnp.full((window,), total, jnp.int32)])
    lane = jnp.arange(window, dtype=jnp.int32)

    def step(state):
        p, out = state
        first = jax.lax.dynamic_index_in_dim(key, p, keepdims=False)
        # aligned down by at most a block, so the block holds ``first``
        start = jnp.minimum(first // align * align, n_loc - block)
        blk = jax.lax.dynamic_slice_in_dim(xs, start, block, 0)
        wk = jax.lax.dynamic_slice_in_dim(key, p, window)
        wp = jax.lax.dynamic_slice_in_dim(pos, p, window)
        # sorted, so the candidates this block holds are a prefix
        take = (wk < start + block) & (lane < held - p)
        got = blk[jnp.clip(wk - start, 0, block - 1)]
        got = jnp.pad(got, ((0, 0), (0, width - dim)))
        out = out.at[jnp.where(take, wp, total)].set(got, mode="drop")
        return p + jnp.sum(take, dtype=jnp.int32), out

    # rows padded to whole lanes: a row-major buffer then costs what a
    # column-major one would, and the compiler keeps one layout of it
    width = -(-dim // 128) * 128
    _, out = jax.lax.while_loop(
        lambda state: state[0] < held, step,
        (jnp.int32(0), jnp.zeros((total, width), xs.dtype)))
    return out


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _score_row_sharded(queries, dataset, candidates, k: int,
                       metric_val: int, mesh, axis: str):
    """:func:`_score_fetched` over rows sharded by row over ``axis``:
    each chip scores the candidates it holds (ids clamped into [0, n)
    as the one-device gather clamps them), 0 elsewhere; a ``psum``
    joins the scores, then invalid ids sink and the top-k is taken.
    The device stage is named ``sharded.rerank``."""
    n = dataset.shape[0]
    metric = DistanceType(metric_val)

    def local(q, xs, cand):
        with jax.named_scope("sharded.rerank"):
            compute = jnp.promote_types(q.dtype, jnp.float32)
            q = q.astype(compute)
            own = (jnp.clip(cand, 0, n - 1)
                   - jax.lax.axis_index(axis) * xs.shape[0])
            mine = (own >= 0) & (own < xs.shape[0])
            vecs = _gather_held_rows(xs, own, mine)
            m, c = cand.shape
            # scored a block of queries at a time: the score's operand
            # layout differs from the gather's, and a copy of every
            # gathered row at once would double them
            mb = next(b for b in range(max(min(m, _SCORE_ROWS // c), 1),
                                       0, -1) if m % b == 0)

            def score(b):
                qb = jax.lax.dynamic_slice_in_dim(q, b * mb, mb)
                vb = jax.lax.dynamic_slice_in_dim(vecs, b * mb * c, mb * c)
                vb = vb[:, :xs.shape[1]].reshape(mb, c, -1)
                return _exact_scores(qb, vb.astype(compute), metric)

            d = jax.lax.map(score, jnp.arange(m // mb))
            d = jax.lax.psum(jnp.where(mine, d.reshape(m, c), 0.0), axis)
            return _select_scored(d, cand, k, metric, compute)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(axis, None), P()),
        out_specs=(P(), P()), check_vma=False,
    )(queries, dataset, candidates)


@jax.jit
def _promote_scatter(hot_block, miss_block, src_pos, dst_slot):
    """Build the successor hot block: promoted rows scattered in FROM
    the already uploaded miss block (device-to-device — promotion
    costs zero link bytes). Padding entries carry ``dst_slot ==
    capacity`` and drop at the out-of-bounds scatter. Deliberately NOT
    donated: an in-flight reader scores against its own snapshot of
    the old block, which must stay readable after the commit installs
    this result — promotions pay one block copy for that (bounded by
    the hot budget, and steady state promotes nothing)."""
    rows = miss_block[jnp.clip(src_pos, 0, miss_block.shape[0] - 1)]
    return hot_block.at[dst_slot].set(rows, mode="drop")


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------


def as_source(dataset, hot_rows: Optional[int] = None) -> RerankSource:
    """Resolve a ``dataset=`` value to a :class:`RerankSource`:

    * a source instance passes through (the persistent-hot-cache path);
    * a device ``jax.Array`` keeps the full-upload
      :class:`DeviceSource` fast path (back-compat: an
      already-uploaded dataset is never re-tiered), or, when its rows
      are sharded over a mesh axis, :class:`RowShardedSource`;
    * a host ``np.ndarray`` / ``np.memmap`` becomes a
      :class:`HostArraySource` — per-call, so the hot cache defaults
      OFF here (``hot_rows=0``); construct the source yourself to keep
      residency across calls;
    * anything else (lists, tuples) uploads like before.
    """
    if isinstance(dataset, RerankSource):
        return dataset
    if isinstance(dataset, jax.Array):
        split = _row_split(dataset)
        if split is not None:
            return RowShardedSource(dataset, *split)
        return DeviceSource(dataset)
    if isinstance(dataset, np.ndarray):
        return HostArraySource(
            dataset, hot_rows=0 if hot_rows is None else hot_rows)
    return DeviceSource(jnp.asarray(dataset))


def _row_split(dataset) -> Optional[tuple]:
    """(mesh, axis) when ``dataset`` is a 2-d array whose rows alone are
    split, evenly, over one axis of a mesh of more than one device."""
    sh = dataset.sharding
    if not isinstance(sh, NamedSharding) or dataset.ndim != 2:
        return None
    spec = tuple(sh.spec) + (None, None)
    axis = spec[0]
    if isinstance(axis, tuple):
        axis = axis[0] if len(axis) == 1 else None
    if axis is None or spec[1] is not None:
        return None
    parts = sh.mesh.shape[axis]
    if parts < 2 or dataset.shape[0] % parts:
        return None
    return sh.mesh, axis


def memmap_source(path: str, dim: Optional[int] = None, dtype=None,
                  hot_rows: Optional[int] = None,
                  offset: int = 0) -> HostArraySource:
    """Open a raw row-major vector file as a memory-mapped
    :class:`HostArraySource`. With ``dim=None`` the file is read as
    big-ann ``*.bin``/``.fbin`` (8-byte ``[n, d]`` uint32 header, f32
    rows unless ``dtype`` says otherwise) — the same layout
    :class:`~raft_tpu.utils.batch.FileBatchLoadIterator` streams."""
    if dim is None:
        header = np.fromfile(path, dtype=np.uint32, count=2)
        n, dim = int(header[0]), int(header[1])
        offset = 8
        dtype = np.float32 if dtype is None else dtype
        mm = np.memmap(path, dtype=np.dtype(dtype), mode="r",
                       offset=offset, shape=(n, dim))
    else:
        dtype = np.float32 if dtype is None else dtype
        mm = np.memmap(path, dtype=np.dtype(dtype), mode="r",
                       offset=offset)
        n = mm.size // int(dim)
        mm = mm[: n * int(dim)].reshape(n, int(dim))
    return HostArraySource(mm, hot_rows=hot_rows)


__all__ = [
    "DEFAULT_HOT_ROWS", "DeviceSource", "FetchInfo", "HOT_ROWS_BUDGET",
    "HostArraySource", "PROMOTE_BATCH", "RUNG_FLOOR", "RerankSource",
    "RowShardedSource", "as_source", "memmap_source",
]
