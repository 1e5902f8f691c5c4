"""Microbenchmark harness behind the dispatch tables.

Times the competing implementations behind each hot-path dispatch over a
grid of static shape keys and records the winners into a
``DispatchTable`` (the measurement half of the reference's learned
``select_k`` heuristic, matrix/detail/select_k-inl.cuh:51-79 /
cpp/scripts/heuristics/select_k). Ops:

``select_k`` / ``merge_topk``
    ``lax.top_k`` (hardware sort) vs the compacting tournament network
    vs the hierarchical tile/merge-tree rung, at selection shapes
    (large n, moderate k) and merge shapes (n = n_probes x kl candidate
    pools) respectively. Cheap — also run inline by
    ``RAFT_TPU_TUNING=measure``.
``ivf_scan``
    end-to-end IVF-Flat search with the fused Pallas list-scan kernel vs
    the XLA bucketized scan (key: cap, k, approx).
``ivf_scan_extract``
    the kernel's in-kernel extraction arms raced head-to-head (exact
    k-pass sweep vs lane-binned vs R-deep binned vs the unextracted
    fold, charged with its deferred merge) by forcing each via
    ``fused_list_scan_topk(extract=...)``; TPU-only by default (the
    kernel's compile target).
``fused_topk_tile``
    brute-force backends end-to-end: XLA lax.scan tiling vs the fused
    Pallas distance+partial-top-k kernel per (variant, row-tile) —
    winners are brute_force impl strings, so tile geometry is adopted
    from measurement with no code change.
``pq_scan``
    end-to-end IVF-PQ search per cache kind — i8 decoded residuals
    (1 MXU pass), packed-i4 raw residuals (1 pass, in-kernel nibble
    decode), pq4 transposed codes (16-pass one-hot contraction), and
    the rabitq sign-bit rung TIMED THROUGH ITS RERANK PIPELINE
    (``search_refined``, codes rerank). The race is matched-recall:
    arms that cannot clear the finest classic rung's recall − 0.01 are
    filtered out before any timing (the ``binned_loss_fits``
    eligibility pattern). The recall-band survivors compete for
    ``cache_dtype="auto"``'s sub-i8-budget slot (``_cache_kind_for``
    keeps the finest rung whenever it fits); i8's time is captured for
    the record.

``graph_join``
    nn-descent local-join backends raced at one join-block shape: the
    XLA einsum + keep-min merge vs the fused Pallas kernel per node
    tile (``pallas:8`` … ``pallas:32``, ops/graph_join.py) — the
    winner string carries the tile, so a live-chip capture adopts
    node-tile geometry with no code change (ISSUE 15).
``beam_step_tile``
    the fused CAGRA beam-step kernel's query-tile (lane) geometry
    raced over ``tuning.BEAM_STEP_TILES`` on real packed inline rows;
    TPU-only by default (the kernel's compile target), winner strings
    ``pallas:<g>`` consumed by ``cagra._resolve_beam_tile``.
``serve_service``
    end-to-end ``ivf_flat.search`` medians per (bucket, probe-rung)
    shape — not a dispatch race but a TIMING table: the serve layer's
    deadline machinery (batcher slack test, shed/downshift estimates)
    reads these through ``serve.adaptive.service_estimate_ms`` instead
    of guessing (ISSUE 14, docs/serving.md §13).

Index-building ops (ivf_scan, pq_scan, serve_service) are only
captured by ``scripts/capture_dispatch_tables.py``; measuring them at
dispatch time would build an index inside a search call.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

_DEF_REPS = 5


def _median_ms(fn, reps: int = _DEF_REPS) -> float:
    """Median wall-clock ms of ``fn()`` after one warmup (compile) call.
    ``fn`` must return jax arrays; completion is forced per rep."""
    import jax

    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(ts))


def _rand(shape, dtype, seed=0):
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return jax.block_until_ready(x.astype(dtype))


# ---------------------------------------------------------------------------
# select_k / merge_topk: top_k vs tournament
# ---------------------------------------------------------------------------


def select_candidates(key: Dict) -> List[str]:
    """Eligible select_k implementations at ``key`` (mirrors the
    constraints in matrix/select_k.py): the tournament is float-only
    and needs k <= n; the hierarchical rung (every dtype) needs at
    least 4 local tiles' worth of data to be a tree at all."""
    cands = ["top_k"]
    dtype = str(key.get("dtype", "float32"))
    if dtype.startswith(("float", "bfloat")):
        cands.append("tournament")
    n, k = int(key.get("n", 0)), int(key.get("k", 1))
    K = 1 << (max(k, 1) - 1).bit_length()
    if n >= 4 * K:
        cands.append("hierarchical")
    return cands


def bench_select(key: Dict, candidates: Optional[List[str]] = None,
                 reps: int = _DEF_REPS) -> Dict[str, float]:
    """Time the select_k implementations at ``key``
    ({n, k, batch, dtype}); returns {candidate: median_ms}."""
    import jax.numpy as jnp

    from raft_tpu.matrix.select_k import (
        _hierarchical_topk,
        _select_k,
        _tournament_topk,
    )

    n = int(key["n"])
    k = int(key["k"])
    batch = int(key.get("batch", 64))
    dtype = jnp.dtype(key.get("dtype", "float32"))
    if candidates is None:
        candidates = select_candidates(key)
    x = _rand((batch, n), dtype)
    times: Dict[str, float] = {}
    if "top_k" in candidates:
        times["top_k"] = _median_ms(lambda: _select_k(x, k, True), reps)
    if "tournament" in candidates:
        times["tournament"] = _median_ms(
            lambda: _tournament_topk(x, k, True), reps
        )
    if "hierarchical" in candidates:
        times["hierarchical"] = _median_ms(
            lambda: _hierarchical_topk(x, k, True), reps
        )
    return times


# ---------------------------------------------------------------------------
# ivf_scan: fused Pallas kernel vs XLA bucketized scan
# ---------------------------------------------------------------------------

# shared small-but-representative search workload for the end-to-end ops
_SCAN_N = 20_000
_SCAN_D = 64
_SCAN_M = 512


def _scan_dataset(n=_SCAN_N, d=_SCAN_D, m=_SCAN_M):
    rng = np.random.default_rng(7)
    data = rng.standard_normal((n, d)).astype(np.float32)
    queries = rng.standard_normal((m, d)).astype(np.float32)
    return data, queries


def bench_ivf_scan(key: Dict, candidates: List[str],
                   reps: int = _DEF_REPS):
    """Time end-to-end IVF-Flat search per scan impl at ``key``
    ({k, approx, ...}). Candidates: "xla" | "pallas" |
    "pallas_interpret" (CPU-debug kernel — orders of magnitude slower
    than compiled, only meaningful relative to itself). Returns
    (times, key) with the key enriched by the built index's list
    capacity — the field ``_resolve_scan_impl`` looks up by."""
    from raft_tpu.neighbors import ivf_flat

    key = dict(key)
    k = int(key.get("k", 10))
    n_lists = int(key.get("n_lists", 64))
    n_probes = int(key.get("n_probes", 8))
    approx = bool(key.get("approx", True))
    data, queries = _scan_dataset(n=int(key.get("n", _SCAN_N)))
    index = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=n_lists, kmeans_n_iters=4), data
    )
    key["cap"] = int(index.storage.shape[1])
    times: Dict[str, float] = {}
    for impl in candidates:
        sp = ivf_flat.SearchParams(
            n_probes=n_probes, scan_impl=impl,
            local_recall_target=0.95 if approx else 1.0,
        )
        times[impl] = _median_ms(
            lambda sp=sp: ivf_flat.search(sp, index, queries, k), reps
        )
    return times, key


def bench_scan_extract(key: Dict, candidates: Optional[List[str]] = None,
                       reps: int = _DEF_REPS,
                       interpret: bool = False) -> Dict[str, float]:
    """Time the fused kernel's in-kernel extraction variants directly
    (exact k-pass sweep vs lane-binned vs R-deep binned) by forcing each
    arm through ``fused_list_scan_topk(extract=...)`` on a synthetic
    list-block workload. ``interpret`` runs the kernel in interpret mode
    (CPU debug — numbers only meaningful relative to each other)."""
    import jax.numpy as jnp

    from raft_tpu.ops import ivf_scan

    k = int(key.get("k", 10))
    cap = int(key.get("cap", 512))
    G = int(key.get("g", 64))
    C = int(key.get("n_lists", 8))
    d = int(key.get("d", 64))
    nb = int(key.get("nb", 16))
    if candidates is None:
        from raft_tpu.ops.ivf_scan import binned_loss_fits

        # race only arms a DEFAULT-target serve call can actually pick:
        # the table key carries no recall dimension, so a winner that
        # is ineligible at serve time would be skipped wholesale by
        # DispatchTable.lookup (it never consults the runner-up) and
        # the chip time racing it wasted (review fix, r6)
        candidates = ["exact"]
        if cap % 128 == 0 and cap > 128:
            if k <= 64 and binned_loss_fits(k):
                candidates.append("binned")
            if k <= 256:
                candidates.append("binned_deep")
                candidates.append("fold")
    storage = _rand((C, cap, d), jnp.float32, seed=1)
    qv = _rand((nb, G, d), jnp.bfloat16, seed=2)
    import jax

    indices = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32)[None],
                               (C, cap))
    sizes = jnp.full((C,), cap, jnp.int32)
    buckets = (jnp.arange(nb, dtype=jnp.int32) % C)
    qaux = jnp.sum(qv.astype(jnp.float32) ** 2, axis=2)
    norms = jnp.sum(storage.astype(jnp.float32) ** 2, axis=2)
    jax.block_until_ready((indices, qaux, norms))
    times: Dict[str, float] = {}
    n_probes = int(key.get("n_probes", 8))

    def run(arm):
        import jax.numpy as jnp

        from raft_tpu.neighbors.common import merge_topk

        out_d, out_i = ivf_scan.fused_list_scan_topk(
            storage, indices, sizes, buckets, qv, qaux, norms,
            None, k=k, metric_kind=ivf_scan.L2,
            approx=arm != "exact", interpret=interpret,
            # the race measures TIME; recall-fit filtering happens at
            # dispatch (choose() intersects table winners with the
            # caller's eligible set), so keep every arm forceable here
            recall_target=0.0,
            extract=arm,
        )
        # charge EVERY arm its downstream cross-probe merge at the real
        # pool width (n_probes x candidate-width): fold's whole trade is
        # a wider merge for zero extraction passes, so the race is only
        # end-to-end honest when both sides pay their merge
        kc = int(out_d.shape[2])
        pool_d = jnp.tile(out_d.reshape(-1, kc), (1, n_probes))
        pool_i = jnp.tile(out_i.reshape(-1, kc), (1, n_probes))
        return merge_topk(pool_d, pool_i, k, True)

    for arm in candidates:
        times[arm] = _median_ms(lambda arm=arm: run(arm), reps)
    return times


def bench_fused_topk(key: Dict, candidates: Optional[List[str]] = None,
                     reps: int = _DEF_REPS,
                     interpret: bool = False) -> Dict[str, float]:
    """Race the brute-force scan backends at ``key`` ({m, n, d, k}):
    the XLA lax.scan tiling ("scan") vs the fused Pallas
    distance+partial-top-k kernel per (variant, row-tile) — candidate
    names are brute_force's impl strings ("fused_exact:1024",
    "fused_fold:2048", ...), so the captured winner IS the dispatch
    answer and a live-chip capture adopts new tile geometry with no
    code change. ``interpret`` appends ":interpret" to the fused
    candidates (CPU debug-only numbers)."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import brute_force

    m = int(key.get("m", 512))
    n = int(key.get("n", _SCAN_N))
    d = int(key.get("d", _SCAN_D))
    k = int(key.get("k", 10))
    if candidates is None:
        from raft_tpu.tuning import fused_topk_candidate_impls

        # race the exact same enumeration brute_force dispatches over
        # (microbench charges fold with its deferred merge either way)
        candidates = ["scan"] + fused_topk_candidate_impls(k, approx_ok=True)
    data, queries = _scan_dataset(n=n, d=d, m=m)
    index = brute_force.build(data, "sqeuclidean")
    q = jnp.asarray(queries)
    times: Dict[str, float] = {}
    for impl in candidates:
        arm = impl
        if interpret and impl.startswith("fused"):
            arm = impl + ":interpret"
        times[impl] = _median_ms(
            lambda arm=arm: brute_force.search(index, q, k, impl=arm),
            reps)
    return times


def bench_graph_join(key: Dict, candidates: Optional[List[str]] = None,
                     reps: int = _DEF_REPS,
                     interpret: bool = False) -> Dict[str, float]:
    """Race the nn-descent local-join backends at ``key``
    ({rows, K, S, d}): the XLA einsum + keep-min merge ("xla") vs the
    fused Pallas kernel per node tile ("pallas:8" ... "pallas:32",
    ops/graph_join.py) — candidate names are nn_descent's join impl
    strings, so the captured winner IS the dispatch answer and a
    live-chip capture adopts node-tile geometry with no code change.
    The workload is one join block at the real shape (current lists +
    sampled candidates + the reverse slab), gathers included — both
    arms pay the candidate-vector gather, so the race isolates the
    score+merge transients the kernel removes. ``interpret`` runs the
    kernel in interpret mode (CPU debug-only numbers)."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors.nn_descent import _join_block, _make_rev

    rows = int(key.get("rows", 4096))
    K = int(key.get("K", 64))
    S = int(key.get("S", 128))
    d = int(key.get("d", 64))
    n = 2 * rows            # join block over half the node range
    if candidates is None:
        from raft_tpu.tuning import GRAPH_JOIN_TILES

        candidates = ["xla"] + [f"pallas:{t}" for t in GRAPH_JOIN_TILES]
    rng = np.random.default_rng(23)
    data = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    norms = jnp.sum(data * data, axis=1)
    graph_i = jnp.asarray(
        rng.integers(0, n, (n, K)).astype(np.int32))
    graph_d = jnp.asarray(
        rng.standard_normal((n, K)).astype(np.float32) ** 2)
    rev_i = jax.block_until_ready(_make_rev(graph_i))
    pool = jnp.concatenate([graph_i, rev_i], axis=1)
    cols = jnp.asarray(rng.integers(0, 2 * K * K, S).astype(np.int32))
    start0 = jnp.int32(0)
    times: Dict[str, float] = {}
    for impl in candidates:
        kind, _, tile = impl.partition(":")
        if kind.startswith("pallas") and interpret:
            kind = "pallas_interpret"
        times[impl] = _median_ms(
            lambda kind=kind, tile=tile: _join_block(
                data, norms, graph_d, graph_i, pool, rev_i, cols,
                start0, rows=rows, ip=False, impl=kind,
                tile_b=int(tile) if tile else 0), reps)
    return times


def bench_beam_step(key: Dict, candidates: Optional[List[str]] = None,
                    reps: int = _DEF_REPS,
                    interpret: bool = False) -> Dict[str, float]:
    """Race the fused beam-step kernel's query-tile geometry at ``key``
    ({m, itopk, width, deg, d}) — op key ``beam_step_tile``, candidate
    names ``pallas:<g>`` over ``tuning.BEAM_STEP_TILES`` (the lane tile
    cagra._resolve_beam_tile dispatches): one packed-scoring
    beam_merge_step call per tile on real inline rows, so the captured
    winner adopts tile geometry with no code change."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors import cagra
    from raft_tpu.ops.beam_step import beam_merge_step, beam_step_vmem_bytes

    m = int(key.get("m", 1024))
    L = int(key.get("itopk", 64))
    width = int(key.get("width", 4))
    deg = int(key.get("deg", 32))
    d = int(key.get("d", 64))
    n = 20_000
    if candidates is None:
        from raft_tpu.tuning import BEAM_STEP_TILES

        candidates = [
            f"pallas:{g}" for g in BEAM_STEP_TILES
            if beam_step_vmem_bytes(g, L, width, deg, d) <= 8 << 20
        ]
    rng = np.random.default_rng(29)
    x = rng.standard_normal((n, d)).astype(np.float32)
    graph = rng.integers(0, n, (n, deg)).astype(np.int32)
    idx = cagra.from_graph(x, graph, "sqeuclidean")
    if idx.nbr_pack is None:
        return {}
    q = rng.standard_normal((m, d)).astype(np.float32)
    qs = jnp.asarray(q * 2.0 * idx.code_scale, jnp.bfloat16)
    qperm = jnp.transpose(qs.reshape(m, d // 4, 4), (0, 2, 1))
    qrep = jnp.tile(qperm, (1, 1, deg))
    parents = jnp.asarray(rng.integers(0, n, (width, m)).astype(np.int32))
    pack = idx.nbr_pack[jnp.maximum(parents.T, 0)]
    bd = jnp.asarray(np.sort(
        rng.standard_normal((L, m)).astype(np.float32) ** 2, axis=0))
    bi = jnp.asarray(rng.integers(0, n, (L, m)).astype(np.int32))
    be = jnp.zeros((L, m), jnp.int32)
    jax.block_until_ready((qrep, pack, bd))
    times: Dict[str, float] = {}
    for impl in candidates:
        try:
            g = int(impl.split(":", 1)[1])
        except (IndexError, ValueError):
            continue
        times[impl] = _median_ms(
            lambda g=g: beam_merge_step(
                bd, bi, be, qrep=qrep, pack=pack, parents=parents,
                deg=deg, d=d, width=width, g=g,
                interpret=interpret), reps)
    return times


def _pq_oracle_ids(data, queries, k: int):
    """Exact L2 top-k ids for the shared pq_scan workload (the recall
    judge for the matched-recall race below)."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(data)
    q = jnp.asarray(queries)
    d2 = (jnp.sum(q * q, 1)[:, None] + jnp.sum(x * x, 1)[None, :]
          - 2.0 * q @ x.T)
    _, ids = jax.lax.top_k(-d2, k)
    return np.asarray(ids)


def _pq_recall(ids, want) -> float:
    """Set-intersection recall@k — THE one implementation
    (bench.harness.compute_recall), so the dispatch race's recall gate
    can never drift from bench's reported recall."""
    from raft_tpu.bench.harness import compute_recall

    return float(compute_recall(np.asarray(ids), np.asarray(want)))


def rabitq_matched_refine_ratio(recalls: Dict[int, float],
                                target: float) -> Optional[int]:
    """Smallest refine_ratio whose measured pipeline recall clears
    ``target`` — or None when no ratio does (the arm is then filtered
    out of the race entirely). The loss-aware-eligibility pattern from
    ``ivf_scan.binned_loss_fits``: an arm that cannot hit the caller's
    recall band must be excluded BEFORE the race, because the table key
    carries no recall dimension and ``DispatchTable.lookup`` never
    consults the runner-up."""
    for rr in sorted(recalls):
        if recalls[rr] >= target:
            return rr
    return None


# refine ratios the rabitq arm may race at (the acceptance band caps
# the pipeline at <= 4; larger ratios would change the op's semantics)
_RABITQ_RATIOS = (2, 4)


def bench_pq_scan(key: Dict, candidates: List[str],
                  reps: int = _DEF_REPS):
    """Time end-to-end IVF-PQ search per cache kind at ``key``. The
    build uses pq_bits=4 so the classic kinds (i8/i4/pq4) are feasible
    on one quantizer config; search runs with lut_dtype="auto" (cache
    scan — the path the choice governs). Returns (times, key) with the
    key enriched by the built geometry (cap/rot/pq_bits — the fields
    ``_cache_kind_for`` looks up by).

    The race is MATCHED-RECALL (ISSUE 11): each arm's recall vs the
    exact oracle is measured first; the target is the finest SUB-i8
    classic rung's recall minus 0.01 (the acceptance band — the entry
    decides the sub-i8 auto slot, so i8 must not set the bar), and an
    arm that cannot hit it is filtered out BEFORE any timing — the
    ``binned_loss_fits`` eligibility pattern, because a table winner is
    never re-filtered by recall at dispatch time. The "rabitq" arm is
    timed through its WHOLE pipeline (``search_refined`` at the
    smallest refine_ratio <= 4 that clears the target; codes rerank),
    so its time is end-to-end honest against the single-stage kinds.
    Sub-target recalls are recorded in the key for the table record."""
    from raft_tpu.neighbors import ivf_pq

    key = dict(key)
    k = int(key.get("k", 10))
    n_lists = int(key.get("n_lists", 64))
    n_probes = int(key.get("n_probes", 8))
    pq_dim = int(key.get("pq_dim", 32))
    data, queries = _scan_dataset(n=int(key.get("n", _SCAN_N)))
    want = _pq_oracle_ids(data, queries, k)
    built: Dict[str, tuple] = {}      # kind -> (index, search thunk)
    recalls: Dict[str, float] = {}
    for kind in ("i8", "i4", "pq4", "rabitq"):
        if kind not in candidates:
            continue
        params = ivf_pq.IndexParams(
            n_lists=n_lists, pq_bits=4, pq_dim=pq_dim, kmeans_n_iters=4,
            cache_decoded=True, cache_dtype=kind,
        )
        index = ivf_pq.build(params, data)
        if index.cache_kind != kind:
            continue  # budget-gated out: not a competitor here
        key.setdefault("cap", int(index.indices.shape[1]))
        key.setdefault("rot", int(index.rot_dim))
        key.setdefault("pq_bits", 4)
        sp = ivf_pq.SearchParams(n_probes=n_probes)
        if kind == "rabitq":
            rr_rec = {}
            for rr in _RABITQ_RATIOS:
                _, ids = ivf_pq.search_refined(sp, index, queries, k,
                                               refine_ratio=rr)
                rr_rec[rr] = _pq_recall(ids, want)
            built[kind] = (index, sp, rr_rec)
        else:
            _, ids = ivf_pq.search(sp, index, queries, k)
            recalls[kind] = _pq_recall(ids, want)
            built[kind] = (index, sp, None)
    # matched-recall target: the finest SUB-i8 classic rung present,
    # minus the acceptance band's 0.01. NOT i8's recall — the table
    # entry decides the sub-i8 "auto" slot (dispatch only consults it
    # when i8 misses the budget, with sub-i8 candidates), so a target
    # set by i8 would filter every actual competitor and leave a
    # winner=i8 entry the lookup can never use (review fix, r10).
    # i8 is still timed below, for the record.
    classic = [recalls[kk] for kk in ("i4", "pq4") if kk in recalls]
    target = (max(classic) - 0.01) if classic else 0.0
    key["recall_target"] = round(target, 4)
    times: Dict[str, float] = {}
    for kind, (index, sp, rr_rec) in built.items():
        if kind == "rabitq":
            rr = rabitq_matched_refine_ratio(rr_rec, target)
            key["rabitq_recall"] = round(max(rr_rec.values()), 4)
            if rr is None:
                continue              # can't hit the band: not raced
            key["rabitq_refine_ratio"] = int(rr)
            times[kind] = _median_ms(
                lambda sp=sp, ix=index, rr=rr: ivf_pq.search_refined(
                    sp, ix, queries, k, refine_ratio=rr),
                reps,
            )
        else:
            if recalls.get(kind, 0.0) < target:
                continue              # below the band: not raced
            times[kind] = _median_ms(
                lambda sp=sp, ix=index: ivf_pq.search(sp, ix, queries, k),
                reps,
            )
    return times, key


# ---------------------------------------------------------------------------
# inline measurement (RAFT_TPU_TUNING=measure) + capture grids
# ---------------------------------------------------------------------------


def measure_op(op: str, key: Dict,
               candidates: List[str]) -> Dict[str, float]:
    """Measure one (op, key) synchronously — only the cheap selection
    ops; the index-building ops raise (capture those with
    scripts/capture_dispatch_tables.py)."""
    if op in ("select_k", "merge_topk"):
        return bench_select(key, candidates, reps=3)
    raise ValueError(
        f"op {op!r} cannot be measured inline; run "
        "scripts/capture_dispatch_tables.py"
    )


def select_grid(quick: bool = True) -> List[Dict]:
    """(n, k, batch) grid for the select_k op — spans the projected
    crossover region (k ~ 256, n >= 8K)."""
    ns = [8_192, 65_536] if quick else [8_192, 65_536, 262_144]
    ks = [64, 256, 1024] if quick else [64, 256, 1024, 4096]
    batches = [64] if quick else [16, 64, 256]
    grid = []
    for n in ns:
        for k in ks:
            if k * 4 > n:
                continue
            for b in batches:
                grid.append({"n": n, "k": k, "batch": b,
                             "dtype": "float32"})
    return grid


def merge_grid(quick: bool = True) -> List[Dict]:
    """(c, k, batch) grid for merge_topk — candidate pools are
    n_probes x kl wide and batch is the query count, so the regime is
    wider-batch / narrower-n than select_k's."""
    grid = []
    shapes = ([(1280, 10), (8192, 64), (16384, 512)] if quick else
              [(1280, 10), (2560, 32), (8192, 64), (8192, 512),
               (16384, 512), (32768, 1024)])
    for c, k in shapes:
        for b in ([256] if quick else [64, 256, 1024]):
            grid.append({"n": c, "k": k, "batch": b, "dtype": "float32"})
    return grid


def scan_grid(quick: bool = True) -> List[Dict]:
    del quick
    # the k=130 exact row covers the known pallas weak spot (the k-pass
    # unrolled extraction measured ~7x slower than XLA at k=130, r4
    # v5e) so the
    # table's interpolation radius cannot route mid-k exact searches
    # onto an unmeasured arm
    return [{"n": _SCAN_N, "k": 10, "approx": True, "n_lists": 64,
             "n_probes": 8},
            {"n": _SCAN_N, "k": 64, "approx": False, "n_lists": 64,
             "n_probes": 8},
            {"n": _SCAN_N, "k": 130, "approx": False, "n_lists": 64,
             "n_probes": 8}]


def pq_grid(quick: bool = True) -> List[Dict]:
    del quick
    return [{"n": _SCAN_N, "k": 10, "pq_dim": 32, "n_lists": 64,
             "n_probes": 8}]


def extract_grid(quick: bool = True) -> List[Dict]:
    ks = [10, 64, 130] if quick else [10, 32, 64, 130, 256]
    return [{"cap": 512, "k": k, "g": 64, "n_lists": 8, "d": 64,
             "nb": 16} for k in ks]


def graph_join_grid(quick: bool = True) -> List[Dict]:
    """(rows, K, S, d) grid for the graph_join race — the nn-descent
    block shapes CAGRA builds dispatch at (K = intermediate degree,
    S = n_candidates), plus the small-K regime where XLA's batched
    einsum can win back."""
    if quick:
        return [{"rows": 4096, "K": 64, "S": 128, "d": 64},
                {"rows": 4096, "K": 96, "S": 128, "d": 128}]
    return [{"rows": r, "K": K, "S": S, "d": d}
            for r in (4096, 16384)
            for (K, S) in ((32, 64), (64, 128), (96, 128))
            for d in (64, 128)]


def beam_step_grid(quick: bool = True) -> List[Dict]:
    """(m, itopk, width, deg, d) grid for the beam_step_tile race —
    the serve bucket ladder's batch range at the CAGRA search shapes."""
    if quick:
        return [{"m": 1024, "itopk": 64, "width": 4, "deg": 32, "d": 64}]
    return [{"m": m, "itopk": L, "width": 4, "deg": 32, "d": d}
            for m in (256, 1024, 10240)
            for L in (64, 128)
            for d in (64, 128)]


def fused_topk_grid(quick: bool = True) -> List[Dict]:
    """(m, n, d, k) grid for the brute-force backend race — the
    north-star bruteforce_sift10k shape's neighborhood plus the large-k
    regime where the exact arm ages out."""
    if quick:
        return [{"m": 512, "n": 20_000, "d": 64, "k": 10},
                {"m": 512, "n": 20_000, "d": 64, "k": 100}]
    return [{"m": m, "n": n, "d": d, "k": k}
            for n in (20_000, 100_000)
            for (m, d) in ((512, 64), (2048, 128))
            for k in (10, 100, 256)]


def serve_grid(quick: bool = True) -> List[Dict]:
    """(bucket, rung) grid for the serve_service capture — the bucket
    ladder the micro-batcher dispatches at crossed with the adaptive
    probe-rung ladder (docs/serving.md §13). The medians feed the
    batcher's deadline slack test and the engine's shed/downshift
    estimates through ``serve.adaptive.service_estimate_ms``."""
    buckets = [8, 32, 128] if quick else [1, 8, 32, 128, 256]
    rungs = [1, 4, 16, 64] if quick else [1, 2, 4, 8, 16, 32, 64]
    return [{"bucket": b, "rung": r} for b in buckets for r in rungs]


def bench_serve_service(keys: List[Dict], reps: int = _DEF_REPS,
                        n: int = 20_000, dim: int = 64,
                        n_lists: int = 64):
    """Median end-to-end ``ivf_flat.search`` service time per
    (bucket, rung) shape over ONE shared index — the per-rung
    service-time table the serve deadline machinery reads instead of a
    hardcoded guess. Yields (key, {"search": median_ms})."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_flat

    rng = np.random.default_rng(17)
    x = rng.standard_normal((n, dim)).astype(np.float32)
    index = ivf_flat.build(
        ivf_flat.IndexParams(n_lists=n_lists, kmeans_n_iters=5), x)
    for key in keys:
        bucket = int(key["bucket"])
        rung = int(min(key["rung"], n_lists))
        q = jnp.asarray(rng.standard_normal(
            (bucket, dim)).astype(np.float32))
        sp = ivf_flat.SearchParams(n_probes=rung, compute_dtype="f32",
                                   local_recall_target=1.0)

        def run(q=q, sp=sp):
            return ivf_flat.search(sp, index, q, 10)

        yield dict(key, rung=rung), {"search": _median_ms(run, reps)}


def bench_pipeline_depth(reps: int = 3, n_items: int = 24,
                         work_ms: float = 2.0) -> Dict[str, float]:
    """Race the graft-flow prefetch depths
    (:data:`raft_tpu.core.pipeline.PIPELINE_DEPTH_CANDIDATES`) on a
    balanced synthetic read/compute stream — equal sleep on the
    producer (the host-tier read) and the consumer (the scoring loop),
    the regime where overlap pays the most. The winner lands in the
    table's ``pipeline_depth`` budget, which every streaming path reads
    through :func:`raft_tpu.core.pipeline.resolve_depth` when the
    caller leaves the depth defaulted."""
    from raft_tpu.core import pipeline as gf

    def run(depth: int) -> float:
        def source():
            for i in range(n_items):
                time.sleep(work_ms / 1e3)
                yield i

        t0 = time.perf_counter()
        with gf.Prefetcher(source, depth=depth,
                           path="capture.pipeline") as pf:
            for _ in pf:
                time.sleep(work_ms / 1e3)
        return (time.perf_counter() - t0) * 1e3

    return {str(depth): min(run(depth) for _ in range(max(reps, 1)))
            for depth in gf.PIPELINE_DEPTH_CANDIDATES}


def default_budgets() -> Dict[str, int]:
    """Measured-environment byte budgets. The CAGRA inline budget tracks
    the device HBM actually present (packed table + dataset + transients
    must co-reside: cap at ~40% of the per-device byte limit), falling
    back to the analytic default when the backend doesn't report one."""
    from raft_tpu.neighbors.cagra import _INLINE_BUDGET

    budget = _INLINE_BUDGET
    try:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        limit = int(stats.get("bytes_limit", 0))
        if limit > 0:
            budget = int(limit * 0.4)
    except Exception:  # noqa: BLE001  # graft-lint: allow-unclassified-swallow memory-stats probe; backends without stats fall back to the analytic budget
        pass
    return {"cagra_inline_bytes": int(budget)}


def capture(backend: Optional[str] = None, quick: bool = True,
            include_interpret: bool = False, reps: int = _DEF_REPS,
            ops: Optional[List[str]] = None, verbose: bool = True):
    """Run the full grid and return a populated DispatchTable."""
    import jax

    from raft_tpu import tuning
    from raft_tpu.tuning.table import TABLE_VERSION, DispatchTable

    backend = backend or tuning.backend_name()
    on_tpu = backend == "tpu"
    t = DispatchTable({
        "version": TABLE_VERSION,
        "backend": backend,
        "captured": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "device": str(jax.devices()[0]),
        "ops": {},
        "budgets": {},
    })

    def log(msg):
        if verbose:
            print(msg, flush=True)

    want = set(ops) if ops else {"select_k", "merge_topk", "ivf_scan",
                                 "pq_scan", "ivf_scan_extract",
                                 "fused_topk_tile", "serve_service",
                                 "graph_join", "beam_step_tile",
                                 "pipeline_depth"}
    if "select_k" in want:
        for key in select_grid(quick):
            times = bench_select(key, reps=reps)
            log(f"select_k {key} -> {t.record('select_k', key, times)} "
                f"{times}")
    if "merge_topk" in want:
        for key in merge_grid(quick):
            times = bench_select(key, select_candidates(key), reps=reps)
            log(f"merge_topk {key} -> "
                f"{t.record('merge_topk', key, times)} {times}")
    scan_cands = ["xla"] + (["pallas"] if on_tpu else
                            ["pallas_interpret"] if include_interpret
                            else [])
    if "ivf_scan" in want:
        for key in scan_grid(quick):
            times, key = bench_ivf_scan(key, scan_cands, reps=reps)
            if times:
                log(f"ivf_scan {key} -> "
                    f"{t.record('ivf_scan', key, times)} {times}")
    if "pq_scan" in want:
        for key in pq_grid(quick):
            times, key = bench_pq_scan(key, ["i8", "i4", "pq4", "rabitq"],
                                       reps=reps)
            if times:
                log(f"pq_scan {key} -> "
                    f"{t.record('pq_scan', key, times)} {times}")
    # in-kernel extraction arms: the kernel only compiles on TPU, so the
    # CPU capture records this op solely under --interpret (debug-only
    # relative numbers); a CPU table without it falls back analytically,
    # which is correct — the choice never fires off-TPU
    if "ivf_scan_extract" in want and (on_tpu or include_interpret):
        for key in extract_grid(quick):
            times = bench_scan_extract(key, reps=reps,
                                       interpret=not on_tpu)
            if times:
                log(f"ivf_scan_extract {key} -> "
                    f"{t.record('ivf_scan_extract', key, times)} {times}")
    # brute-force backend race (scan vs fused kernel per variant/tile):
    # same TPU-only rule — fused candidates need the compile target, the
    # CPU capture times only the scan arm unless --interpret
    if "fused_topk_tile" in want:
        for key in fused_topk_grid(quick):
            cands = (None if on_tpu or include_interpret else ["scan"])
            times = bench_fused_topk(key, cands, reps=reps,
                                     interpret=not on_tpu)
            if times:
                log(f"fused_topk_tile {key} -> "
                    f"{t.record('fused_topk_tile', key, times)} {times}")
    # nn-descent local-join backends: the xla arm races everywhere; the
    # fused-kernel tiles need the compile target (or --interpret for
    # CPU debug numbers) — same rule as the other kernel ops
    if "graph_join" in want:
        for key in graph_join_grid(quick):
            cands = (None if on_tpu or include_interpret
                     else ["xla"])
            times = bench_graph_join(key, cands, reps=reps,
                                     interpret=not on_tpu)
            if times:
                log(f"graph_join {key} -> "
                    f"{t.record('graph_join', key, times)} {times}")
    # beam query-tile geometry: kernel-only op, TPU (or --interpret)
    if "beam_step_tile" in want and (on_tpu or include_interpret):
        for key in beam_step_grid(quick):
            times = bench_beam_step(key, reps=reps, interpret=not on_tpu)
            if times:
                log(f"beam_step_tile {key} -> "
                    f"{t.record('beam_step_tile', key, times)} {times}")
    if "serve_service" in want:
        # single-candidate op: the entry's TIMES are the product (the
        # serve deadline machinery reads the per-(bucket, rung) median
        # through adaptive.service_estimate_ms), the winner is moot
        medians = []
        for key, times in bench_serve_service(serve_grid(quick),
                                              reps=reps):
            log(f"serve_service {key} -> {times}")
            t.record("serve_service", key, times)
            medians.append(times["search"])
        # the deadline headroom budget scales with THIS host's service
        # times (a p95-based shed gate needs slack to absorb the
        # service distribution's own tail; the median-of-medians is a
        # robust proxy that shrinks to ~nothing on a real chip)
        t.set_budget("serve_deadline_headroom_ms",
                     max(5, int(round(float(np.median(medians))))))
    if "pipeline_depth" in want:
        # graft-flow depth race (host-side timing, backend-independent):
        # the measured winner becomes the default prefetch depth for
        # every streaming path on this backend
        times = bench_pipeline_depth(reps=min(reps, 3))
        winner = t.record("pipeline_depth", {"shape": "balanced"}, times)
        log(f"pipeline_depth balanced -> {winner} {times}")
        t.set_budget("pipeline_depth", int(winner))
    for name, val in default_budgets().items():
        t.set_budget(name, val)
    return t
