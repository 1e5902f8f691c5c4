"""NN-descent: iterative all-KNN-graph construction.

TPU-native analog of the reference's nn_descent
(cpp/include/raft/neighbors/nn_descent.cuh; impl detail/nn_descent.cuh:
GnndGraph bloom-filter sampling :303-331, GNND::local_join :342-358,700,
reverse-edge kernel :499-513).

Design — pull-based local join, not a port: the reference's push-style
join (every node scatters candidate edges to *other* nodes' lists with
atomics) is hostile to XLA. The equivalent pull formulation: each node
gathers candidates from its 2-hop neighborhood over the forward+reverse
graph (the same candidate set the reference's local join generates, seen
from the receiving side), scores them, and merges them into its list
with a unique top-K — all static shapes, no atomics. Reverse edges come
from the same sort-scatter pack used by the IVF builds; the bloom-filter
"already tried" tracking is replaced by per-iteration random sampling of
the 2-hop columns, biased to near ranks, and of the reverse edges
(candidates are re-drawn, duplicates cost only a re-score). The
initial lists come from two coarse balanced k-means clusterings plus a
few random links, so the join refines local structure from the first
iteration instead of finding it by chance (:func:`_init_graph`).

Rebuilt for the memory hierarchy (the TPU-KNN treatment, ROADMAP item
7): the join is **sample-then-gather** — the sampled columns select
``(pool row, neighbor slot)`` pairs first and only those ``[n, S]``
entries are gathered, never the full two-hop tensor
``graph[pool]`` (``[n, 2K, K]`` int32, ~73 GB at n=1M / K=96, which the
original formulation materialized per iteration) — and the iteration is
**blocked over node tiles**: each dispatch covers ``graph_join_rows``
rows (a tuned budget), so peak transient memory is bounded by the block
size, not n, and the OOM degradation ladder
(``resilience.degrade.run_shrinking_blocks``) applies — a
RESOURCE_EXHAUSTED halves the block and records the survivor size
instead of killing the build.
The two formulations are algebraically identical (same columns of the
same tensor), so the rebuild is bitwise-neutral on results; measured
2026-08-04 on the CPU host (GRAPH_r15.json): 3.5x faster per iteration
at 1M rows/K=48 (361 s -> 102 s), old-path two-hop transient 18.4 GB
per iteration at that scale vs the ~3.2 GB blocked bound here.

Scoring + unique-merge dispatch under the ``graph_join`` op key
(docs/dispatch_tuning.md): the XLA path (einsum scoring +
``_merge_topk_unique``) is the fallback and the bitwise oracle; the
fused Pallas local-join kernel (``ops/graph_join.py``) keeps the
``[B, S+K]`` distance matrix and the merge transients out of HBM.

Convergence is checked against a device-side window: per-iteration
update counts stay on device and the host reads the stacked window once
every ``check_every`` iterations (one transfer per window instead of a
blocking scalar sync per iteration), trading at most ``check_every - 1``
surplus iterations — which only refine the graph — for an unblocked
dispatch pipeline.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.distance.types import DistanceType, resolve_metric

_NO_ID = jnp.int32(2147483647)  # sort-to-end sentinel for invalid ids

# analytic node-block default for the blocked join (rows per dispatch);
# the ``graph_join_rows`` budget (tuned table entry or an OOM-ladder
# survivor) overrides it
_DEF_BLOCK_ROWS = 1 << 16


@dataclasses.dataclass
class IndexParams:
    """Build params (reference nn_descent_types.hpp: graph_degree,
    intermediate_graph_degree, max_iterations, termination_threshold)."""

    graph_degree: int = 64
    intermediate_graph_degree: int = 0     # 0 -> 1.5x graph_degree
    max_iterations: int = 20
    termination_threshold: float = 0.0001
    metric: DistanceType = DistanceType.L2Expanded
    # candidates joined per node per iteration, beyond the list size K
    # (the reference's max_candidates analog): n_candidates + K in all,
    # _REV_COLS reverse edges and the rest sampled two-hop columns
    n_candidates: int = 128
    seed: int = 0
    # join backend: "auto" = dispatch table (op key "graph_join"; the
    # fused Pallas local-join kernel on TPU, XLA elsewhere);
    # "xla" | "pallas" | "pallas_interpret" force. A forced pallas
    # string may carry its node tile ("pallas:16").
    join_impl: str = "auto"
    # rows per join dispatch; 0 = the graph_join_rows budget (tuned
    # table entry / OOM-ladder survivor, analytic default 65536). Peak
    # per-iteration transient memory is proportional to this, not n.
    block_rows: int = 0
    # convergence host-sync cadence: the device-side update-count
    # window is read once every this many iterations
    check_every: int = 4

    def __post_init__(self):
        self.metric = resolve_metric(self.metric)
        if self.metric not in (
            DistanceType.L2Expanded,
            DistanceType.L2SqrtExpanded,
            DistanceType.L2Unexpanded,
            DistanceType.InnerProduct,
        ):
            raise ValueError(
                f"nn_descent supports L2/IP metrics, got {self.metric!r}"
            )


@dataclasses.dataclass
class Index:
    """All-neighbors graph (reference nn_descent index: graph [n, deg])."""

    graph: jax.Array       # [n, graph_degree] int32
    distances: jax.Array   # [n, graph_degree] f32


def _score(q_ids, cand_ids, data, norms, ip: bool):
    """dist(x[q_ids[v]], x[cand_ids[v, :]]) for every node v — batched
    matvec epilogue; min-close in both metrics (IP negated)."""
    qv = data[q_ids]                                     # [n, d]
    cv = data[cand_ids]                                  # [n, C, d]
    dots = jnp.einsum(
        "nd,ncd->nc", qv, cv,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGH,
    )
    if ip:
        return -dots
    return jnp.maximum(
        norms[q_ids][:, None] + norms[cand_ids] - 2.0 * dots, 0.0
    )


def _merge_topk_unique(cur_d, cur_i, new_d, new_i, K: int):
    """Merge candidate (dist, id) lists into each row's unique top-K.

    Dedup: stable id-sort, first copy of each id kept, repeats &
    invalids scored +inf. Duplicate copies of an id carry bitwise-equal
    distances in this pipeline (the same deterministic scoring produces
    them), so keep-first coincides with the fused kernel's keep-min
    (ops/graph_join.py) and the two paths agree bitwise; distance ties
    between DIFFERENT ids resolve to the smallest id on both (the
    id-sorted layout makes top_k's lowest-index tie-break the lowest
    id). The final selection routes through ``merge_topk`` (the
    dispatch-tabled ``merge_topk``/``select_k`` rungs,
    matrix/select_k.py) instead of a hard-coded ``lax.top_k``, so the
    hierarchical rung and any future table winner apply to graph build
    too."""
    from raft_tpu.neighbors.common import merge_topk

    all_d = jnp.concatenate([cur_d, new_d], axis=1)
    all_i = jnp.concatenate([cur_i, new_i], axis=1)
    # dedup by id: stable id-sort; repeats & invalids scored +inf
    order = jnp.argsort(jnp.where(all_i < 0, _NO_ID, all_i), axis=1,
                        stable=True)
    si = jnp.take_along_axis(all_i, order, axis=1)
    sd = jnp.take_along_axis(all_d, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((si.shape[0], 1), jnp.bool_), si[:, 1:] == si[:, :-1]],
        axis=1,
    ) | (si < 0)
    sd = jnp.where(dup, jnp.inf, sd)
    si = jnp.where(dup, -1, si)  # dup slots must not leak ids into the top-K
    return merge_topk(sd, si, K, select_min=True)


@jax.jit
def _make_rev(graph_i):
    """Reverse graph, capped at K per node (kern_make_rev_graph analog):
    pack sources by destination with the IVF sort-scatter."""
    from raft_tpu.neighbors.ivf_flat import _pack_lists

    n, K = graph_i.shape
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), K)
    dst = graph_i.reshape(-1)
    dst = jnp.where(dst >= 0, dst, n)
    _, rev_i, _ = _pack_lists(
        jnp.zeros((n * K, 1), jnp.int8), dst, src, n, K
    )
    return rev_i


@functools.partial(jax.jit, static_argnames=("rows", "ip"))
def _init_block(data, norms, init_i, start, *, rows: int, ip: bool):
    """Exactly score + dedup one node block of the random init."""
    d = data.shape[1]
    K = init_i.shape[1]
    ib = jax.lax.dynamic_slice(init_i, (start, 0), (rows, K))
    q_ids = start + jnp.arange(rows, dtype=jnp.int32)
    idist = _score(q_ids, ib, data, norms, ip)
    return _merge_topk_unique(
        idist, ib, jnp.full((rows, 1), jnp.inf), jnp.full((rows, 1), -1), K
    )


@functools.partial(
    jax.jit, static_argnames=("rows", "ip", "impl", "tile_b"),
)
def _join_block(data, norms, graph_d, graph_i, pool, rev_i, cols, rcols,
                start, *, rows: int, ip: bool, impl: str, tile_b: int):
    """One local-join dispatch over node rows [start, start+rows).

    Sample-then-gather: ``cols`` selects (pool slot, neighbor slot)
    pairs and ``rcols`` reverse-list slots, so only the sampled two-hop
    and reverse entries are gathered —
    the full [rows, 2K, K] two-hop tensor is never formed. Row
    independent (the blocked cover is bitwise what one unblocked
    dispatch would produce), which is what lets the OOM ladder split it.
    """
    n, d = data.shape
    K = graph_i.shape[1]
    S = cols.shape[0]
    gd = jax.lax.dynamic_slice(graph_d, (start, 0), (rows, K))
    gi = jax.lax.dynamic_slice(graph_i, (start, 0), (rows, K))
    pool_b = jax.lax.dynamic_slice(pool, (start, 0), (rows, 2 * K))
    rev_b = jnp.take(jax.lax.dynamic_slice(rev_i, (start, 0), (rows, K)),
                     rcols, axis=1)

    sel = cols // K                                      # [S] pool slot
    off = cols % K                                       # [S] neighbor slot
    hop_rows = jnp.take(jnp.maximum(pool_b, 0), sel, axis=1)   # [rows, S]
    cand = graph_i[hop_rows, jnp.broadcast_to(off[None, :],
                                              (rows, S))]      # [rows, S]
    src_ok = jnp.take(pool_b, sel, axis=1) >= 0
    cand = jnp.where(src_ok, cand, -1)
    cand = jnp.concatenate([cand, rev_b], axis=1)        # pool reverse too
    node_ids = start + jnp.arange(rows, dtype=jnp.int32)
    cand = jnp.where(cand == node_ids[:, None], -1, cand)  # no self loops

    cand_safe = jnp.maximum(cand, 0)
    if impl.startswith("pallas"):
        from raft_tpu.ops.graph_join import graph_local_join

        qv = jax.lax.dynamic_slice(data, (start, 0), (rows, d))
        new_d, new_i = graph_local_join(
            qv, cand, data[cand_safe], gd, gi,
            None if ip else jax.lax.dynamic_slice(norms, (start,), (rows,)),
            None if ip else norms[cand_safe],
            ip=ip, tile_b=tile_b,
            interpret=impl.startswith("pallas_interpret"),
        )
    else:
        cand_d = _score(node_ids, cand_safe, data, norms, ip)
        cand_d = jnp.where(cand < 0, jnp.inf, cand_d)
        new_d, new_i = _merge_topk_unique(gd, gi, cand_d, cand, K)
    n_updates = jnp.sum(new_i != gi, dtype=jnp.int32)
    return new_d, new_i, n_updates


# nodes per cluster of the initial graph's coarse clusterings, and the
# random (long-range) slots of each initial list
_INIT_CLUSTER_ROWS = 256
_INIT_RANDOM = 16
# reverse-edge columns joined per iteration (of K); the rest of the
# candidate budget goes to two-hop columns
_REV_COLS = 32


def _init_graph(data, n: int, K: int, key, ip: bool, seed: int):
    """Initial candidate lists: random members of the node's own cluster
    in two independent balanced k-means clusterings into ~n/256 clusters
    (a two-tree forest: a neighbor cut off by one partition's boundary is
    often inside the other's), plus ``_INIT_RANDOM`` random nodes (the
    long-range links that let two-hop joins cross clusters; without
    them the clusters stay islands). From a random init alone, recall
    per iteration fell with n: 20 iterations gave search recall@10 0.97
    at 50k rows, 0.87 at 200k (CPU), 0.61 at 1M (v5e). Small n keeps the
    all-random init."""
    from raft_tpu.cluster import kmeans_balanced

    keys = jax.random.split(key, 3)
    rnd = jax.random.randint(keys[0], (n, K), 0, n).astype(jnp.int32)
    C = n // _INIT_CLUSTER_ROWS
    if C < 2 or K <= _INIT_RANDOM:
        return rnd
    metric = DistanceType.InnerProduct if ip else DistanceType.L2Expanded
    # train on >= 32 rows per cluster (at least 64k rows)
    step = max(1, n // max(1 << 16, 32 * C))
    h = (K - _INIT_RANDOM) // 2
    parts = []
    for t, width in enumerate((h, K - _INIT_RANDOM - h)):
        params = kmeans_balanced.KMeansBalancedParams(
            n_clusters=C, n_iters=10, seed=seed + t, metric=metric)
        labels = kmeans_balanced.predict(
            params, kmeans_balanced.fit(params, data[t::step]), data)
        order = jnp.argsort(labels, stable=True).astype(jnp.int32)
        counts = jnp.bincount(labels, length=C)
        starts = (jnp.cumsum(counts) - counts)[labels]
        pick = jax.random.randint(keys[1 + t], (n, width), 0, 1 << 30)
        parts.append(order[starts[:, None] + pick % counts[labels][:, None]])
    return jnp.concatenate(parts + [rnd[:, K - _INIT_RANDOM:]], axis=1)


def _sample_cols(key, S: int, K: int):
    """This iteration's ``S`` two-hop columns (``pool slot * K +
    neighbor slot``), shared by every node. Forward lists are sorted
    nearest first, so ranks are drawn as ``floor(K * u**2)``: a
    neighbor's near neighbors come up most often, far ranks still
    appear. A quarter of the pool slots are reverse edges (unsorted,
    drawn uniformly). Against uniform columns this raised graph
    recall@64 at 200k rows from 0.36 to 0.56 (CPU, random init)."""
    k_p, k_o, k_r, k_s = jax.random.split(key, 4)

    def rank(k):
        u = jax.random.uniform(k, (S,))
        return jnp.minimum(jnp.floor(K * u * u), K - 1).astype(jnp.int32)

    rev = jax.random.uniform(k_r, (S,)) < 0.25
    slot = jnp.where(rev, K + jax.random.randint(k_s, (S,), 0, K), rank(k_p))
    return slot * K + rank(k_o)


def _blocked(fn, n: int, block: int):
    """Cover [0, n) with ``fn(start, rows)`` under the OOM ladder —
    every dispatch, single-block covers included, so a
    RESOURCE_EXHAUSTED always halves and records instead of killing the
    build (the ladder's per-block completion sync is the price; the
    per-iteration host read this module used to pay — the scalar
    convergence transfer — stays killed, see the build loop's window)."""
    from raft_tpu.resilience import degrade

    return list(degrade.run_shrinking_blocks(
        fn, n, block, budget_name="graph_join_rows",
        stage="nn_descent.join",
    ))


def _resolve_join_impl(requested: str, C: int, K: int, d: int,
                       ip: bool) -> str:
    """Pick the join backend through the per-backend dispatch table
    (``tuning.choose("graph_join", ...)`` — docs/dispatch_tuning.md).
    The fused kernel is TPU-only and caps at K <= 128 (its K-pass
    extraction budget); winner strings carry the node tile
    (``pallas:<tile_b>``), so a live-chip capture adopts tile geometry
    with no code change. The analytic fallback on TPU is the fused
    kernel at the expression-derived tile; everywhere else the XLA
    join."""
    from raft_tpu import tuning
    from raft_tpu.ops.graph_join import tile_geometry

    if requested != "auto":
        if requested in ("pallas", "pallas_interpret"):
            return f"{requested}:{tile_geometry(C, K, d, ip)['tile_b']}"
        return requested
    if K > 128 or tuning.backend_name() != "tpu":
        return "xla"
    cands = ["xla"] + [f"pallas:{t}" for t in tuning.GRAPH_JOIN_TILES]
    fallback = f"pallas:{tile_geometry(C, K, d, ip)['tile_b']}"
    return tuning.choose(
        "graph_join", {"C": int(C), "K": int(K), "d": int(d)},
        cands, fallback,
    )


def build(params: IndexParams, dataset) -> Index:
    """Build the all-KNN graph (reference nn_descent.cuh build)."""
    from raft_tpu import obs

    data = jnp.asarray(dataset).astype(jnp.float32)
    n, d = data.shape
    with obs.entry_span("build", "nn_descent", rows=n):
        return _build(params, data, n)


def _build(params: IndexParams, data, n: int) -> Index:
    from raft_tpu import obs, tuning

    K = int(params.intermediate_graph_degree) or max(
        int(params.graph_degree * 3 // 2), int(params.graph_degree)
    )
    K = min(K, n - 1)
    out_K = min(int(params.graph_degree), K)
    d = int(data.shape[1])
    ip = params.metric == DistanceType.InnerProduct
    norms = jnp.sum(data * data, axis=1)
    key = jax.random.PRNGKey(params.seed)

    S = int(params.n_candidates)
    R = min(_REV_COLS, K)
    impl = _resolve_join_impl(str(params.join_impl), S + K, K, d, ip)
    kind, _, tile = impl.partition(":")
    tile_b = int(tile) if tile else 0

    def block_rows() -> int:
        # re-read per iteration: an OOM downshift records a runtime
        # ceiling mid-build, and later iterations must START at the
        # survivor size instead of re-attempting the known-too-big
        # block once per iteration. An explicit block_rows wins over
        # the tuned default; the learned ceiling outranks both.
        if int(params.block_rows) > 0:
            ceil = tuning.runtime_budget("graph_join_rows")
            b = int(params.block_rows) if ceil is None else min(
                int(params.block_rows), ceil)
        else:
            b = int(tuning.budget("graph_join_rows", _DEF_BLOCK_ROWS))
        return max(1, b)

    # init lists (_init_graph), exactly scored + deduped, blocked like
    # the join (the [rows, K, d] init gather is the same transient class)
    key, k0 = jax.random.split(key)
    init_i = _init_graph(data, n, K, k0, ip, int(params.seed))
    init_i = jnp.where(init_i == jnp.arange(n)[:, None], (init_i + 1) % n,
                       init_i)
    parts = _blocked(
        lambda s, r: _init_block(data, norms, init_i, s, rows=r, ip=ip),
        n, block_rows(),
    )
    graph_d = jnp.concatenate([p[0] for p in parts], axis=0)
    graph_i = jnp.concatenate([p[1] for p in parts], axis=0)

    threshold = float(params.termination_threshold) * n * K
    check_every = max(1, int(params.check_every))
    updates = []                      # device-side window, read per-window
    with obs.span("nn_descent.iterate", impl=impl, block=block_rows(),
                  iters=int(params.max_iterations)):
        for it in range(int(params.max_iterations)):
            key, kit = jax.random.split(key)
            rev_i = _make_rev(graph_i)
            pool = jnp.concatenate([graph_i, rev_i], axis=1)   # [n, 2K]
            # fresh column draw per iteration — the bloom-filter
            # "new vs old" bookkeeping collapses into re-sampling
            kit, krev = jax.random.split(kit)
            cols = _sample_cols(kit, S + K - R, K)
            rcols = jax.random.permutation(krev, K)[:R]
            parts = _blocked(
                lambda s, r: _join_block(
                    data, norms, graph_d, graph_i, pool, rev_i, cols, rcols,
                    s, rows=r, ip=ip, impl=kind, tile_b=tile_b),
                n, block_rows(),
            )
            graph_d = jnp.concatenate([p[0] for p in parts], axis=0)
            graph_i = jnp.concatenate([p[1] for p in parts], axis=0)
            updates.append(sum(p[2] for p in parts))
            if len(updates) >= check_every:
                window = jax.device_get(jnp.stack(updates))
                updates = []
                if int(window.min()) <= threshold:
                    break
    dists = graph_d[:, :out_K]
    if params.metric == DistanceType.L2SqrtExpanded:
        dists = jnp.sqrt(jnp.maximum(dists, 0.0))
    elif ip:
        dists = -dists
    return Index(graph=graph_i[:, :out_K], distances=dists)
