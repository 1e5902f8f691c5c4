#!/usr/bin/env python
"""Graph-build measurement battery (ISSUE 15; artifact GRAPH_r{N}.json).

Measures the nn-descent rebuild against the pre-r15 formulation on the
CURRENT host, honestly labeled (CPU today; rerun on chip day — the
stage is wired into scripts/r5_measure_all.py as ``graph_bench``):

1. **A/B: gather-then-sample vs sample-then-gather** — the old
   iteration materialized the FULL two-hop tensor ``graph[pool]``
   (``[n, 2K, K]`` int32) before sampling S columns; the rebuild
   samples first and gathers only the ``[n, S]`` chosen entries. The
   two are *algebraically identical* (same columns of the same
   tensor), so the graphs agree bitwise and the comparison is pure
   wall-clock + bytes — recall is equal by construction (asserted).
2. **Blocked 1M-row build** — wall clock + KNN-graph recall of the
   new blocked path at the ROADMAP-item-7 scale, with the analytic
   per-iteration transient columns showing the peak is bounded by
   ``graph_join_rows``, not n; one old-formulation iteration is timed
   at the same scale for the headline ratio (capped: at 1M/K=96 the
   old tensor alone is ~73 GB, beyond most hosts).

Usage:
  python scripts/graph_bench.py [out.json] [--n 1000000] [--dim 64]
      [--degree 32] [--iters 6] [--ab-n 100000] [--skip-big]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _old_iter_fn():
    """The pre-r15 iteration (gather-then-sample, unblocked), kept
    HERE — not in the library — purely as the measured baseline."""
    import functools

    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors.nn_descent import (
        _make_rev,
        _merge_topk_unique,
        _score,
    )

    @functools.partial(jax.jit, static_argnums=(3, 4, 5))
    def old_iter(state, data, norms, K: int, S: int, ip: bool, key=None):
        graph_d, graph_i = state
        n = data.shape[0]
        node_ids = jnp.arange(n, dtype=jnp.int32)
        rev_i = _make_rev(graph_i)
        pool = jnp.concatenate([graph_i, rev_i], axis=1)
        pool_safe = jnp.maximum(pool, 0)
        cols = jax.random.randint(key, (S,), 0, 2 * K * K)
        two_hop = graph_i[pool_safe]                     # [n, 2K, K]
        cand = two_hop.reshape(n, 2 * K * K)[:, cols]    # [n, S]
        cand = jnp.where(
            jnp.take_along_axis(
                pool, jnp.broadcast_to(cols[None, :] // K, (n, S)), axis=1
            ) >= 0,
            cand, -1,
        )
        cand = jnp.concatenate([cand, rev_i], axis=1)
        cand = jnp.where(cand == node_ids[:, None], -1, cand)
        cand_d = _score(node_ids, jnp.maximum(cand, 0), data, norms, ip)
        cand_d = jnp.where(cand < 0, jnp.inf, cand_d)
        new_d, new_i = _merge_topk_unique(graph_d, graph_i, cand_d, cand, K)
        return (new_d, new_i), jnp.sum(new_i != graph_i)

    return old_iter


def _new_iter(state, data, norms, K, S, ip, key, block):
    """One rebuild iteration through the library's blocked join."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.neighbors.nn_descent import (
        _blocked,
        _join_block,
        _make_rev,
    )

    graph_d, graph_i = state
    n = data.shape[0]
    rev_i = _make_rev(graph_i)
    pool = jnp.concatenate([graph_i, rev_i], axis=1)
    cols = jax.random.randint(key, (S,), 0, 2 * K * K)
    parts = _blocked(
        lambda s, r: _join_block(data, norms, graph_d, graph_i, pool,
                                 rev_i, cols, s, rows=r, ip=ip,
                                 impl="xla", tile_b=0),
        n, block,
    )
    gd = jnp.concatenate([p[0] for p in parts], axis=0)
    gi = jnp.concatenate([p[1] for p in parts], axis=0)
    return (gd, gi), sum(p[2] for p in parts)


def _transient_columns(n, K, S, d, block):
    """Analytic per-iteration transient bytes (the bound the blocked
    rebuild enforces): old = the full two-hop tensor; new = one block's
    sampled ids + gathered candidate vectors + merge pool."""
    C = S + K
    old = n * (2 * K) * K * 4                    # [n, 2K, K] int32
    rows = min(n, block)
    new = rows * S * 4 + rows * C * d * 4 + rows * C * 8 \
        + rows * (C + K) * 8                     # ids + gather + merge pool
    return {
        "old_two_hop_bytes": int(old),
        "new_block_transient_bytes": int(new),
        "new_bound": "graph_join_rows block (%d rows), independent of n"
                     % rows,
    }


def ab_stage(results, n, d, K, S, iters, seed=3, data=None):
    """Old vs new, iteration-for-iteration on identical state: same
    keys, bitwise-identical graphs (asserted), wall clock compared
    (first iteration carries the compile — recorded, excluded from the
    medians)."""
    import jax
    import jax.numpy as jnp

    from raft_tpu import tuning
    from raft_tpu.neighbors.nn_descent import _blocked, _init_block

    rng = np.random.default_rng(seed)
    if data is None:
        data = rng.standard_normal((n, d)).astype(np.float32)
    data = jnp.asarray(data)
    norms = jnp.sum(data * data, axis=1)
    key = jax.random.PRNGKey(0)
    key, k0 = jax.random.split(key)
    init_i = jax.random.randint(k0, (n, K), 0, n).astype(jnp.int32)
    init_i = jnp.where(init_i == jnp.arange(n)[:, None], (init_i + 1) % n,
                       init_i)
    block = int(tuning.budget("graph_join_rows", 1 << 16))
    parts = _blocked(
        lambda s, r: _init_block(data, norms, init_i, s, rows=r,
                                 ip=False), n, block)
    state0 = (jnp.concatenate([p[0] for p in parts]),
              jnp.concatenate([p[1] for p in parts]))
    jax.block_until_ready(state0)

    old_iter = _old_iter_fn()
    keys = []
    kk = key
    for _ in range(iters):
        kk, kit = jax.random.split(kk)
        keys.append(kit)

    def run(step):
        state = state0
        t_iters = []
        for kit in keys:
            t0 = time.perf_counter()
            state, _ = step(state, kit)
            jax.block_until_ready(state)
            t_iters.append(time.perf_counter() - t0)
        return state, t_iters

    state_new, t_new = run(
        lambda st, kit: _new_iter(st, data, norms, K, S, False, kit,
                                  block))
    state_old, t_old = run(
        lambda st, kit: old_iter(st, data, norms, K, S, False, key=kit))
    same = bool((np.asarray(state_old[1]) == np.asarray(state_new[1]))
                .all())
    # steady-state per-iteration medians (first iteration carries the
    # compile; keep it in the recorded lists, exclude from the median)
    med_old = float(np.median(t_old[1:])) if iters > 1 else t_old[0]
    med_new = float(np.median(t_new[1:])) if iters > 1 else t_new[0]
    results["ab"] = {
        "n": n, "d": d, "K": K, "S": S, "iters": iters,
        "bitwise_identical_graphs": same,
        "iter_s_old": [round(t, 3) for t in t_old],
        "iter_s_new": [round(t, 3) for t in t_new],
        "iter_s_old_median": round(med_old, 3),
        "iter_s_new_median": round(med_new, 3),
        "speedup_old_over_new": round(med_old / max(med_new, 1e-9), 2),
        **_transient_columns(n, K, S, d, block),
    }
    return same


def big_stage(results, n, d, degree, iters, ab_iters=2, seed=4):
    """The ROADMAP-item-7 scale, two measurements:

    * ``iter_ab`` — the per-iteration old-vs-new A/B at the FULL scale
      (``ab_iters`` iterations each, compile-carrying first iteration
      recorded but excluded from the medians; graphs asserted bitwise
      identical). At n=1M/K=48 the old path's two-hop tensor is
      ~18.4 GB *per iteration* — the thing sample-then-gather deletes.
    * ``build`` — the rebuilt blocked build end to end: wall clock +
      KNN-graph recall at ``iters`` iterations (nn-descent needs
      ~O(log n) rounds to localize from random init — at 1M, ~6 rounds
      is still noise; pick iters from a convergence sweep)."""
    from raft_tpu import tuning
    from raft_tpu.bench.run import generate_groundtruth
    from raft_tpu.neighbors import nn_descent

    rng = np.random.default_rng(seed)
    # clustered blobs (the shape the repo's graph suites use — 2026-08-04
    # measured: a flat 16-intrinsic-dim manifold at this scale converges
    # at only ~0.04 recall/iteration from random init, a pre-existing
    # property of the sampled pull-join shared bitwise by old AND new
    # paths; blobs localize in ~10 rounds, so the build column reports a
    # converged graph instead of an iteration-budget artifact)
    centers = rng.uniform(-5, 5, (1024, d)).astype(np.float32)
    x = (centers[rng.integers(0, 1024, n)]
         + 0.6 * rng.standard_normal((n, d)).astype(np.float32))
    K = max(degree * 3 // 2, degree)
    S = 128
    sub_results = {}
    try:
        ab_stage(sub_results, n, d, K, S, ab_iters, seed=seed + 1,
                 data=x)
        results["iter_1m"] = sub_results["ab"]
    except Exception as e:  # noqa: BLE001 - OOM at scale IS the result
        results["iter_1m"] = {
            "iter_s_old": f"DNF: {type(e).__name__}: {str(e)[:160]}"}

    params = nn_descent.IndexParams(
        graph_degree=degree, max_iterations=iters)
    t0 = time.perf_counter()
    idx = nn_descent.build(params, x)
    g = np.asarray(idx.graph)                    # sync
    build_s = time.perf_counter() - t0
    sub = 200
    want = np.asarray(generate_groundtruth(
        x, x[:sub], degree + 1, "sqeuclidean", chunk=1_000_000))
    rec = float(np.mean(
        [len(set(g[i]) & set(want[i][1:degree + 1])) / degree
         for i in range(sub)]))
    block = int(tuning.budget("graph_join_rows", 1 << 16))
    results["build"] = {
        "n": n, "d": d, "graph_degree": degree, "K": K, "S": S,
        "iters": iters, "build_s_new": round(build_s, 1),
        "recall_at_degree": round(rec, 4),
        **_transient_columns(n, K, S, d, block),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out", nargs="?", default="GRAPH_r15.json")
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--degree", type=int, default=32)
    ap.add_argument("--iters", type=int, default=14)
    ap.add_argument("--ab-n", type=int, default=100_000)
    ap.add_argument("--ab-iters", type=int, default=4)
    ap.add_argument("--big-ab-iters", type=int, default=2)
    ap.add_argument("--skip-big", action="store_true")
    args = ap.parse_args()

    import jax

    results = {
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "note": "old-vs-new are algebraically identical (bitwise-equal "
                "graphs), so recall is equal by construction and the "
                "comparison is wall-clock + transient bytes only",
    }
    t0 = time.time()
    K = max(args.degree * 3 // 2, args.degree)
    ok = ab_stage(results, args.ab_n, args.dim, K, 128, args.ab_iters)
    if not ok:
        results["ab"]["warning"] = "graphs diverged — investigate before " \
                                   "trusting the timing columns"
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)          # flush the A/B early
    if not args.skip_big:
        big_stage(results, args.n, args.dim, args.degree, args.iters,
                  args.big_ab_iters)
    results["elapsed_s"] = round(time.time() - t0, 1)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
