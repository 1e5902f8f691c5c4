#!/usr/bin/env python
"""Chip smoke: drive raft_tpu's main search path once on a TPU, at
deployment sizes, through the entry points a user calls, and check every
answer against an independent numpy oracle (tests/oracles.py).

    python chip_smoke.py [--seed S]          # one chip: phases a-e
    python chip_smoke.py --chips 4 [--seed S]  # four chips: sharded IVF-PQ

Data is generated on the device from ``--seed`` at the published shapes
of raft-ann-bench's ``sift-128-euclidean`` (1M x 128 f32, 10k queries)
and of DEEP (96-d unit-norm rows; the DEEP-10M subset of
``deep-image-96-inner``; DEEP-1B's recipe for the four-chip path). Each
phase prints one JSON line: build and search wall seconds (a smoke, not a
benchmark: cold compiles included), recall@10 on a query subset against
the oracle, and the implementation each op was dispatched to (the
``tuning.dispatch`` counters). A phase whose answer is wrong, or whose op
ran on another implementation than the TPU kernel expected, fails the run.
The last line is ``{"ok": true, "device": {...}}``.

There is no CPU path: without a TPU this exits non-zero with the reason.
Tests import the phase functions and run them tiny on the CPU
(tests/test_chip_smoke.py), never through ``main()``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
K = 10

# (phase, op) -> implementation prefix the analytic rule picks on a TPU
EXPECTED = {
    "brute_force": {"fused_topk_tile": "fused_exact"},
    "ivf_flat": {"ivf_scan": "pallas"},
    "cagra": {"graph_join": "pallas", "beam_step_tile": "pallas"},
    "serve": {"ivf_scan": "pallas"},
}


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _oracles():
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import oracles

    return oracles


# ---------------------------------------------------------------------------
# data (generated on the device, in bulk)
# ---------------------------------------------------------------------------


def sift_like(n: int, n_queries: int, seed: int):
    """SIFT-shaped rows: a 16-d manifold in 128 dims, values in [0, 255]
    (the bench-wide recipe, raft_tpu.bench.run)."""
    from raft_tpu.bench.run import synthetic_dataset_device

    return synthetic_dataset_device(n, 128, n_queries, seed=seed,
                                    intrinsic_dim=16)


def deep_like(n: int, n_queries: int, seed: int, sharding=None,
              block: int = 1 << 21):
    """DEEP-shaped rows: 96-d, unit norm (DEEP's CNN features are
    L2-normalised), same manifold recipe. ``sharding`` places the rows as
    they are made, so no device ever holds the whole dataset."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.bench.run import _gen_device_block

    def unit(a):
        return a / jnp.linalg.norm(a, axis=1, keepdims=True)

    key = jax.random.PRNGKey(seed)
    q = unit(_gen_device_block(n_queries, 96, 16)(jax.random.fold_in(key, 0)))
    if sharding is None:
        block = min(block, n)
        gen = jax.jit(lambda kk: unit(_gen_device_block(block, 96, 16)(kk)))
        parts = [gen(jax.random.fold_in(key, 1 + b))[:min(block, n - off)]
                 for b, off in enumerate(range(0, n, block))]
        x = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    else:
        gen = jax.jit(lambda kk: unit(_gen_device_block(n, 96, 16)(kk)),
                      out_shardings=sharding)
        x = gen(jax.random.fold_in(key, 1))
    return x, q


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


def true_dists(queries, base, ids, metric: str):
    """float64 distances of ``ids`` [m, k] (rows gathered on the device),
    and the f32 rounding scale of each: ||q||^2 + ||x||^2 for the
    expanded L2 form, ||q|| ||x|| for a dot product."""
    q = np.asarray(queries, np.float64)
    rows = np.asarray(base[np.asarray(np.maximum(ids, 0))], np.float64)
    dots = np.einsum("md,mkd->mk", q, rows)
    qn, xn = (q * q).sum(1)[:, None], (rows * rows).sum(2)
    if metric == "inner_product":
        return dots, np.sqrt(qn * xn)
    return qn + xn - 2.0 * dots, qn + xn


TIE_TOL = 4e-6


def tie_gap(found_ids, oracle_d, queries, base, metric: str) -> float:
    """How far the ids are from the oracle's beyond distance ties: the
    worst gap, over ranks, between the true distance of the returned id
    and the oracle's distance at that rank, relative to the f32 rounding
    scale (:func:`true_dists`). Ids equal the oracle's up to ties when
    this is within ``TIE_TOL``; an invalid (-1) id scores infinity."""
    found_ids = np.asarray(found_ids)
    if np.any(found_ids < 0):
        return float("inf")
    got, scale = true_dists(queries, base, found_ids, metric)
    if metric == "inner_product":
        got, want = -got, -np.asarray(oracle_d)
    else:
        want = np.asarray(oracle_d)
    order = np.argsort(got, axis=1)
    got = np.take_along_axis(got, order, axis=1)
    scale = np.take_along_axis(scale, order, axis=1)
    return float(np.max(np.abs(got - want) / scale))


def shortlist_program(mesh, rows: int, width: int, chunk: int):
    """The device half of :func:`shortlist_oracle`: per chip, the best
    ``width`` inner products of each query over its ``rows`` rows, by a
    full-f32 matmul and ``lax.top_k`` in ``chunk``-row pieces. Returns
    (scores, global ids), each [m, chips * width]."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    chunk = next(c for c in range(min(chunk, rows), 0, -1) if rows % c == 0)

    def local(qq, xs):
        def one(blk):
            return jax.lax.top_k(jnp.dot(
                qq, blk.T, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32), width)

        m, d = qq.shape
        v, i = jax.lax.map(one, xs.reshape(rows // chunk, chunk, d))
        i = i + (jnp.arange(rows // chunk, dtype=jnp.int32)
                 * chunk)[:, None, None]
        v = v.transpose(1, 0, 2).reshape(m, -1)
        i = i.transpose(1, 0, 2).reshape(m, -1)
        v, j = jax.lax.top_k(v, width)
        i = jnp.take_along_axis(i, j, axis=1)
        return v, i + jax.lax.axis_index("shard") * rows

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P("shard", None)),
        out_specs=(P(None, "shard"), P(None, "shard"))))


def shortlist_oracle(q, x, mesh, k: int, width: int = 128,
                     chunk: int = 1 << 20):
    """Exact inner-product top-``k`` of ``q`` over the row-sharded ``x``
    without copying ``x`` to the host, by plain JAX and numpy only (no
    raft_tpu code): each chip keeps its best ``width`` f32 scores per
    query (:func:`shortlist_program`); the host rescores the gathered
    shortlist rows in float64 and keeps ``k``. That is the whole set's
    top ``k`` when the k-th float64 score beats every chip's
    ``width``-th f32 score by more than the f32 error: no row outside
    the shortlist can reach it (checked). Returns (scores [m, k]
    float64, ids [m, k]), best first, ties to the lower id, as
    ``oracles.exact_knn_blocked``."""
    import jax.numpy as jnp

    m, d = q.shape
    chips = mesh.devices.size
    v, ids = shortlist_program(mesh, x.shape[0] // chips, width, chunk)(q, x)
    v, ids = np.asarray(v), np.asarray(ids)              # [m, chips*width]
    qh = np.asarray(q, np.float64)
    got = np.einsum("md,mwd->mw", qh,
                    np.asarray(x[jnp.asarray(ids)], np.float64))
    order = np.lexsort((ids, -got), axis=1)[:, :k]
    best, best_i = (np.take_along_axis(got, order, 1),
                    np.take_along_axis(ids, order, 1))
    # the largest score a row outside the shortlist can have: below its
    # chip's width-th kept f32 score, plus the f32 error of a d-term dot
    floor = v.reshape(m, chips, width)[:, :, -1].max(1)
    xn = float(jnp.sqrt(jnp.max(jnp.sum(x * x, axis=1))))
    err = 4 * d * np.finfo(np.float32).eps * np.linalg.norm(qh, axis=1) * xn
    check(np.all(best[:, -1] > floor + err),
          "shortlist oracle: a row outside the shortlist could reach the "
          "top k (widen it)")
    return best, best_i


def recall(found_ids, oracle_ids) -> float:
    return float(_oracles().eval_recall(np.asarray(found_ids),
                                        np.asarray(oracle_ids)))


def same_answers(d1, i1, d2, i2, rtol: float = 1e-5):
    """Two answers to the same query rows agree: distances match at every
    rank, and ids match wherever that rank's distance is not tied."""
    d1, d2 = np.asarray(d1, np.float64), np.asarray(d2, np.float64)
    i1, i2 = np.asarray(i1), np.asarray(i2)
    tol = rtol * np.maximum(np.abs(d2), 1.0)
    check(np.all(np.abs(d1 - d2) <= tol), "distances differ")
    gaps = np.abs(d2[:, :, None] - d2[:, None, :]) <= tol[:, :, None]
    tied = gaps.sum(2) > 1
    check(np.all((i1 == i2) | tied), "ids differ at untied ranks")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


class Phase:
    """Times one phase and collects its ``tuning.dispatch`` counters."""

    def __init__(self, name: str):
        from raft_tpu import obs

        self.name, self.row = name, {"phase": name}
        self.build_s = self.search_s = None
        obs.reset()
        note(f"{name} starts")

    def dispatch(self) -> dict:
        from raft_tpu import obs

        pts = obs.snapshot(runtime_gauges=False)["metrics"].get(
            "tuning.dispatch", {"points": []})["points"]
        out: dict = {}
        for p in pts:
            lab = p["labels"]
            out.setdefault(lab["op"], set()).add(lab["impl"])
        return {op: sorted(v) for op, v in sorted(out.items())}

    def finish(self, tpu: bool, checks=(), **fields) -> dict:
        """Print the phase's line, then fail on the first check that
        does not hold (``checks``: (condition, message) pairs), or on an
        op that did not run on its expected TPU kernel."""
        disp = self.dispatch()
        checks = list(checks)
        if tpu:
            for op, want in EXPECTED.get(self.name, {}).items():
                got = disp.get(op, [])
                checks.append((got and all(g.startswith(want) for g in got),
                               f"op {op} ran on {got or 'nothing'}, "
                               f"expected {want}"))
        self.row.update(smoke_not_benchmark={
            "build_s": self.build_s, "search_s": self.search_s})
        self.row.update(fields)
        self.row["dispatch"] = disp
        emit(self.row)
        for cond, msg in checks:
            check(cond, f"{self.name}: {msg}")
        return self.row


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def phase_brute_force(x, q, sub, oracle, tpu=True):
    """(a) exact search over every row; ids equal the oracle's up to
    distance ties."""
    from raft_tpu.neighbors import brute_force

    ph = Phase("brute_force")
    index, ph.build_s = _timed(lambda: brute_force.build(x, "sqeuclidean"))
    (d, i), ph.search_s = _timed(lambda: brute_force.search(index, q, K))
    gap = tie_gap(np.asarray(i[:sub]), oracle[0], q[:sub], x, "sqeuclidean")
    r = recall(np.asarray(i[:sub]), oracle[1])
    return ph.finish(tpu, [(gap <= TIE_TOL, f"ids differ from the oracle "
                            f"beyond distance ties (gap {gap:.3g})")],
                     recall_at_10=r, worst_tie_gap=gap,
                     queries=int(q.shape[0]))


def phase_ivf_flat(x, q, sub, oracle, n_lists=1024, n_probes=64,
                   floor=0.90, tpu=True):
    """(b) IVF-Flat, recall@10 >= ``floor``."""
    from raft_tpu.neighbors import ivf_flat

    ph = Phase("ivf_flat")
    index, ph.build_s = _timed(lambda: ivf_flat.build(
        ivf_flat.IndexParams(n_lists=n_lists), x))
    sp = ivf_flat.SearchParams(n_probes=n_probes)
    (d, i), ph.search_s = _timed(lambda: ivf_flat.search(sp, index, q, K))
    r = recall(np.asarray(i[:sub]), oracle[1])
    return ph.finish(tpu, [(r >= floor, f"recall@10 {r:.4f} < {floor}")],
                     recall_at_10=r, n_lists=n_lists,
                     n_probes=n_probes)


def phase_ivf_pq(x, q, sub, oracle, n_lists=1024, n_probes=128,
                 floor=0.95, batch_size=2_000_000, tpu=True):
    """(c) IVF-PQ pq48x8, refined (refine_ratio=3) recall@10 >= ``floor``."""
    from raft_tpu.neighbors import ivf_pq

    ph = Phase("ivf_pq")
    params = ivf_pq.IndexParams(n_lists=n_lists, pq_dim=48, pq_bits=8,
                                kmeans_trainset_fraction=0.1)
    index, ph.build_s = _timed(
        lambda: ivf_pq.build(params, x, batch_size=batch_size))
    sp = ivf_pq.SearchParams(n_probes=n_probes)
    (d, i), ph.search_s = _timed(lambda: ivf_pq.search_refined(
        sp, index, q, K, refine_ratio=3, dataset=x))
    r = recall(np.asarray(i[:sub]), oracle[1])
    _, i_raw = ivf_pq.search(sp, index, q[:sub], K)
    return ph.finish(tpu, [(r >= floor, f"refined recall@10 {r:.4f} < "
                            f"{floor}")],
                     recall_at_10=r,
                     recall_at_10_unrefined=recall(np.asarray(i_raw),
                                                   oracle[1]),
                     rows=int(x.shape[0]), n_lists=n_lists,
                     n_probes=n_probes, refine_ratio=3)


def phase_cagra(x, q, sub, oracle, floor=0.90, tpu=True):
    """(d) CAGRA, graph built by nn-descent, recall@10 >= ``floor``."""
    from raft_tpu.neighbors import cagra

    ph = Phase("cagra")
    params = cagra.IndexParams(
        graph_degree=32, intermediate_graph_degree=64,
        graph_build_algo=cagra.build_algo.NN_DESCENT)
    index, ph.build_s = _timed(lambda: cagra.build(params, x))
    sp = cagra.SearchParams(n_seeds=64, max_iterations=15)
    (d, i), ph.search_s = _timed(lambda: cagra.search(sp, index, q, K))
    r = recall(np.asarray(i[:sub]), oracle[1])
    return ph.finish(tpu, [(r >= floor, f"recall@10 {r:.4f} < {floor}")],
                     recall_at_10=r, rows=int(x.shape[0]), graph_degree=32)


def phase_serve(x, q, n_lists=1024, n_probes=64, requests=256,
                max_rows=64, seed=0, tpu=True):
    """(e) serve.Server over IVF-Flat: ``requests`` concurrent submits of
    1..``max_rows`` rows, each answer equal to ivf_flat.search on the same
    rows of the served index."""
    from raft_tpu import serve
    from raft_tpu.neighbors import ivf_flat

    ph = Phase("serve")
    sp = ivf_flat.SearchParams(n_probes=n_probes)
    srv = serve.Server(serve.ServeParams(
        warmup=False, max_k=K, max_queue_rows=requests * max_rows))
    try:
        _, ph.build_s = _timed(lambda: srv.create_index(
            "sift", np.asarray(x), algo="ivf_flat",
            build_params=ivf_flat.IndexParams(n_lists=n_lists),
            search_params=sp))
        rng = np.random.default_rng(seed)
        qh = np.asarray(q)
        sizes = rng.integers(1, max_rows + 1, requests)
        starts = rng.integers(0, qh.shape[0] - max_rows, requests)
        t0 = time.perf_counter()
        futs = [srv.submit(qh[s:s + n], K, index="sift")
                for s, n in zip(starts, sizes)]
        answers = [f.result(timeout=600) for f in futs]
        ph.search_s = time.perf_counter() - t0
        # one direct search over every request's rows (rows are
        # answered independently, and one shape compiles once)
        index = srv.registry.get("sift").handle.index
        d0, i0 = ivf_flat.search(sp, index, np.concatenate(
            [qh[s:s + n] for s, n in zip(starts, sizes)]), K)
        same_answers(np.concatenate([a[0] for a in answers]),
                     np.concatenate([a[1] for a in answers]), d0, i0)
    finally:
        srv.close()
    return ph.finish(tpu, requests=requests,
                     rows=int(sizes.sum()), max_request_rows=max_rows)


def phase_sharded(x, q, oracle, mesh, n_lists=4096, n_probes=256,
                  refine_ratio=10, floor=0.90, tpu=True):
    """Four chips: sharded IVF-PQ (inner product, pq48x8) built over the
    mesh and searched list-sharded, exact rerank of the merged shortlist
    from the row-sharded originals on the chips; compared with
    sharded_knn (ids equal the oracle's up to ties) and with the oracle
    (recall >= floor)."""
    from raft_tpu.comms import (
        sharded_ivf_pq_build, sharded_ivf_pq_search, sharded_knn,
    )
    from raft_tpu.neighbors import ivf_pq

    ph = Phase("sharded_ivf_pq")
    # 1M training rows for the coarse and PQ quantizers, 10 k-means
    # iterations (the recipe's own sharded rehearsal used 10)
    params = ivf_pq.IndexParams(
        n_lists=n_lists, pq_dim=48, pq_bits=8, metric="inner_product",
        kmeans_n_iters=10, kmeans_trainset_fraction=1_000_000 / x.shape[0],
        cache_decoded=False)
    index, ph.build_s = _timed(lambda: sharded_ivf_pq_build(params, x, mesh))
    note(f"sharded build done ({ph.build_s:.1f} s)")
    sp = ivf_pq.SearchParams(n_probes=n_probes)
    (d, i), ph.search_s = _timed(lambda: sharded_ivf_pq_search(
        sp, index, q, K, mesh, refine_ratio=refine_ratio,
        rerank_source=x))
    note("sharded search done")
    index = None    # sharded_knn's scan needs the room
    (kd, ki), knn_s = _timed(lambda: sharded_knn(
        q, x, K, mesh, metric="inner_product"))
    gap = tie_gap(np.asarray(ki), oracle[0], q, x, "inner_product")
    r = recall(np.asarray(i), oracle[1])
    r_knn = recall(np.asarray(i), np.asarray(ki))
    return ph.finish(tpu, [
        (gap <= TIE_TOL, f"sharded_knn ids differ from the oracle beyond "
                         f"distance ties (gap {gap:.3g})"),
        (r >= floor, f"recall@10 {r:.4f} < {floor}")],
                     recall_at_10=r, recall_vs_sharded_knn=r_knn,
                     sharded_knn_s=knn_s, sharded_knn_worst_tie_gap=gap,
                     rows=int(x.shape[0]), chips=int(mesh.devices.size),
                     n_lists=n_lists, n_probes=n_probes,
                     refine_ratio=refine_ratio)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


_T0 = time.perf_counter()


def emit(row: dict):
    print(json.dumps(row, default=float), flush=True)


def note(step: str):
    """A progress line on stderr (seconds since start), so that a run cut
    by its time limit still shows how far it got."""
    print(f"chip_smoke: {time.perf_counter() - _T0:.1f} s: {step}",
          file=sys.stderr, flush=True)


def run_one_chip(seed: int):
    sub = 200
    emit({"cuts": []})
    ora = _oracles()
    x, q = sift_like(1_000_000, 10_000, seed)
    oracle = ora.exact_knn_blocked(np.asarray(q[:sub]), x, K)
    phase_brute_force(x, q, sub, oracle)
    phase_ivf_flat(x, q, sub, oracle)
    phase_serve(x, q, seed=seed)
    phase_cagra(x, q, sub, oracle)
    del x, q
    x, q = deep_like(10_000_000, 10_000, seed + 1)
    oracle = ora.exact_knn_blocked(np.asarray(q[:sub // 2]), x, K)
    phase_ivf_pq(x, q, sub // 2, oracle)


def run_four_chips(seed: int):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs).reshape(4), ("shard",))
    n = 40_000_000
    emit({"cuts": ["DEEP-1B 1e9 rows -> 4e7 (1e7 per chip, what 4 x 16 GB "
                   "holds with the f32 rows resident for the rerank)",
                   "n_lists 50K (deep-1B.json) -> 4096 at 4e7 rows"]})
    x, q = deep_like(n, 100, seed + 2,
                     sharding=NamedSharding(mesh, P("shard", None)))
    jax.block_until_ready(x)
    note("data made")
    oracle = shortlist_oracle(q, x, mesh, K)
    note("oracle done")
    phase_sharded(x, q, oracle, mesh)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s); JAX found "
              f"{len(devs)} x {dev.platform} ({dev.device_kind})")
        return 1
    sys.path.insert(0, ROOT)
    from raft_tpu import obs

    obs.set_mode("on")
    emit({"compile_cache": jax.config.jax_compilation_cache_dir or None,
          "cache_dir_from_env": "JAX_COMPILATION_CACHE_DIR" in os.environ})
    if args.chips == 4:
        run_four_chips(args.seed)
    else:
        run_one_chip(args.seed)
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devs)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
