"""Config-driven benchmark orchestration — the raft-ann-bench analog
(reference python/raft-ann-bench/src/raft-ann-bench/run/__main__.py:62-130
and its conf/*.json format; plot: .../plot/__main__.py).

A config names a dataset (file-backed .fbin or a synthetic spec) and a
list of index definitions, each with one build param set and many search
param sets — exactly the reference layout:

    {
      "dataset": {"name": "sift-1m-synth", "synthetic": {"n": 1000000,
                  "dim": 128, "n_queries": 10000, "seed": 1},
                  "distance": "sqeuclidean", "k": 10},
      "index": [
        {"name": "ivf_flat.1024", "algo": "ivf_flat",
         "build_param": {"n_lists": 1024},
         "search_params": [{"n_probes": 16}, {"n_probes": 64}]}
      ]
    }

File-backed datasets use ``base_file``/``query_file``/``groundtruth_file``
(big-ann .fbin/.ibin layout, bench/datasets.py). Ground truth is computed
with tiled brute force and cached next to the dataset when absent —
the reference's generate_groundtruth tool
(python/raft-ann-bench/src/raft-ann-bench/generate_groundtruth/).

Usage:
    python -m raft_tpu.bench.run --config conf.json --output out/
    python -m raft_tpu.bench.run --config conf.json --plot  # + pareto png
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from raft_tpu.bench import datasets as ds
from raft_tpu.bench.harness import (
    BenchResult,
    compute_recall,
    export_csv,
    pareto_frontier,
)


def _synthetic(spec: dict) -> Tuple[np.ndarray, np.ndarray]:
    """Low-intrinsic-dimension manifold data (real descriptor sets have
    intrinsic dim far below ambient; isolated-blob mixtures disconnect
    KNN graphs and make graph-ANN recall meaningless).

    Generated in row blocks, float32 throughout — float64 [n, d]
    temporaries would need >20 GB host RAM at DEEP-10M scale."""
    rng = np.random.default_rng(spec.get("seed", 0))
    n, d, nq = spec["n"], spec["dim"], spec["n_queries"]
    intrinsic = spec.get("intrinsic_dim", 16)
    proj = np.random.default_rng(12345).normal(
        0, 1.0 / np.sqrt(intrinsic), (intrinsic, d)
    ).astype(np.float32)

    def gen(count):
        out = np.empty((count, d), np.float32)
        for r0 in range(0, count, 1 << 20):
            r1 = min(r0 + (1 << 20), count)
            z = rng.normal(0, 24.0, (r1 - r0, intrinsic)).astype(np.float32)
            blk = 64.0 + z @ proj
            blk += rng.normal(0, 2.0, (r1 - r0, d)).astype(np.float32)
            np.clip(blk, 0, 255, out=out[r0:r1])
        return out

    return gen(n), gen(nq)


def synthetic_dataset(n, dim, n_queries, seed=0, intrinsic_dim=16):
    """Shared generator for bench.py and config-driven runs — ONE set of
    constants so the headline bench and the orchestrated runs see
    byte-identical datasets for the same spec."""
    return _synthetic({"n": n, "dim": dim, "n_queries": n_queries,
                       "seed": seed, "intrinsic_dim": intrinsic_dim})


@functools.lru_cache(maxsize=None)
def _gen_device_block(count: int, d: int, intr: int):
    """One shared jitted generator per shape (defining it per call would
    defeat jit's function-identity cache and recompile every time)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        kp = jax.random.PRNGKey(12345)
        proj = jax.random.normal(kp, (intr, d), jnp.float32) / jnp.sqrt(
            jnp.float32(intr)
        )
        kz, kn = jax.random.split(key)
        z = 24.0 * jax.random.normal(kz, (count, intr), jnp.float32)
        blk = 64.0 + z @ proj + 2.0 * jax.random.normal(
            kn, (count, d), jnp.float32
        )
        return jnp.clip(blk, 0, 255)

    return gen


def synthetic_dataset_device(n, dim, n_queries, seed=0, intrinsic_dim=16,
                             block: int = 4 << 20):
    """Same manifold recipe as ``synthetic_dataset`` generated ON DEVICE
    with jax.random (bit-different values, identical structure), in bulk
    on the device. Generated in fixed-shape row blocks so each generator
    program's temporaries stay at ``block`` rows; the assembled output
    (plus up to one extra copy during the final concatenate) still needs
    ~2x the dataset's bytes of HBM headroom — size n accordingly. Ground
    truth must be computed from the returned arrays."""
    import jax
    import jax.numpy as jnp

    def make(count, key):
        if count <= block:
            return _gen_device_block(int(count), int(dim),
                                     int(intrinsic_dim))(key)
        parts = []
        for off in range(0, count, block):
            key, sub = jax.random.split(key)
            rows = min(block, count - off)
            parts.append(
                _gen_device_block(int(rows), int(dim), int(intrinsic_dim))(sub)
            )
        return jnp.concatenate(parts, axis=0)

    kb, kq = jax.random.split(jax.random.PRNGKey(seed))
    return make(int(n), kb), make(int(n_queries), kq)


def load_dataset(cfg: dict) -> Tuple[np.ndarray, np.ndarray]:
    if "synthetic" in cfg:
        return _synthetic(cfg["synthetic"])
    base = ds.read_bin(cfg["base_file"])
    queries = ds.read_bin(cfg["query_file"])
    return base, queries


def generate_groundtruth(
    base: np.ndarray, queries: np.ndarray, k: int, metric: str,
    chunk: int = 1_000_000,
) -> np.ndarray:
    """Tiled exact KNN ground truth (generate_groundtruth analog)."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.distance.types import is_min_close, resolve_metric
    from raft_tpu.neighbors import brute_force
    from raft_tpu.neighbors.common import knn_merge_parts

    select_min = is_min_close(resolve_metric(metric))
    n = base.shape[0]
    if n <= chunk:
        _, idx = brute_force.knn(jnp.asarray(queries), jnp.asarray(base), k,
                                 metric=metric)
        return np.asarray(idx)
    parts_d, parts_i, offs = [], [], []
    q_dev = jax.device_put(queries)
    for c0 in range(0, n, chunk):
        block = jax.device_put(base[c0 : c0 + chunk])
        dd, ii = brute_force.knn(q_dev, block, k, metric=metric)
        parts_d.append(dd)
        parts_i.append(ii)
        offs.append(c0)
        del block
    md, mi = knn_merge_parts(
        jnp.stack(parts_d), jnp.stack(parts_i), k, select_min=select_min,
        translations=jnp.asarray(offs),
    )
    return np.asarray(mi)


def get_groundtruth(cfg: dict, base, queries, k: int) -> np.ndarray:
    metric = cfg.get("distance", "sqeuclidean")
    gt_file = cfg.get("groundtruth_file")
    if gt_file and os.path.exists(gt_file + ".neighbors.ibin"):
        gt = ds.read_groundtruth(gt_file)[0]
        if gt.shape[1] < k:
            raise ValueError(
                f"groundtruth_file has {gt.shape[1]} neighbors < k={k}"
            )
        if gt.shape[0] != queries.shape[0]:
            raise ValueError(
                f"groundtruth_file has {gt.shape[0]} rows but the query "
                f"set has {queries.shape[0]}"
            )
        return gt[:, :k]
    cache = cfg.get("groundtruth_cache")
    if cache is None and "synthetic" in cfg and cfg.get("name"):
        # deterministic synthetic data: default a cache keyed on the FULL
        # spec (a name-only key poisons runs whose configs share a name
        # but differ in size/seed)
        spec = cfg["synthetic"]
        tag = "-".join(
            [str(spec.get(f, "")) for f in
             ("n", "dim", "n_queries", "seed", "intrinsic_dim")]
            + [str(cfg.get("distance", "sqeuclidean"))]
        )
        os.makedirs(".bench_cache", exist_ok=True)
        cache = os.path.join(".bench_cache", f"{cfg['name']}-{tag}-gt")
    if cache and os.path.exists(cache + ".neighbors.ibin"):
        gt = ds.read_groundtruth(cache)[0]
        if gt.shape[1] >= k and gt.shape[0] == queries.shape[0]:
            return gt[:, :k]
    gt = generate_groundtruth(base, queries, max(k, 100), metric)
    if cache:
        ds.write_groundtruth(cache, gt)
    return gt[:, :k]


# --- algo adapters ---------------------------------------------------------


_HOST_ALGOS = frozenset({"hnswlib_cpu"})


def _make_case(algo: str, metric: str, build_param: dict, search_param: dict,
               base, k: int):
    """Returns (build_fn, search_q) closures for one (build, search) pair;
    ``search_q(ix, q)`` is query-parametrized so the timing loop can feed
    rolled (distinct) batches."""
    import jax.numpy as jnp

    if algo == "brute_force":
        from raft_tpu.neighbors import brute_force

        return (
            lambda: brute_force.build(jnp.asarray(base), metric),
            lambda ix, q: brute_force.search(ix, q, k, **search_param),
        )
    if algo == "ivf_flat":
        from raft_tpu.neighbors import ivf_flat

        params = ivf_flat.IndexParams(metric=metric, **build_param)
        sp = ivf_flat.SearchParams(**search_param)
        return (
            lambda: ivf_flat.build(params, base),
            lambda ix, q: ivf_flat.search(sp, ix, q, k),
        )
    if algo == "ivf_pq":
        from raft_tpu.neighbors import ivf_pq

        params = ivf_pq.IndexParams(metric=metric, **build_param)
        sp = ivf_pq.SearchParams(**search_param)
        return (
            lambda: ivf_pq.build(params, base),
            lambda ix, q: ivf_pq.search(sp, ix, q, k),
        )
    if algo == "cagra":
        from raft_tpu.neighbors import cagra

        params = cagra.IndexParams(metric=metric, **build_param)
        sp = cagra.SearchParams(**search_param)
        return (
            lambda: cagra.build(params, base),
            lambda ix, q: cagra.search(sp, ix, q, k),
        )
    if algo == "ball_cover":
        from raft_tpu.neighbors import ball_cover

        return (
            lambda: ball_cover.build(base, metric=metric, **build_param),
            lambda ix, q: ball_cover.knn_query(ix, q, k, **search_param),
        )
    if algo == "hnswlib_cpu":
        # competitor wrapper (the reference benches hnswlib via
        # cpp/bench/ann/src/hnswlib/): the real library is not
        # installable here, so the CAGRA graph is exported to the
        # hnswlib format and searched with hnswlib's base-layer
        # algorithm on the host (neighbors/hnswlib_io.py) — a CPU
        # single-thread baseline, honest about what it is
        import tempfile

        import numpy as _np

        from raft_tpu.neighbors import cagra
        from raft_tpu.neighbors.hnswlib_io import (
            greedy_search, load_hnswlib_index,
        )

        ef = int(search_param.get("ef", 96))

        def _build():
            import os as _os

            params = cagra.IndexParams(metric=metric, **build_param)
            idx = cagra.build(params, base)
            fd, path = tempfile.mkstemp(suffix=".hnsw")
            _os.close(fd)
            try:
                cagra.serialize_to_hnswlib(path, idx)
                return load_hnswlib_index(path, dim=base.shape[1])
            finally:
                _os.unlink(path)

        def _search(ix, q):
            qh = _np.asarray(q)
            ds = _np.full((qh.shape[0], k), _np.inf, _np.float32)
            ids = _np.full((qh.shape[0], k), -1, _np.int64)
            for i in range(qh.shape[0]):
                di, ii = greedy_search(ix, qh[i], k, ef=max(ef, k))
                ds[i, : len(ii)] = di[: k]
                ids[i, : len(ii)] = ii[: k]
            return jnp.asarray(ds), jnp.asarray(ids)

        return _build, _search
    raise ValueError(f"unknown algo {algo!r}")


def run_config(cfg: dict, iters: int = 10,
               mode: str = "throughput") -> List[BenchResult]:
    """``mode``: "throughput" (scan-chained batch QPS, default) or
    "latency" (reference raft_ann_benchmarks.md:240-254 `--mode latency`:
    per-call p50/p95 at batch 1 and 10; qps is then batch/p50)."""
    dcfg = cfg["dataset"]
    k = int(dcfg.get("k", 10))
    metric = dcfg.get("distance", "sqeuclidean")
    base, queries = load_dataset(dcfg)
    gt = get_groundtruth(dcfg, base, queries, k)
    results: List[BenchResult] = []
    for index_def in cfg["index"]:
        algo = index_def["algo"]
        bp = index_def.get("build_param", {})
        index = None
        build_s = 0.0
        from raft_tpu.bench.constraints import check_case

        if not check_case(algo, bp, {}, int(base.shape[1]), k):
            print(f"[bench] skip invalid build {algo} {bp}")
            continue
        for si, sp in enumerate(index_def.get("search_params", [{}])):
            if not check_case(algo, bp, sp, int(base.shape[1]), k):
                print(f"[bench] skip invalid case {algo} {bp} {sp}")
                continue
            build_fn, search_q = _make_case(algo, metric, bp, sp, base, k)
            if index is None:
                # build once per index definition, like the reference's
                # bench_build / bench_search split (benchmark.hpp:124,174)
                t0 = time.time()
                index = build_fn()
                import jax

                leaves = (
                    [v for v in vars(index).values() if isinstance(v, jax.Array)]
                    if hasattr(index, "__dict__") else [index]
                )
                jax.block_until_ready(leaves)
                build_s = time.time() - t0
            from raft_tpu.bench.harness import scan_qps_time
            import jax
            import jax.numpy as jnp

            q_dev = jnp.asarray(queries)
            dist, idx = search_q(index, q_dev)
            recall = compute_recall(np.asarray(idx), gt)
            if mode == "latency":
                from raft_tpu.bench.harness import latency_percentiles

                lat = {}
                for b in (1, 10):
                    lat[f"b{b}"] = latency_percentiles(
                        lambda q, ops: search_q(ops, q), q_dev, b,
                        n_calls=max(10, iters * 3), operands=index,
                    )
                p50_10 = lat["b10"]["p50"]
                r = BenchResult(
                    name=f"{index_def['name']}#{si}",
                    build_s=build_s,
                    search_s=p50_10 / 10.0,
                    qps=10.0 / p50_10,
                    recall=recall,
                    k=k,
                    n_queries=queries.shape[0],
                    extra={"algo": algo, "mode": "latency",
                           **{f"lat.{bk}.{mk}": round(mv, 6)
                              for bk, d_ in lat.items()
                              for mk, mv in d_.items()},
                           **{f"s.{kk}": vv for kk, vv in sp.items()}},
                )
                results.append(r)
                print(json.dumps(r.row()), flush=True)
                continue
            if algo in _HOST_ALGOS:
                # pure-host competitors can't jit at all; plain host timer
                from raft_tpu.bench.harness import time_fn

                search_s = time_fn(
                    lambda: search_q(index, q_dev)[1], iters=max(1, iters // 4)
                )
            else:
                try:
                    search_s = scan_qps_time(
                        lambda qq, ix: search_q(ix, qq),
                        q_dev, n1=max(2, iters // 4), n2=max(4, iters),
                        operands=index,
                    )
                except (jax.errors.TracerBoolConversionError,
                        jax.errors.ConcretizationTypeError):
                    # algos with host-side adaptive loops (ball_cover's
                    # certification rounds) can't run inside the scan;
                    # fall back to the pipelined host timer
                    from raft_tpu.bench.harness import time_fn

                    search_s = time_fn(
                        lambda: search_q(index, q_dev)[1], iters=iters
                    )
            r = BenchResult(
                name=f"{index_def['name']}#{si}",
                build_s=build_s,
                search_s=search_s,
                qps=queries.shape[0] / search_s,
                recall=recall,
                k=k,
                n_queries=queries.shape[0],
                extra={"algo": algo,
                       **{f"s.{kk}": vv for kk, vv in sp.items()}},
            )
            results.append(r)
            print(json.dumps(r.row()), flush=True)
    return results


def plot_results(results: List[BenchResult], path: str) -> None:
    """Recall-vs-QPS scatter + Pareto frontier PNG
    (raft-ann-bench.plot analog)."""
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, ax = plt.subplots(figsize=(7, 5))
    by_algo: Dict[str, List[BenchResult]] = {}
    for r in results:
        by_algo.setdefault(r.extra.get("algo", "?"), []).append(r)
    for algo, rs in by_algo.items():
        ax.scatter([r.recall for r in rs], [r.qps for r in rs], label=algo,
                   s=24)
    front = pareto_frontier(results)
    ax.plot([r.recall for r in front], [r.qps for r in front], "k--",
            lw=1, label="pareto")
    ax.set_xlabel(f"recall@{results[0].k}")
    ax.set_ylabel("QPS")
    ax.set_yscale("log")
    ax.legend()
    fig.tight_layout()
    fig.savefig(path, dpi=120)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--output", default=".")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--plot", action="store_true")
    ap.add_argument("--mode", choices=("throughput", "latency"),
                    default="throughput")
    ap.add_argument("--obs-snapshot", action="store_true",
                    help="run instrumented (graft-scope; forces "
                         "RAFT_TPU_OBS=on if off) and write a "
                         "<stem>.obs.json metrics sidecar next to the "
                         "results (docs/observability.md)")
    args = ap.parse_args(argv)
    if args.obs_snapshot:
        from raft_tpu import obs

        if not obs.enabled():
            obs.set_mode("on")
    cfg = json.load(open(args.config))
    os.makedirs(args.output, exist_ok=True)
    results = run_config(cfg, iters=args.iters, mode=args.mode)
    stem = os.path.splitext(os.path.basename(args.config))[0]
    export_csv(results, os.path.join(args.output, f"{stem}.csv"))
    with open(os.path.join(args.output, f"{stem}.json"), "w") as fp:
        json.dump([r.row() for r in results], fp, indent=2)
    if args.obs_snapshot:
        from raft_tpu.bench.harness import write_obs_snapshot

        write_obs_snapshot(os.path.join(args.output, f"{stem}.obs.json"))
    if args.plot:
        plot_results(results, os.path.join(args.output, f"{stem}.png"))


if __name__ == "__main__":
    main()
