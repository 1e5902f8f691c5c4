"""ANN benchmark harness.

Analog of the reference's bench driver (cpp/bench/ann/src/common/
benchmark.hpp: ``bench_build``:124, ``bench_search``:174, in-harness recall
:341-375) and the raft-ann-bench orchestration
(python/raft-ann-bench/src/raft-ann-bench/run/__main__.py): JSON configs
name a dataset + algo + param sets; the harness builds, searches, computes
recall against ground truth, and reports QPS / latency / build time.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np


@dataclasses.dataclass
class BenchResult:
    name: str
    build_s: float
    search_s: float
    qps: float
    recall: float
    k: int
    n_queries: int
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def row(self) -> dict:
        return {
            "name": self.name,
            "build_time": self.build_s,
            "search_time": self.search_s,
            "qps": self.qps,
            "recall": self.recall,
            "k": self.k,
            "n_queries": self.n_queries,
            **self.extra,
        }


def compute_recall(found_idx: np.ndarray, true_idx: np.ndarray) -> float:
    """Set-intersection recall@k (reference benchmark.hpp:341-375)."""
    n, k = found_idx.shape
    true_idx = true_idx[:, :k]
    hits = 0
    for i in range(n):
        hits += len(np.intersect1d(found_idx[i], true_idx[i], assume_unique=False))
    return hits / (n * k)


def time_fn(fn: Callable[[], Any], iters: int = 10, warmup: int = 2) -> float:
    """Mean wall-clock of fn() amortized over a pipelined batch.

    Dispatch latency to the device is amortized by enqueueing ``iters``
    calls back-to-back and materializing only the final result on the
    host — the same way a production search service pipelines query
    batches. Per-call blocking would measure round-trip latency, not
    throughput.

    CAVEAT: repeated *identical* calls can be served from a result cache
    and unfetched outputs may be elided, so this can over-report. Prefer
    ``scan_qps_time`` (distinct inputs, on-device loop, two-point timing)
    when the workload can be expressed as ``step(queries)``.
    """
    out = None
    for _ in range(warmup):
        out = fn()
    # graft-lint: allow-host-sync bench timing — the fetch IS the measurement fence
    np.asarray(jax.tree_util.tree_leaves(out)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    # graft-lint: allow-host-sync bench timing — the fetch IS the measurement fence
    np.asarray(jax.tree_util.tree_leaves(out)[0])  # fetch forces completion
    return (time.perf_counter() - t0) / iters


def scan_qps_time(search_step, queries, n1: int = 3, n2: int = 13,
                  operands=None) -> float:
    """Trustworthy per-iteration seconds of ``search_step(q) -> (d, i)``
    (or ``search_step(q, operands)`` when ``operands`` is given).

    Runs N iterations of the step *inside one jitted program* (lax.scan),
    each on a rolled — hence distinct — query batch, folding every output
    into a returned checksum so no iteration can be cached or elided.
    Times the program at two iteration counts and reports
    (T2-T1)/(N2-N1), cancelling constant dispatch/RTT/fetch overhead.
    This is steady-state on-device throughput, robust against result
    caching.

    Pass the index through ``operands`` (any pytree — the Index
    dataclasses are registered pytrees): closure-captured arrays would be
    baked into the HLO as constants, which blows up remote compilation
    for GB-scale indexes.
    """
    import jax.numpy as jnp

    def runner(iters):
        @jax.jit
        def run(qs, salt, ops):
            def body(carry, i):
                q = jnp.roll(qs, i + 1 + salt, axis=0)
                if ops is None:
                    d, idx = search_step(q)
                else:
                    d, idx = search_step(q, ops)
                return carry + d.sum() + idx.sum(), None

            acc, _ = jax.lax.scan(body, jnp.float32(0.0), jnp.arange(iters))
            return acc

        return run

    # every executed (program, input) pair is unique — the `salt` operand
    # changes each call so a platform-level result cache can never serve a
    # timed execution from the warmup (or a previous timed) run
    r1, r2 = runner(n1), runner(n2)
    # graft-lint: allow-host-sync bench timing — sync fences bracket each timed run
    _ = float(r1(queries, jnp.int32(0), operands))  # compile + warm both
    # graft-lint: allow-host-sync bench timing
    _ = float(r2(queries, jnp.int32(1), operands))
    t0 = time.perf_counter()
    # graft-lint: allow-host-sync bench timing
    _ = float(r1(queries, jnp.int32(2), operands))
    t1 = time.perf_counter()
    # graft-lint: allow-host-sync bench timing
    _ = float(r2(queries, jnp.int32(3), operands))
    t2 = time.perf_counter()
    per_iter = ((t2 - t1) - (t1 - t0)) / (n2 - n1)
    if per_iter <= 0:
        # fast workloads on a local backend can be noise-dominated; fall
        # back to the overhead-inclusive total (never over-reports QPS)
        t3 = time.perf_counter()
        # graft-lint: allow-host-sync bench timing
        _ = float(r2(queries, jnp.int32(4), operands))
        per_iter = (time.perf_counter() - t3) / n2
    return per_iter


# ---------------------------------------------------------------------------
# Roofline (ROADMAP item 1: "fast as the hardware allows" as a NUMBER)
# ---------------------------------------------------------------------------

# Peak throughput per chip, keyed by ``device_kind`` as JAX reports it.
# f32-carried matmuls run multi-pass on the MXU, so the bf16 peak is the
# honest denominator for the bf16-operand hot paths. A device missing
# from this table is an error: there is no default peak, and a CPU run
# gets no roofline share at all.
PEAK_SPECS = {
    "TPU v5 lite": {
        "flops_peak": 197.0e12, "hbm_gbps": 819.0,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip"},
}


def peak_spec(device_kind: Optional[str] = None) -> dict:
    """The peak row for ``device_kind`` (default: the first device's)."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAK_SPECS:
        raise ValueError(
            f"no peak spec for device_kind {device_kind!r}; known: "
            f"{sorted(PEAK_SPECS)}")
    return PEAK_SPECS[device_kind]


def roofline(bytes_moved: float, flops: float, seconds: float,
             device_kind: Optional[str] = None) -> dict:
    """One roofline row: achieved GB/s + GFLOP/s against the device's
    peak spec, which ceiling binds, and the achieved fraction of that
    ceiling (docs/kernels.md §roofline).

    ``bytes_moved``/``flops`` are the op's COST MODEL (ideal HBM traffic
    and arithmetic of the algorithm as implemented); ``seconds`` the
    measured wall time. ``peak_fraction`` is achieved/peak on the
    BINDING axis: ops whose arithmetic intensity (flops/byte) clears
    the ridge point are scored against the FLOP/s peak, the rest
    against HBM bandwidth — so 1.0 always means "the hardware can do no
    better"."""
    spec = peak_spec(device_kind)
    seconds = max(float(seconds), 1e-12)
    gbps = bytes_moved / seconds / 1e9
    gflops = flops / seconds / 1e9
    intensity = flops / max(bytes_moved, 1.0)
    ridge = spec["flops_peak"] / (spec["hbm_gbps"] * 1e9)
    bound = "compute" if intensity >= ridge else "memory"
    frac = (gflops * 1e9 / spec["flops_peak"] if bound == "compute"
            else gbps / spec["hbm_gbps"])
    return {
        "bytes": int(bytes_moved),
        "flops": int(flops),
        "gbps": round(gbps, 2),
        "gflops": round(gflops, 2),
        "intensity_flops_per_byte": round(intensity, 3),
        "ridge_flops_per_byte": round(ridge, 3),
        "bound": bound,
        "peak_fraction": round(frac, 4),
        "peak_source": spec["source"],
    }


def require_tpu():
    """The first device, which must be a TPU: measurement paths fail
    rather than time the CPU. Checked in-process — a child probe would
    hold the chip this process needs."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0] is {dev.platform} "
                         f"({dev.device_kind})")
    return dev


def latency_percentiles(search_step, queries, batch: int,
                        n_calls: int = 50, operands=None) -> dict:
    """Per-call latency distribution for small-batch serving (the
    reference's `--mode latency` measurement,
    docs/source/raft_ann_benchmarks.md:240-254): each timed call
    dispatches ONE ``batch``-sized query slice and blocks for its
    result — end-to-end serving latency including dispatch, which is
    what a latency SLO sees (unlike scan-chained throughput timing,
    which amortizes dispatch away). Every call — warmup included —
    dispatches a DISTINCT row rotation of the pool (strided slicing
    degenerates to a repeated slice whenever (m - batch) divides batch,
    m == batch included), defeating platform result caching for any
    n_calls < m. Rotation is materialized before the clock starts.
    Returns seconds: {p50, p95, mean, batch, n_calls}.
    """
    import jax
    import jax.numpy as jnp

    m = queries.shape[0]
    if m < batch:
        raise ValueError(f"need >= {batch} queries, got {m}")
    jitted = jax.jit(
        search_step if operands is None
        else functools.partial(search_step, ops=operands)
    )
    # warmup/compile on rotation n_calls+1 — outside the timed rotation
    # set {1..n_calls}, so no timed call can be served its cached result
    qs = jnp.roll(queries, n_calls + 1, axis=0)[:batch]
    jax.block_until_ready(jitted(qs))
    times = []
    for c in range(n_calls):
        q = jnp.roll(queries, c + 1, axis=0)[:batch]
        q = jax.block_until_ready(q)   # keep rotation out of the timed call
        t0 = time.perf_counter()
        out = jitted(q)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    arr = np.sort(np.asarray(times))
    return {
        "p50": float(np.percentile(arr, 50)),
        "p95": float(np.percentile(arr, 95)),
        "mean": float(arr.mean()),
        "batch": batch,
        "n_calls": n_calls,
    }


def run_case(
    name: str,
    build_fn: Callable[[], Any],
    search_fn: Callable[[Any], tuple],
    true_idx: np.ndarray,
    k: int,
    n_queries: int,
    iters: int = 10,
    extra: Optional[dict] = None,
) -> BenchResult:
    t0 = time.perf_counter()
    index = build_fn()
    # block on every array the build produced (norms, list structures, ...),
    # not just the dataset, so build_s covers the whole build
    leaves = [
        v for v in vars(index).values() if isinstance(v, jax.Array)
    ] if hasattr(index, "__dict__") else [index]
    jax.block_until_ready(leaves)
    build_s = time.perf_counter() - t0

    dist, idx = search_fn(index)
    jax.block_until_ready(idx)
    recall = compute_recall(np.asarray(idx), true_idx)
    search_s = time_fn(lambda: search_fn(index)[1], iters=iters)
    return BenchResult(
        name=name,
        build_s=build_s,
        search_s=search_s,
        qps=n_queries / search_s,
        recall=recall,
        k=k,
        n_queries=n_queries,
        extra=extra or {},
    )


def write_obs_snapshot(path: str) -> str:
    """Write the graft-scope metrics snapshot (docs/observability.md) as
    a JSON sidecar next to a bench artifact — every ``BENCH_*.json`` run
    with ``--obs-snapshot`` gains the dispatch-winner counts, per-algo
    latency histograms, ladder/retry counters, and device memory gauges
    that explain its headline numbers. Returns ``path``."""
    from raft_tpu import obs

    return obs.write_snapshot(path)


def export_csv(results: List[BenchResult], path: str) -> None:
    """gbench-JSON→CSV analog (raft-ann-bench data_export)."""
    import csv

    rows = [r.row() for r in results]
    if not rows:
        return
    keys = sorted({k for r in rows for k in r})
    with open(path, "w", newline="") as fp:
        w = csv.DictWriter(fp, fieldnames=keys)
        w.writeheader()
        w.writerows(rows)


def pareto_frontier(results: List[BenchResult]) -> List[BenchResult]:
    """Recall-vs-QPS Pareto frontier (raft-ann-bench plot's frontier logic)."""
    frontier: List[BenchResult] = []
    best_qps = -1.0
    for r in sorted(results, key=lambda r: (-r.recall, -r.qps)):
        if r.qps > best_qps:
            frontier.append(r)
            best_qps = r.qps
    return frontier
