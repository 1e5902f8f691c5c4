#!/usr/bin/env python
"""Small-batch latency table for the three flagship indexes (VERDICT r3
#5): per-call p50/p95 at batch 1 and 10 on the real chip, the analog of
the reference's `--mode latency` runs (raft_ann_benchmarks.md:240-254).

Also settles the multi-CTA question empirically: the reference ships a
multi-CTA-per-query kernel family so ONE query can use many SMs. On TPU
the whole batch is one XLA program on one core — if batch-1 latency is
dominated by the same fixed cost as batch-10 (dispatch + the sequential
beam/scan structure), intra-query parallelism has nothing to win and the
latency lever is fewer/fused steps instead. The printed fixed-cost share
is that argument, measured.

Run: python scripts/latency_table.py [out.json]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from bench import _sift_like as sift_like
from raft_tpu.bench.harness import latency_percentiles


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else "LATENCY_r05.json"
    n, d, k = 1_000_000, 128, 10
    print(f"devices: {jax.devices()}", flush=True)
    x = jax.device_put(sift_like(n, d, seed=1))
    q = jax.device_put(sift_like(4096, d, seed=2))
    jax.block_until_ready(x)

    rows = {}

    from raft_tpu.neighbors import cagra, ivf_flat, ivf_pq

    t0 = time.time()
    fi = ivf_flat.build(ivf_flat.IndexParams(n_lists=1024), x)
    jax.block_until_ready(fi.list_sizes)
    print(f"ivf_flat build {time.time()-t0:.0f}s", flush=True)
    fsp = ivf_flat.SearchParams(n_probes=64)
    rows["ivf_flat"] = {
        f"b{b}": latency_percentiles(
            lambda qq, ops: ivf_flat.search(fsp, ops, qq, k), q, b,
            operands=fi)
        for b in (1, 10)
    }
    print("ivf_flat", rows["ivf_flat"], flush=True)

    t0 = time.time()
    pi = ivf_pq.build(
        ivf_pq.IndexParams(n_lists=1024, pq_dim=64, pq_bits=8,
                           kmeans_trainset_fraction=0.2), x)
    jax.block_until_ready(pi.list_sizes)
    print(f"ivf_pq build {time.time()-t0:.0f}s", flush=True)
    psp = ivf_pq.SearchParams(n_probes=64)
    rows["ivf_pq"] = {
        f"b{b}": latency_percentiles(
            lambda qq, ops: ivf_pq.search(psp, ops, qq, k), q, b,
            operands=pi)
        for b in (1, 10)
    }
    print("ivf_pq", rows["ivf_pq"], flush=True)

    t0 = time.time()
    ci = cagra.build(cagra.IndexParams(graph_degree=32,
                                       intermediate_graph_degree=64), x)
    jax.block_until_ready(ci.graph)
    print(f"cagra build {time.time()-t0:.0f}s", flush=True)
    csp = cagra.SearchParams(n_seeds=64, max_iterations=15)
    rows["cagra"] = {
        f"b{b}": latency_percentiles(
            lambda qq, ops: cagra.search(csp, ops, qq, k), q, b,
            operands=ci)
        for b in (1, 10)
    }
    print("cagra", rows["cagra"], flush=True)

    # the multi-CTA argument: share of batch-1 latency that is fixed cost
    for name, r in rows.items():
        fixed = r["b1"]["p50"] / max(r["b10"]["p50"], 1e-9)
        r["b1_over_b10_p50"] = round(fixed, 3)

    res = {"config": {"n": n, "dim": d, "k": k, "chip": jax.devices()[0].device_kind},
           "latency_s": rows}
    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
