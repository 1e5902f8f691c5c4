#!/usr/bin/env python
"""Measure the select_k dispatch crossover: hardware lax.top_k vs the
tournament network (VERDICT r4 #4: >= 2x at n=256k, k in {1024, 4096}).
Emits the select_k crossover table.

Run: python scripts/select_crossover.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.bench.harness import scan_qps_time
from raft_tpu.matrix.select_k import _select_k, _tournament_topk


def time_impl(fn, x, k):
    # roll the row axis so every scan iteration sees distinct data
    def step(xx, _ops):
        v, i = fn(xx, k, True)
        return v, i

    return scan_qps_time(step, x, n1=2, n2=8, operands=None)


def main():
    print(f"devices: {jax.devices()}", flush=True)
    rows = []
    key = jax.random.PRNGKey(0)
    for n, m in ((262_144, 64), (65_536, 256)):
        x = jax.random.normal(key, (m, n), jnp.float32)
        jax.block_until_ready(x)
        for k in (256, 1024, 4096):
            if k * 8 > n:
                continue
            t_top = time_impl(_select_k, x, k)
            t_trn = time_impl(_tournament_topk, x, k)
            rows.append({
                "n": n, "m": m, "k": k,
                "top_k_ms": round(t_top * 1e3, 2),
                "tournament_ms": round(t_trn * 1e3, 2),
                "speedup": round(t_top / t_trn, 2),
            })
            print(rows[-1], flush=True)
    with open("SELECT_CROSSOVER_r05.json", "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
