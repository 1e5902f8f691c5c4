"""A tiny benchmark tree for CPU tests: the repository's benchmark files,
tiny configurations and mixes, and a BENCHMARK.json naming them."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_FLAT = {
    "name": "tiny-ivf_flat", "entry": "ivf_flat", "rows": 4096, "dim": 32,
    "queries": 256, "data_seed": 1, "intrinsic_dim": 8, "unit_norm": False,
    "metric": "sqeuclidean", "k": 10, "n_lists": 16, "n_probes": 8,
    "compute_dtype": "bf16",
    "control_dtype": "float8_e4m3fn",
    "limits": {"recall_short": 0.2, "dist_err": 0.01},
}
TINY_BATCH = {"kind": "batch", "batch": 256, "rotations": 4}
TINY_OPEN = {"kind": "open_loop", "rate_qps": 100, "drain_s": 30,
             "server": {"max_batch_rows": 16, "max_wait_ms": 2.0,
                        "max_queue_rows": 1 << 20}}


def make_tree(tmp, configs=None, traffic=None, workloads=None,
              per_layer=()) -> str:
    """Copy the benchmark's files under ``tmp`` and add ``configs``
    ({name: dict}) and ``traffic`` ({name: dict}); ``workloads`` is a list
    of (name, config, traffic) or (name, config, traffic, chips), on one
    chip where the chips are not given. The end-to-end metrics are the
    repository's, with their cell lists pointed at the new cells."""
    tree = str(tmp)
    root = os.path.join(tree, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    configs = configs or {"tiny-ivf_flat": TINY_FLAT}
    traffic = traffic or {"tiny_batch": TINY_BATCH}
    workloads = workloads or [("tiny-ivf_flat.batch", "tiny-ivf_flat",
                               "tiny_batch")]
    for name, cfg in configs.items():
        with open(os.path.join(root, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
    for name, mix in traffic.items():
        with open(os.path.join(root, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["paths"] = ["benchmark"]
    spec["configs"] = [{"name": n, "source": "tiny", "reduced": [],
                        "file": f"benchmark/configs/{n}.json", "why": "test"}
                       for n in configs]
    workloads = [tuple(w) + (1,) * (4 - len(w)) for w in workloads]
    spec["workloads"] = [{"name": n, "config": c, "traffic": t,
                          "chips": chips, "why": "test"}
                         for n, c, t, chips in workloads]
    for m in spec["end_to_end"]:
        if "workloads" in m:
            # a metric of batch cells stays with batch cells, and so on
            old = m["workloads"]
            m["workloads"] = [n for n, _, t, _ in workloads
                              if any(_kind_of(o) == traffic[t]["kind"]
                                     for o in old)]
    spec["per_layer"] = list(per_layer)
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tree


def _kind_of(workload: str) -> str:
    return "open_loop" if workload.endswith(".serve") else "batch"
