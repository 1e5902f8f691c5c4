"""The ``sharded_ivf_pq`` entry on four of the host's CPU devices: a tiny
configuration of the four-chip IVF-PQ deployment runs through the
harness, reads ``correct``, and reports its per-layer metrics when
traced; and the collective share's reader on a timeline with known
answers."""

import os
import types

import pytest

from benchmark import harness, trace
from benchtree import REPO, TINY_BATCH, make_tree
from test_bench_sharded import SHARDED_ROWS

CELL = "tiny-sharded_pq.batch"
LAYER = "sharded search (comms/sharded.py)"

TINY_SHARDED_PQ = {
    "name": "tiny-sharded_pq", "entry": "sharded_ivf_pq",
    "rows": SHARDED_ROWS, "dim": 8, "queries": 256, "data_seed": 4,
    "intrinsic_dim": 4, "unit_norm": True, "metric": "sqeuclidean",
    "k": 10, "n_lists": 1024, "n_probes": 32, "pq_dim": 4, "pq_bits": 8,
    "refine_ratio": 10, "kmeans_trainset_fraction": 0.01,
    "control_dtype": "bfloat16",
    "limits": {"recall_short": 0.05, "dist_err": 5e-05},
}


def _per_layer(name, source):
    return {"name": name, "unit": "fraction", "better": "lower",
            "source": source, "layer": LAYER, "moves": "qps",
            "workloads": [CELL]}


def test_the_sharded_ivf_pq_cell_runs_and_reports_its_layer(tmp_path):
    per_layer = [_per_layer("collective_share.batch", "device_trace"),
                 _per_layer("sharded.program_hit_share", "program_counter")]
    tree = make_tree(tmp_path, configs={"tiny-sharded_pq": TINY_SHARDED_PQ},
                     traffic={"tiny_batch": TINY_BATCH},
                     workloads=[(CELL, "tiny-sharded_pq", "tiny_batch", 4)],
                     per_layer=per_layer)
    r = harness.run_cell(tree, CELL, 2**33 + 27, 1.0, trace_on=True,
                         require_accelerator=False)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] % 256 == 0
    assert len(r["_info"]["peak_bytes_per_device"]) == 4
    # every search of the window found its program held
    assert r["metrics"]["sharded.program_hit_share"]["value"] == 1.0
    assert r["_info"]["window_programs_compiled"] == 0
    # the CPU backend's trace has no device ops, so the collective share
    # reads nothing here; on a chip it reads the merge and the rerank
    assert "collective_share.batch" not in r["metrics"]


def _read(timeline):
    mod = harness._load_module(os.path.join(
        REPO, "benchmark", "metrics", "collective_share.batch.py"))
    return mod.read(types.SimpleNamespace(
        timelines={} if timeline is None else {"bench.window": timeline}))


def _timeline(ops):
    host = [(0.0, 100.0, "bench.window")]
    return trace.Timeline({dev: list(evs) for dev, evs in ops.items()},
                          host)


def test_collective_share_is_collective_time_over_busy_time():
    ops = {"/device:TPU:0": [(0.0, 10.0, "%fusion.1 = f32[8] fusion()"),
                             (10.0, 14.0, "%all-gather.2 = f32[8] "
                                          "all-gather()"),
                             (20.0, 22.0, "%all-reduce-start.1 = f32[8] "
                                          "all-reduce-start()")],
           "/device:TPU:1": [(0.0, 12.0, "%fusion.1 = f32[8] fusion()"),
                             (12.0, 14.0, "%all-reduce.3 = f32[8] "
                                          "all-reduce()")]}
    # chip 0: 6 of 16 busy; chip 1: 2 of 14: (6 + 2) / 2 over (16 + 14) / 2
    assert _read(_timeline(ops)) == pytest.approx(8.0 / 30.0)


@pytest.mark.parametrize("ops", [None, {},
                                 {"/device:TPU:0": [(0.0, 5.0, "%fusion.1 "
                                                    "= f32[8] fusion()")]}])
def test_collective_share_reads_nothing_without_a_collective(ops):
    """An untraced run, a window with no device op, or one with no
    collective."""
    assert _read(None if ops is None else _timeline(ops)) is None
