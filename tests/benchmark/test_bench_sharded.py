"""A cell on four chips, on four of the host's CPU devices: its rows are
made sharded by row, bit for bit the one-device rows; a cell added by
files alone runs through the harness and reads ``correct``; and the same
cell with the exchange between chips left out reads incorrect."""

import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmark import data, harness, reference
from benchtree import TINY_BATCH, TINY_FLAT, make_tree

# the smallest kind of row count whose block (the largest divisor up to
# 2**20) divides a quarter: 4 x 655,360 rows, one block per chip
SHARDED_ROWS = 4 * 655_360

TINY_SHARDED = dict(TINY_FLAT, name="tiny-sharded", entry="sharded_knn",
                    rows=SHARDED_ROWS, dim=8, intrinsic_dim=4)

ENTRY = '''"""Exact search over rows sharded across the cell's chips
(``raft_tpu.comms.sharded_knn``), the mesh read from the rows."""

ALGO = "sharded_knn"


def build(cfg, x):
    return None


def searcher(cfg, index, x):
    from raft_tpu.comms.sharded import sharded_knn

    k = int(cfg["k"])
    return lambda q: sharded_knn(q, x, k, x.sharding.mesh)
'''


def _mesh(chips=4):
    return Mesh(np.array(jax.devices()[:chips]), (harness.MESH_AXIS,))


@pytest.mark.parametrize("unit_norm", [False, True])
def test_sharded_rows_are_the_one_device_rows(unit_norm):
    spec = {"rows": SHARDED_ROWS, "dim": 8, "queries": 16, "data_seed": 3,
            "intrinsic_dim": 4, "unit_norm": unit_norm}
    x1, q1 = data.generate(spec, 2**33 + 5)
    x4, q4 = data.generate(spec, 2**33 + 5, _mesh())
    shards = x4.addressable_shards
    assert len({s.device for s in shards}) == 4
    assert all(s.data.shape == (SHARDED_ROWS // 4, 8) for s in shards)
    assert np.array_equal(np.asarray(x1), np.asarray(x4))
    # the queries: the same pool, a copy on every chip
    assert len(q4.sharding.device_set) == 4
    assert q4.sharding.is_fully_replicated
    assert np.array_equal(np.asarray(q1), np.asarray(q4))


def test_a_block_that_straddles_two_chips_is_refused():
    spec = {"rows": 6000, "dim": 8, "queries": 4, "data_seed": 1,
            "intrinsic_dim": 4}
    with pytest.raises(ValueError, match="does not divide a chip's share"):
        data.generate(spec, 1, _mesh())


@pytest.mark.parametrize("chunk", [8, 40, 200])
def test_rows_gathered_a_chunk_at_a_time_are_the_rows(chunk):
    x = np.arange(200 * 3, dtype=np.float32).reshape(200, 3)
    ids = np.array([[0, 199, 7], [8, 39, 40], [41, 120, 160]], np.int32)
    got = reference._gather_rows(jax.numpy.asarray(x), ids, chunk)
    assert np.array_equal(np.asarray(got), x[ids])


def _sharded_tree(tmp_path):
    tree = make_tree(tmp_path, configs={"tiny-sharded": TINY_SHARDED},
                     traffic={"tiny_batch": TINY_BATCH},
                     workloads=[("tiny-sharded.batch", "tiny-sharded",
                                 "tiny_batch", 4)])
    with open(os.path.join(tree, "benchmark", "entries",
                           "sharded_knn.py"), "w") as f:
        f.write(ENTRY)
    return tree


def test_a_four_chip_cell_added_by_files_alone_runs(tmp_path):
    """A configuration, an entry over ``sharded_knn`` and a cell with
    ``chips: 4`` are files; the harness builds the mesh, makes the rows
    sharded, and judges the answers per chip."""
    tree = _sharded_tree(tmp_path)
    r = harness.run_cell(tree, "tiny-sharded.batch", 11, 1.0,
                         require_accelerator=False)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"qps", "recall_at_10", "build_s",
                                 "setup_s"}
    assert r["metrics"]["recall_at_10"]["value"] > 0.9
    assert r["failed"] == 0 and r["attempted"] % 256 == 0
    assert len(r["_info"]["peak_bytes_per_device"]) == 4


def test_a_four_chip_cell_without_the_exchange_reads_incorrect(
        tmp_path, monkeypatch):
    """Each chip's answers merged without the other chips' (the
    all-gather's candidates of chip 0 alone): ``correct`` reads false."""
    from raft_tpu.comms import sharded

    real = sharded.merge_topk

    def local_only(gd, gi, k, select_min):
        return real(gd[:, :k], gi[:, :k], k, select_min)

    monkeypatch.setattr(sharded, "merge_topk", local_only)
    tree = _sharded_tree(tmp_path)
    r = harness.run_cell(tree, "tiny-sharded.batch", 12, 1.0,
                         require_accelerator=False)
    assert r["correct"] is False
    assert r["checks"]["recall_short"]["value"] > 0.5


def test_the_control_on_four_chips_reads_as_on_one(tmp_path):
    """The control in the entry's place judges the same rows alike,
    sharded across four chips or on one."""
    tree = make_tree(tmp_path, configs={"tiny-sharded": TINY_SHARDED},
                     traffic={"tiny_batch": TINY_BATCH},
                     workloads=[("tiny-sharded.one", "tiny-sharded",
                                 "tiny_batch", 1),
                                ("tiny-sharded.four", "tiny-sharded",
                                 "tiny_batch", 4)])
    one, four = (harness.run_cell(tree, f"tiny-sharded.{w}", 13, 0.5,
                                  require_accelerator=False, control=True)
                 for w in ("one", "four"))
    assert four["checks"] == one["checks"]
    assert four["checks"]["dist_err"]["value"] > 0
