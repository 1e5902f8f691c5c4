"""The benchmark's yardstick on the CPU at tiny sizes: the reference, the
control, the ivf_scan cost model, the peaks, and the command's refusal
to run without an accelerator. The reference and the control run on rows
on one device and on rows sharded across four of the host's devices, as
a four-chip cell holds them, and give the same answers."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import data, harness, peaks, reference
from benchtree import REPO

sys.path.insert(0, os.path.join(REPO, "tests"))
import oracles  # noqa: E402


@pytest.fixture(scope="module")
def small():
    x, q = data.generate({"rows": 6000, "dim": 32, "queries": 200,
                          "data_seed": 1, "intrinsic_dim": 8}, 3)
    return x, q


def _on_chips(x, chips: int):
    """``x`` as a cell on ``chips`` chips holds it: on one device, or
    sharded by row across a mesh of ``chips`` devices."""
    if chips == 1:
        return x
    mesh = Mesh(np.array(jax.devices()[:chips]), (harness.MESH_AXIS,))
    return jax.device_put(x, NamedSharding(mesh, P(harness.MESH_AXIS, None)))


def test_seed_draws_the_queries_of_one_fixed_dataset():
    spec = {"rows": 64, "dim": 8, "queries": 4, "data_seed": 7,
            "intrinsic_dim": 4}
    xa, a = data.generate(spec, 2**33 + 1)
    xb, b = data.generate(spec, 1)
    _, c = data.generate(spec, 2**33 + 1)
    assert np.array_equal(np.asarray(xa), np.asarray(xb))
    assert np.array_equal(np.asarray(a), np.asarray(c))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    xc, _ = data.generate(dict(spec, data_seed=8), 1)
    assert not np.array_equal(np.asarray(xa), np.asarray(xc))


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("width", [16, 6000])
def test_reference_equals_blocked_oracle(small, width, chips):
    x, q = small
    d, i, _ = reference.exact_knn(q, _on_chips(x, chips), 10, width=width,
                                  chunk=1000, query_block=64)
    od, oi = oracles.exact_knn_blocked(np.asarray(q), x, 10)
    assert np.array_equal(i, oi)
    np.testing.assert_allclose(d, od, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("chips", [1, 4])
def test_reference_redoes_unproven_queries(small, chips):
    x, q = small
    # a shortlist as long as k proves nothing: every query is redone in
    # the direct form, and the answer is still exact
    d, i, redone = reference.exact_knn(q[:20], _on_chips(x, chips), 10,
                                       width=10, chunk=1000)
    od, oi = oracles.exact_knn_blocked(np.asarray(q[:20]), x, 10)
    assert redone > 0
    assert np.array_equal(i, oi)
    np.testing.assert_allclose(d, od, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("chips", [1, 4])
def test_judge_counts_hits_once_and_flags_wrong_distances(small, chips):
    x, q = small
    xc = _on_chips(x, chips)
    _, gt, _ = reference.exact_knn(q, xc, 10, chunk=1000)
    qidx = np.arange(q.shape[0])
    x64, q64 = np.asarray(x, np.float64), np.asarray(q, np.float64)
    true_d = ((x64[gt] - q64[:, None, :]) ** 2).sum(2)
    good = reference.judge(q, xc, gt, qidx, gt, true_d)
    assert good["recall"] == 1.0 and good["dist_err"] < 1e-6
    dup = np.repeat(gt[:, :1], 10, axis=1)
    assert reference.judge(q, xc, gt, qidx, dup,
                           true_d[:, :1].repeat(10, 1))["recall"] == 0.1
    bad = reference.judge(q, xc, gt, qidx, gt, true_d * 1.01)
    assert bad["dist_err"] > 100 * max(good["dist_err"], 1e-7)
    nan = true_d.copy()
    nan[7, 3] = np.nan
    assert reference.judge(q, xc, gt, qidx, gt, nan)["dist_err"] == np.inf
    # an id past either end, and a missing one (-1), read as on one device
    odd = gt.copy()
    odd[3, 2], odd[5, 1] = x.shape[0] + 7, -1
    for ids in (gt, odd):
        for d in (true_d, true_d * 1.01):
            assert (reference.judge(q, xc, gt, qidx, ids, d)
                    == reference.judge(q, x, gt, qidx, ids, d))


@pytest.mark.parametrize("chips", [1, 4])
def test_control_reads_worse_than_the_reference(small, chips):
    x, q = small
    xc = _on_chips(x, chips)
    _, gt, _ = reference.exact_knn(q, xc, 10, chunk=1000)
    d, i = reference.lowp_search(q, xc, 10, "float8_e4m3fn", chunk=1000)
    j = reference.judge(q, xc, gt, np.arange(q.shape[0]), np.asarray(i),
                        np.asarray(d))
    assert j["recall"] < 0.9 and j["dist_err"] > 1e-3
    d1, i1 = reference.lowp_search(q, x, 10, "float8_e4m3fn", chunk=1000)
    assert np.array_equal(np.asarray(i), np.asarray(i1))
    assert np.array_equal(np.asarray(d), np.asarray(d1))


def _cost_module():
    import importlib.util

    path = os.path.join(REPO, "benchmark", "costs", "ivf_scan.py")
    spec = importlib.util.spec_from_file_location("cost_ivf_scan", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ivf_scan_cost_is_the_probed_rows(small):
    from raft_tpu.neighbors import ivf_flat

    cost = _cost_module()
    x, q = small
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
    layout = _flat_layout(index, n_probes=4)
    pr = cost.probes(q, layout["centers"], 4)
    sizes = np.asarray(index.list_sizes)
    flops = sum(2 * 32 * sizes[l] for row in pr for l in row)
    touched = set(pr.reshape(-1).tolist())
    bytes_ = sum(sizes[l] for l in touched) * (32 * 4 + 8)
    c = cost.batch_cost(layout, pr)
    assert c == {"flops": float(flops), "bytes": float(bytes_)}
    # the probes are the exact nearest centers
    cd = ((np.asarray(q, np.float64)[:, None, :]
           - np.asarray(index.centers, np.float64)[None]) ** 2).sum(2)
    assert np.array_equal(np.sort(pr, 1), np.sort(np.argsort(cd, 1)[:, :4], 1))


@pytest.mark.parametrize("extract", ["exact", "binned", "binned_deep", "fold"])
def test_ivf_scan_cost_does_not_depend_on_the_extraction_arm(
        small, extract, monkeypatch):
    """Whichever arm the dispatch picks, the search runs and the cost
    model's count (shapes, list sizes and probes only) is unchanged."""
    from raft_tpu import tuning
    from raft_tpu.neighbors import ivf_flat

    cost = _cost_module()
    x, q = small
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
    want = cost.batch_cost(_flat_layout(index, 4),
                           cost.probes(q, index.centers, 4))
    real = tuning.choose
    monkeypatch.setattr(tuning, "choose", lambda op, key, cands, fb: (
        extract if op == "ivf_scan_extract" else real(op, key, cands, fb)))
    sp = ivf_flat.SearchParams(n_probes=4, scan_impl="pallas_interpret")
    _, ids = ivf_flat.search(sp, index, q[:16], 10)
    assert np.all(np.asarray(ids) >= 0)
    layout = _flat_layout(index, 4)
    assert cost.batch_cost(layout, cost.probes(q, index.centers, 4)) == want


def _flat_layout(index, n_probes):
    import importlib.util

    path = os.path.join(REPO, "benchmark", "entries", "ivf_flat.py")
    spec = importlib.util.spec_from_file_location("entry_ivf_flat", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scan_layout({"n_probes": n_probes}, index)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peak spec"):
        peaks.roofline_share(1e9, 1e9, 1.0, "TPU v99")
    r = peaks.roofline_share(197e12, 1.0, 2.0, "TPU v5 lite")
    assert r["percent"] == pytest.approx(50.0) and r["bound"] == "compute"


def test_command_exits_nonzero_without_an_accelerator(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sift1m-ivf_flat.batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_command_exits_nonzero_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sift1m-ivf_flat.batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
