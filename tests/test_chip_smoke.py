"""chip_smoke.py's phases, run tiny on the CPU so the script cannot rot
between chip runs. ``main()`` refuses the CPU by design, so these call
the phase functions directly; the expected-TPU-kernel checks are off
(``tpu=False``) because the CPU takes the XLA paths."""

import importlib.util
import os

import jax
import numpy as np
import pytest

from oracles import exact_knn_blocked, naive_knn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from raft_tpu import obs

    was = obs.mode()
    obs.set_mode("on")            # main() does this; the phases read
    yield mod                     # the tuning.dispatch counters
    obs.set_mode(was)


@pytest.fixture(scope="module")
def sift(smoke):
    x, q = smoke.sift_like(4096, 64, seed=0)
    oracle = exact_knn_blocked(np.asarray(q[:32]), x, smoke.K, block=1000)
    return x, q, oracle


def test_blocked_oracle_matches_naive(rng):
    x = rng.standard_normal((700, 8)).astype(np.float32)
    q = rng.standard_normal((5, 8)).astype(np.float32)
    for metric in ("sqeuclidean", "inner_product"):
        d, i = exact_knn_blocked(q, x, 7, metric, block=128)
        d0, i0 = naive_knn(q, x, 7, metric)
        np.testing.assert_array_equal(i, i0)
        np.testing.assert_allclose(d, d0)


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) == 1
    assert "needs 1 TPU device(s)" in capsys.readouterr().out


def test_brute_force_phase(smoke, sift):
    x, q, oracle = sift
    row = smoke.phase_brute_force(x, q, 32, oracle, tpu=False)
    assert row["recall_at_10"] == 1.0
    assert row["worst_tie_gap"] <= smoke.TIE_TOL
    assert row["smoke_not_benchmark"]["search_s"] > 0


def test_tie_gap_flags_wrong_ids(smoke, sift):
    x, q, (od, oi) = sift
    assert smoke.tie_gap(oi, od, q[:32], x, "sqeuclidean") <= smoke.TIE_TOL
    wrong = np.roll(oi, 1, axis=0)
    assert smoke.tie_gap(wrong, od, q[:32], x, "sqeuclidean") > 1e-3
    assert smoke.tie_gap(np.full_like(oi, -1), od, q[:32], x,
                         "sqeuclidean") == np.inf


def test_phase_prints_its_line_then_fails(smoke, sift, capsys):
    x, q, oracle = sift
    with pytest.raises(smoke.SmokeFailure, match="ivf_flat: recall@10"):
        smoke.phase_ivf_flat(x, q, 32, oracle, n_lists=16, n_probes=1,
                             floor=1.01, tpu=False)
    assert '"phase": "ivf_flat"' in capsys.readouterr().out


def test_ivf_flat_phase(smoke, sift):
    x, q, oracle = sift
    row = smoke.phase_ivf_flat(x, q, 32, oracle, n_lists=16, n_probes=8,
                               tpu=False)
    assert row["dispatch"]["ivf_scan"] == ["xla"]


def test_cagra_phase(smoke, sift):
    x, q, oracle = sift
    row = smoke.phase_cagra(x, q, 32, oracle, tpu=False)
    assert row["graph_degree"] == 32


def test_serve_phase(smoke, sift):
    x, q, _ = sift
    row = smoke.phase_serve(x, q, n_lists=16, n_probes=8, requests=12,
                            max_rows=8, tpu=False)
    assert row["requests"] == 12


def test_ivf_pq_phase(smoke):
    x, q = smoke.deep_like(8192, 64, seed=1, block=3000)
    assert x.shape == (8192, 96)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=1), 1.0,
                               rtol=1e-5)
    oracle = exact_knn_blocked(np.asarray(q[:32]), x, smoke.K)
    smoke.phase_ivf_pq(x, q, 32, oracle, n_lists=16, n_probes=8,
                       batch_size=3000, floor=0.9, tpu=False)


def test_sharded_phase(smoke):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    x, q = smoke.deep_like(8192, 16, seed=2,
                           sharding=NamedSharding(mesh, P("shard", None)))
    assert len(x.sharding.device_set) == 4
    oracle = exact_knn_blocked(np.asarray(q), x, smoke.K, "inner_product")
    short = smoke.shortlist_oracle(q, x, mesh, smoke.K, width=32, chunk=512)
    np.testing.assert_array_equal(short[1], oracle[1])
    np.testing.assert_allclose(short[0], oracle[0], rtol=1e-12)
    row = smoke.phase_sharded(x, q, short, mesh, n_lists=64, n_probes=32,
                              tpu=False)
    assert row["chips"] == 4
