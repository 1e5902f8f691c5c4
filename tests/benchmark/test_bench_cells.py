"""The harness on the CPU at tiny sizes: a cell added by files alone
runs, and a run whose timed path is broken underneath reads
``correct: false``."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchtree import TINY_BATCH, TINY_FLAT, TINY_OPEN, make_tree

NEW_METRIC = '''"""Batches the window ran (a test metric)."""


def read(run):
    return float(len(run.window["batches"]))
'''


def test_a_cell_added_by_files_alone_runs(tmp_path):
    """A new configuration, traffic mix and per-layer metric are files the
    harness finds by name; no file of the benchmark is edited."""
    cfg = dict(TINY_FLAT, name="tiny-new", n_lists=8, n_probes=4)
    mix = dict(TINY_BATCH, batch=128, rotations=2)
    metric = {"name": "bench.batches_seen", "unit": "batches",
              "better": "higher", "source": "program_counter",
              "layer": "test", "moves": "qps",
              "workloads": ["tiny-new.batch"]}
    tree = make_tree(tmp_path, configs={"tiny-new": cfg},
                     traffic={"tiny_new": mix},
                     workloads=[("tiny-new.batch", "tiny-new", "tiny_new")],
                     per_layer=[metric])
    with open(os.path.join(tree, "benchmark", "metrics",
                           "bench.batches_seen.py"), "w") as f:
        f.write(NEW_METRIC)
    r = harness.run_cell(tree, "tiny-new.batch", 5, 1.0,
                         require_accelerator=False)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"qps", "recall_at_10", "build_s",
                                 "setup_s"}
    assert r["attempted"] % 128 == 0 and r["failed"] == 0
    assert list(r)[-2:] == ["checks", "_info"]
    t = harness.run_cell(tree, "tiny-new.batch", 5, 1.0, trace_on=True,
                         require_accelerator=False)
    assert t["metrics"]["bench.batches_seen"]["value"] >= 1
    assert {"busy_s", "window_s"} <= set(t["device"])
    json.dumps({k: v for k, v in t.items() if k != "_info"})


def _break(monkeypatch, fault):
    """Break the timed path underneath every entry that reaches the
    IVF-Flat search program (``ivf_flat.search`` and ``serve``)."""
    from raft_tpu.neighbors import ivf_flat

    real = ivf_flat._ivf_search
    if fault == "none":
        return

    def broken(*args, **kw):
        d, i = real(*args, **kw)
        if fault == "answer_altered":
            # one answer of each call, altered where it is produced
            i = i.at[0].set(jnp.where(i[0] >= 0, i[0] + 1, i[0]))
        else:
            # half of the batch left out
            half = i.shape[0] // 2
            i = i.at[half:].set(-1)
            d = d.at[half:].set(jnp.inf)
        return d, i

    monkeypatch.setattr(ivf_flat, "_ivf_search", broken)


@pytest.mark.parametrize("cell", ["batch", "serve"])
@pytest.mark.parametrize("fault", ["none", "answer_altered",
                                   "half_left_out"])
def test_a_broken_timed_path_reads_incorrect(tmp_path, monkeypatch, cell,
                                             fault):
    """The sound run reads correct; each fault reads incorrect."""
    tree = make_tree(tmp_path, traffic={"tiny_batch": TINY_BATCH,
                                        "tiny_open": TINY_OPEN},
                     workloads=[("tiny-ivf_flat.batch", "tiny-ivf_flat",
                                 "tiny_batch"),
                                ("tiny-ivf_flat.serve", "tiny-ivf_flat",
                                 "tiny_open")])
    _break(monkeypatch, fault)
    r = harness.run_cell(tree, f"tiny-ivf_flat.{cell}", 7, 1.5,
                         require_accelerator=False)
    assert r["correct"] is (fault == "none"), r["checks"]
    assert np.isfinite([c["value"] for c in r["checks"].values()]).all()


def _break_serving(monkeypatch, fault):
    """Every other single-query request of the window is refused or fails
    (set-up's block requests go through)."""
    from concurrent.futures import Future

    from raft_tpu import serve

    real = serve.Server.submit
    calls = [0]

    def broken(self, queries, k, **kw):
        if np.ndim(queries) != 1:
            return real(self, queries, k, **kw)
        calls[0] += 1
        if calls[0] % 2:
            return real(self, queries, k, **kw)
        if fault == "refused":
            raise serve.Overloaded("refused by the test")
        failed: Future = Future()
        real(self, queries, k, **kw).add_done_callback(
            lambda _f: failed.set_exception(RuntimeError("failed by the test")))
        return failed

    monkeypatch.setattr(serve.Server, "submit", broken)


@pytest.mark.parametrize("fault", ["refused", "failed"])
def test_a_served_request_refused_or_failed_reads_incorrect(
        tmp_path, monkeypatch, fault):
    """Half of the window's requests refused at submit, or failed by their
    future: the answered half is right, and the run reads incorrect."""
    tree = make_tree(tmp_path, traffic={"tiny_open": TINY_OPEN},
                     workloads=[("tiny-ivf_flat.serve", "tiny-ivf_flat",
                                 "tiny_open")])
    _break_serving(monkeypatch, fault)
    r = harness.run_cell(tree, "tiny-ivf_flat.serve", 7, 1.5,
                         require_accelerator=False)
    assert r["correct"] is False
    checks = {name: c["value"] for name, c in r["checks"].items()}
    assert checks["unanswered"] >= r["attempted"] // 2 - 1
    assert checks["recall_short"] <= 0.2 and checks["dist_err"] <= 0.01
