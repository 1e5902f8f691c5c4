"""The served path's per-layer readers: the means of the program's
request-wait, dispatch, device-wait and deliver histograms, and the
device's idle share under the batcher's dispatch spans, on synthetic
snapshots and timelines with known answers, and through a tiny traced
serve cell on the CPU."""

import os
import types

import pytest

from benchmark import harness, trace
from benchtree import REPO, TINY_FLAT, TINY_OPEN, make_tree

INTERVALS = ("serve.request_wait_ms", "serve.dispatch_ms",
             "serve.device_wait_ms", "serve.deliver_ms")


def _reader(name):
    return harness._load_module(
        os.path.join(REPO, "benchmark", "metrics", name + ".py"))


def _run(obs=None, timelines=None):
    return types.SimpleNamespace(obs=obs, timelines=timelines or {})


@pytest.mark.parametrize("name", INTERVALS)
def test_interval_reader_is_the_mean_over_every_label_set(name):
    snap = {"metrics": {name: {"points": [
        {"labels": {"bucket": "16"}, "count": 3, "sum": 12.0},
        {"labels": {"bucket": "32"}, "count": 1, "sum": 8.0}]}}}
    read = _reader(name).read
    assert read(_run(snap)) == pytest.approx(5.0)
    # an untraced run, and a program that records no such series
    assert read(_run(None)) is None
    assert read(_run({"metrics": {"serve.queue_wait_ms": {"points": [
        {"labels": {}, "count": 1, "sum": 1.0}]}}})) is None


def _timeline(devices=1):
    # window 0-10000 ns; the device runs 0-2000, 3000-5000, 9000-12000;
    # the batcher dispatches 1500-3500 (1000 idle), 6000-7000 (1000
    # idle) and, once more from another thread, 6500-6800 (counted
    # once), and 9500-10500 (busy, half outside the window); the load
    # generator's spans cover idle time too and must not count
    ops = [(0.0, 2000.0, "fusion.1"), (3000.0, 5000.0, "scan"),
           (9000.0, 12000.0, "fusion.2")]
    device_ops = {"/device:TPU:0": ops}
    if devices == 2:
        device_ops["/device:TPU:1"] = [(0.0, 10000.0, "scan")]
    host = [(0.0, 10000.0, "bench.window"),
            (1500.0, 3500.0, "serve.dispatch"),
            (6000.0, 7000.0, "serve.dispatch"),
            (6500.0, 6800.0, "serve.dispatch"),
            (9500.0, 10500.0, "serve.dispatch"),
            (2000.0, 2500.0, "serve.request"),
            (4000.0, 9000.0, "bench.submit")]
    return trace.Timeline(device_ops, host)


def test_dispatch_idle_share_counts_idle_under_dispatch_only():
    read = _reader("idle_share.serve_dispatch").read
    assert read(_run(timelines={"bench.window": _timeline()})) == \
        pytest.approx(0.2)
    # averaged over the chips traced: the second is busy throughout
    assert read(_run(timelines={"bench.window": _timeline(2)})) == \
        pytest.approx(0.1)


def test_dispatch_idle_share_reads_nothing_without_the_span():
    read = _reader("idle_share.serve_dispatch").read
    assert read(_run()) is None
    tl = _timeline()
    tl.host = [h for h in tl.host if h[2] != "serve.dispatch"]
    assert read(_run(timelines={"bench.window": tl})) is None


def test_a_traced_serve_cell_reports_the_intervals(tmp_path):
    """Through the harness, a traced open-loop cell reads each interval
    from the program; the CPU trace holds no device plane, so the idle
    share reads nothing and is left out."""
    per_layer = [{"name": n, "unit": "ms", "better": "lower",
                  "source": "program_counter", "layer": "serve",
                  "moves": "p50_ms", "workloads": ["tiny-ivf_flat.serve"]}
                 for n in INTERVALS]
    per_layer.append({"name": "idle_share.serve_dispatch",
                      "unit": "fraction", "better": "lower",
                      "source": "device_trace", "layer": "device",
                      "moves": "p50_ms",
                      "workloads": ["tiny-ivf_flat.serve"]})
    tree = make_tree(tmp_path, configs={"tiny-ivf_flat": TINY_FLAT},
                     traffic={"tiny_open": TINY_OPEN},
                     workloads=[("tiny-ivf_flat.serve", "tiny-ivf_flat",
                                 "tiny_open")],
                     per_layer=per_layer)
    r = harness.run_cell(tree, "tiny-ivf_flat.serve", 7, 1.0,
                         trace_on=True, require_accelerator=False)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == set(INTERVALS)
    assert all(r["metrics"][n]["value"] > 0 for n in INTERVALS)
