"""The list scan's keep-mask reader: the share of filtered searches that
reused a cached per-slot mask, on synthetic snapshots with known answers
and through a tiny traced serve cell on the CPU."""

import os
import types

import pytest

from benchmark import harness
from benchtree import REPO, TINY_FLAT, TINY_OPEN, make_tree

NAME = "filter.slot_keep_hit_share"


def _read(snapshot):
    mod = harness._load_module(
        os.path.join(REPO, "benchmark", "metrics", NAME + ".py"))
    return mod.read(types.SimpleNamespace(obs=snapshot))


def _counters(hits, misses):
    out = {}
    for name, pts in (("filter.slot_keep_hits", hits),
                      ("filter.slot_keep_misses", misses)):
        if pts is not None:
            out[name] = {"points": [{"labels": {"i": str(j)}, "value": v}
                                    for j, v in enumerate(pts)]}
    return {"metrics": out}


@pytest.mark.parametrize("hits,misses,want", [
    ([30.0, 9.0], [1.0], 39.0 / 40.0),   # summed over every label set
    ([12.0], None, 1.0),                 # no miss in the window
    (None, [2.0], 0.0),                  # every search built its mask
])
def test_hit_share_is_hits_over_lookups(hits, misses, want):
    assert _read(_counters(hits, misses)) == pytest.approx(want)


@pytest.mark.parametrize("snapshot", [None, {}, _counters(None, None),
                                      _counters([0.0], [0.0])])
def test_hit_share_reads_nothing_without_a_lookup(snapshot):
    """An untraced run, a program older than the counters, or a window
    with no filtered search."""
    assert _read(snapshot) is None


def test_a_traced_serve_cell_reuses_the_keep_mask(tmp_path):
    """Through the harness, the served window's filtered searches all
    reuse the mask that set-up built."""
    per_layer = [{"name": NAME, "unit": "fraction", "better": "higher",
                  "source": "program_counter",
                  "layer": "list-scan kernel (ops/ivf_scan.py)",
                  "moves": "p50_ms", "workloads": ["tiny-ivf_flat.serve"]}]
    tree = make_tree(tmp_path, configs={"tiny-ivf_flat": TINY_FLAT},
                     traffic={"tiny_open": TINY_OPEN},
                     workloads=[("tiny-ivf_flat.serve", "tiny-ivf_flat",
                                 "tiny_open")],
                     per_layer=per_layer)
    r = harness.run_cell(tree, "tiny-ivf_flat.serve", 2_900_000_011, 1.0,
                         trace_on=True, require_accelerator=False)
    assert r["correct"], r["checks"]
    assert r["metrics"][NAME]["value"] == 1.0
