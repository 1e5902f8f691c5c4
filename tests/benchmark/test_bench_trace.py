"""The trace reduction: busy time, idle share, kernel time and the
breakdown, on a small synthetic trace with known answers and on a small
trace recorded on a TPU v5e (two IVF-Flat searches, 131k x 128 rows)."""

import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "fixtures", "ivf_flat_v5e.xplane.pb")

# device ops (ns): fusion 0-2000, overlapping copy 1500-2500, scan kernel
# 4000-5000, one op outside the window; host: the window 0-10000 holding
# bench.step 0-3500 and bench.fetch 3500-10000
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "_scan_kernel.3" } }
  event_metadata { key: 3 value { id: 3 name: "copy.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_search" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 3500000 }
    events { metadata_id: 3 offset_ps: 3500000 duration_ps: 6500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.fetch" } }
}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    return trace.from_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_busy_is_the_union_of_op_intervals_in_the_window(synthetic):
    w = trace.window_reading(synthetic, "bench.window")
    assert w["window_s"] == pytest.approx(10e-6)
    # [0, 2500] and [4000, 5000]: the overlap counts once, the op past
    # the window not at all, the module line never
    assert w["busy_s"] == pytest.approx(3.5e-6)
    assert trace.idle_share(synthetic, "bench.window") == pytest.approx(
        0.65)


def test_kernel_time_and_breakdown(synthetic):
    lo, hi = synthetic.span("bench.window")
    secs, n = synthetic.op_seconds(["_scan_kernel"], lo, hi)
    assert n == 1 and secs == pytest.approx(1e-6)
    top = synthetic.top_ops(lo, hi)
    assert [t[0] for t in top] == ["fusion.1", "copy.2", "_scan_kernel.3"]
    gaps = dict(synthetic.idle_gaps(lo, hi, skip=("bench.window",)))
    # idle 2500-4000 (middle under bench.step) and 5000-10000 (under
    # bench.fetch)
    assert gaps == pytest.approx({"bench.step": 1.5e-6,
                                  "bench.fetch": 5e-6})


def test_an_untraced_phase_reads_nothing(synthetic):
    assert trace.idle_share(None, "bench.build") is None
    with pytest.raises(KeyError):
        synthetic.span("bench.build")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="recorded trace not present")
def test_recorded_v5e_trace():
    tl = trace.load(RECORDED)
    assert list(tl.devices) == ["/device:TPU:0"]
    w = trace.window_reading(tl, "bench.window")
    assert 0 < w["busy_s"] < w["window_s"]
    lo, hi = w["lo"], w["hi"]
    import json

    with open(os.path.join(HERE, "..", "..", "benchmark", "metrics",
                           "kernels.json")) as f:
        names = json.load(f)["ivf_scan"]
    secs, n = tl.op_seconds(names, lo, hi)
    # two searches, each one scan kernel call
    assert n == 2 and 0 < secs < w["busy_s"]
    gaps = dict(tl.idle_gaps(lo, hi, skip=("bench.window",)))
    assert set(gaps) <= {"bench.step", "bench.fetch", "(no host span)"} | {
        s for s in gaps if not s.startswith("bench.window")}
