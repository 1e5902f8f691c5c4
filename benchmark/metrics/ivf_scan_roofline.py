"""The list scan's share of its roofline over the window, in percent:
the least time the chip needs for the scan's work (``costs/ivf_scan.py``,
against ``benchmark.peaks``), over the device time of the scan kernel's
events in the trace (names in ``kernels.json``). Nothing when the kernel
did not run."""

from benchmark.peaks import roofline_share


def read(run):
    tl = run.timelines.get("bench.window")
    if tl is None or run.layout is None:
        return None
    lo, hi = tl.span("bench.window")
    secs, n = tl.op_seconds(run.kernel_names("ivf_scan"), lo, hi)
    if n == 0:
        return None
    c = run.cost("ivf_scan")
    return roofline_share(c["flops"], c["bytes"], secs,
                          run.device_kind)["percent"]
