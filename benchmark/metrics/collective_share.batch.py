"""Share of the batch window's busy device time spent in collectives:
device seconds of the ops whose instruction name holds ``all-gather``,
``all-reduce``, ``reduce-scatter``, ``collective-permute`` or
``all-to-all``, over busy device seconds, both averaged over the chips.
It reads the merge's all-gather and the rerank's all-reduce; a window
that ran no collective, or was not traced, reads nothing."""

from benchmark.trace import window_reading

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def read(run):
    tl = run.timelines.get("bench.window")
    w = window_reading(tl, "bench.window")
    if w is None or w["busy_s"] <= 0:
        return None
    seconds, count = tl.op_seconds(COLLECTIVES, w["lo"], w["hi"])
    if count == 0:
        return None
    return seconds / w["busy_s"]
