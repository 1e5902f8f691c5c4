"""One run of one cell: set-up, the measured window, the reference, and
the result line. Everything particular to a configuration, a traffic mix
or a metric is found by name under the benchmark tree (see
:mod:`benchmark`); nothing here names one.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
from typing import Optional

from benchmark import data, reference, trace

# the events JAX reports for every program it compiles or loads from the
# persistent compilation cache, and for each one loaded from the cache
_PROGRAM = "/jax/core/compile/backend_compile_duration"
_CACHE_LOADED = "/jax/compilation_cache/cache_retrieval_time_sec"


# the axis of a several-chip cell's mesh, the name raft_tpu.comms takes
MESH_AXIS = "shard"


class NoAccelerator(RuntimeError):
    pass


class BenchSpec:
    """``BENCHMARK.json`` of a benchmark tree, and the files it names."""

    def __init__(self, tree: str):
        self.tree = os.path.abspath(tree)
        with open(os.path.join(self.tree, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self.root = os.path.join(self.tree, self.spec["paths"][0])

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.tree, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _load_json(os.path.join(self.root, "traffic", name + ".json"))

    def module(self, kind: str, name: str):
        return _load_module(os.path.join(self.root, kind, name + ".py"))

    def metrics_for(self, workload: str, trace_on: bool) -> list:
        """The metric entries a cell reports: with ``--trace 0`` its
        end-to-end metrics, with ``--trace 1`` its per-layer ones."""
        e2e = [m for m in self.spec["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if not trace_on:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload])
                and ("workloads" in m or m["moves"] in names)]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = "bench_" + os.path.relpath(path).replace(os.sep, "_").replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """What one run knows: its cell, its files, and what it measured.
    Metric readers and cost models read it."""

    def __init__(self, bench: BenchSpec, workload: str, seed: int,
                 seconds: float, trace_on: bool):
        self.bench = bench
        self.workload = bench.workload(workload)
        self.name = workload
        self.cfg = bench.config(self.workload["config"])
        self.traffic = bench.traffic(self.workload["traffic"])
        self.seed, self.seconds, self.trace_on = int(seed), seconds, trace_on
        self.timelines: dict = {}
        self.obs: Optional[dict] = None
        self.layout: Optional[dict] = None
        self.window: dict = {}
        self.queries = None
        self.device_kind = ""
        self.info: dict = {}

    def kernel_names(self, kernel: str) -> list:
        return _load_json(os.path.join(self.bench.root, "metrics",
                                       "kernels.json"))[kernel]

    def cost(self, kernel: str) -> dict:
        return self.bench.module("costs", kernel).count(self)


class _CompileCounter:
    def __init__(self):
        import jax

        self.programs = self.loaded = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == _PROGRAM:
            self.programs += 1
        elif event == _CACHE_LOADED:
            self.loaded += 1

    def count(self) -> tuple:
        return self.programs, self.loaded

    def since(self, c0: tuple, prefix: str) -> dict:
        programs, loaded = (a - b for a, b in zip(self.count(), c0))
        return {f"{prefix}_compiled": programs - loaded,
                f"{prefix}_from_cache": loaded}


class _GcPauses:
    """The collector's pauses while it is installed: how many of each
    generation, the longest and their sum."""

    def __init__(self):
        self.count, self.max_ms, self.total_ms = [0, 0, 0], 0.0, 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        ms = (time.perf_counter() - self._t0) * 1e3
        self.count[info["generation"]] += 1
        self.max_ms, self.total_ms = max(self.max_ms, ms), self.total_ms + ms

    def stop(self) -> None:
        gc.callbacks.remove(self._on)

    def summary(self) -> dict:
        return {"collections": self.count, "pause_ms_max": self.max_ms,
                "pause_ms_total": self.total_ms}


def _free(index, x, q) -> None:
    """Delete the index's device arrays (not the rows or queries)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(index):
        if leaf is not x and leaf is not q:
            leaf.delete()
    gc.collect()


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        raise NoAccelerator(
            f"needs {chips} accelerator chip(s); JAX found {len(devs)} x "
            f"{devs[0].platform} ({devs[0].device_kind})")
    return devs


class _Control:
    """The control in the program's place: the exact search, its operands
    rounded to the configuration's ``control_dtype``."""

    ALGO = None

    def __init__(self, dtype: str):
        self.dtype = dtype

    def build(self, cfg, x):
        return None

    def searcher(self, cfg, index, x):
        k = int(cfg["k"])
        return lambda q: reference.lowp_search(q, x, k, self.dtype)


def run_cell(tree: str, workload: str, seed: int, seconds: float,
             trace_on: bool = False, t_start: Optional[float] = None,
             require_accelerator: bool = True, control: bool = False,
             log=None) -> dict:
    """Run one cell once and return its result line (a dict). With
    ``control`` the entry is replaced by the control and a ``batch``
    driver over the whole pool, and no metric is read."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(f"bench: {msg}", file=sys.stderr,
                                    flush=True))
    bench = BenchSpec(tree)
    run = Run(bench, workload, seed, seconds, trace_on)
    cfg, k = run.cfg, int(run.cfg["k"])
    chips = int(run.workload["chips"])
    devs = (check_devices(chips) if require_accelerator
            else jax.devices())
    run.device_kind = devs[0].device_kind
    mesh = None
    if chips > 1:
        from jax.sharding import Mesh

        if len(devs) < chips:
            raise ValueError(f"{workload} needs {chips} devices, JAX has "
                             f"{len(devs)}")
        mesh = Mesh(devs[:chips], (MESH_AXIS,))
    compiles = _CompileCounter()

    if control:
        entry = _Control(cfg["control_dtype"])
        run.traffic = {"kind": "batch", "batch": int(cfg["queries"]),
                       "rotations": 1}
    else:
        entry = bench.module("entries", cfg["entry"])
    driver = bench.module("drivers", run.traffic["kind"])

    x, q = jax.block_until_ready(data.generate(cfg, seed, mesh))
    run.queries = q
    log(f"data made at {time.perf_counter() - t_start:.1f} s")
    readings: dict = {}

    def build():
        c0, t0 = compiles.count(), time.perf_counter()
        if trace_on and not control:
            with trace.capture("bench.build", run.timelines):
                index = jax.block_until_ready(entry.build(cfg, x))
        else:
            index = jax.block_until_ready(entry.build(cfg, x))
        readings["build_s"] = time.perf_counter() - t0
        run.info.update(compiles.since(c0, "build_programs"))
        return index

    index = build()
    if run.info["build_programs_compiled"] and not control:
        # the first run in a checkout compiles the build's programs: build
        # again from a cleared in-memory cache, so that build_s is what
        # every later run pays, programs loaded from the persistent cache
        run.info["build_compiling_s"] = readings["build_s"]
        _free(index, x, q)
        jax.clear_caches()
        index = build()
    st = driver.setup(run, entry, index, x, q)
    # the collector skips every object set-up left, so that a collection
    # in the window walks only what the window made
    gc.collect()
    gc.freeze()
    readings["setup_s"] = time.perf_counter() - t_start
    log(f"set-up done at {readings['setup_s']:.1f} s")

    from raft_tpu import obs

    if trace_on:
        obs.set_mode("on")
    obs.reset()
    c0 = compiles.count()
    pauses = _GcPauses()
    try:
        if trace_on:
            with trace.capture("bench.window", run.timelines):
                win = driver.window(run, st, seconds)
        else:
            win = driver.window(run, st, seconds)
    finally:
        pauses.stop()
        gc.unfreeze()
    run.info.update(compiles.since(c0, "window_programs"))
    run.info["gc_in_window"] = pauses.summary()
    run.window = win
    readings.update(win["readings"])
    run.info.update(win["info"])
    if trace_on:
        run.obs = obs.snapshot(runtime_gauges=False)
    obs.set_mode(None)
    per_device = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devs[:chips]]
    peak = max(per_device)
    run.info["peak_bytes_in_use"] = peak
    run.info["peak_bytes_per_device"] = per_device
    if trace_on and index is not None:
        run.layout = entry.scan_layout(cfg, index)
    driver.close(st)
    # the program's state goes before the reference runs
    _free(index, x, q)
    del index, st

    t_ref = time.perf_counter()
    _, gt_ids, redone = reference.exact_knn(q, x, k)
    qidx, ids, dist = win["answers"]
    judged = reference.judge(q, x, gt_ids, qidx, ids, dist)
    run.info["reference_s"] = time.perf_counter() - t_ref
    run.info["reference_redone_queries"] = redone
    log(f"reference done in {run.info['reference_s']:.1f} s")
    readings["recall_at_10"] = judged["recall"]

    limits = cfg["limits"]
    checks = {"recall_short": (1.0 - judged["recall"],
                               limits["recall_short"]),
              "dist_err": (judged["dist_err"], limits["dist_err"])}
    # a request refused, failed or never answered is an answer missing
    checks["unanswered"] = (float(win["failed"]), 0.0)
    correct = all(v <= lim for v, lim in checks.values())

    metrics = {}
    if not control:
        for m in bench.metrics_for(workload, trace_on):
            v = (readings.get(m["name"]) if not trace_on
                 else bench.module("metrics", m["name"]).read(run))
            if v is None and not trace_on:
                raise KeyError(f"end-to-end metric {m['name']!r} was not "
                               f"measured in {workload}")
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": int(win["attempted"]),
           "failed": int(win["failed"]), "metrics": metrics,
           "device": device}
    if trace_on and "bench.window" in run.timelines:
        tl = run.timelines["bench.window"]
        w = trace.window_reading(tl, "bench.window")
        device["busy_s"], device["window_s"] = w["busy_s"], w["window_s"]
        out["breakdown"] = {
            "device_ops": tl.top_ops(w["lo"], w["hi"]),
            "idle_gaps": tl.idle_gaps(w["lo"], w["hi"],
                                      skip=("bench.window",))}
    # JSON has no infinity: a reading past any limit prints as 1e308
    out["checks"] = {name: {"value": v if math.isfinite(v) else 1e308,
                            "limit": lim}
                     for name, (v, lim) in checks.items()}
    run.info["readings"] = readings
    out["_info"] = run.info
    return out


def emit(result: dict) -> None:
    """Print a run's result: its info line, then each compared number
    beside its limit as the last lines of standard error, then the result
    line as the last line of standard output."""
    info = result.pop("_info", {})
    print(json.dumps({"info": info}, default=float), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
