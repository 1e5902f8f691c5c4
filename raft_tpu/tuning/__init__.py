"""Measurement-driven dispatch for the select/scan/merge hot paths.

The reference library chooses between its radix and warpsort ``select_k``
backends with a heuristic *learned from benchmark measurements*
(matrix/detail/select_k-inl.cuh:51-79). This package is the TPU analog,
generalized to every hot-path dispatch the repo used to hard-code:

* ``select_k``   — hardware ``lax.top_k`` vs the compacting tournament
* ``merge_topk`` — the cross-probe/parts merge's selection backend
* ``ivf_scan``   — fused Pallas list scan vs the XLA bucketized scan
* ``pq_scan``    — IVF-PQ cache/scoring kind (i8 / i4 / pq4 one-hot)
* budgets        — e.g. CAGRA's inline packed-table byte budget, the
  tiered rerank's ``tiered_hot_rows`` HBM hot-row cache capacity

Consumers call ``choose(op, key, candidates, fallback)`` with a static
shape key; the answer comes from a **persisted per-backend table** of
microbenchmark measurements (``tables/<backend>.json``, captured by
``scripts/capture_dispatch_tables.py``), falling back to the caller's
analytic projection when no measurement covers the key. Behavior is
frozen with ``RAFT_TPU_TUNING``:

    RAFT_TPU_TUNING=off       always use the analytic fallback
    RAFT_TPU_TUNING=table     consult the persisted table (default)
    RAFT_TPU_TUNING=measure   table mode + measure cheap ops (select_k /
                              merge_topk) on first use at uncovered keys,
                              caching the winner in-process

``RAFT_TPU_TUNING_TABLE=/path.json`` overrides the packaged table — the
user-writable slot for site-captured tables (point
``capture_dispatch_tables.py --out`` there).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

from raft_tpu.tuning.table import DispatchTable

_MODES = ("off", "table", "measure")

# Canonical row-tile candidates for the fused brute-force kernel
# (ops/fused_topk.py, op key ``fused_topk_tile``). ONE home on purpose:
# brute_force._resolve_bf_impl builds its dispatch candidate strings
# ("fused_<variant>:<tile>") from this set, microbench races exactly the
# same set, and the graft-kern static verifier (analysis/kernels.py)
# evaluates kernel geometry over every value that can flow in from a
# table winner — a tile added here is automatically raced, dispatched,
# and statically audited.
FUSED_TOPK_TILES = (512, 1024, 2048)
# tile_geometry's analytic fallback halves below the raced set down to
# this floor; it is part of the reachable-value domain the verifier
# must cover even though it is never raced by name
FUSED_TOPK_TILE_FLOOR = 256

# Canonical node-tile candidates for the fused nn-descent local-join
# kernel (ops/graph_join.py, op key ``graph_join``; winner strings
# ``pallas:<tile_b>``). Same one-home rule as FUSED_TOPK_TILES: the
# dispatch resolver (neighbors.nn_descent._resolve_join_impl), the
# microbench race (bench_graph_join) and the graft-kern static audit
# (kernel_shape_candidates + the contract's per-tile cases) all consume
# this tuple — a tile added here is raced, dispatched, and audited.
GRAPH_JOIN_TILES = (8, 16, 32)

# Canonical query-tile (lane) candidates for the fused CAGRA beam-step
# kernel (ops/beam_step.py, op key ``beam_step_tile``; winner strings
# ``pallas:<g>``) — cagra._resolve_beam_tile dispatches over them,
# bench_beam_step races them, and the beam contract carries one static
# geometry case per value so the audit covers every injectable tile.
BEAM_STEP_TILES = (128, 256)

# ops cheap enough to measure synchronously at first use in "measure"
# mode; scan-path ops need an index built around them — capture those
# with scripts/capture_dispatch_tables.py instead
MEASURABLE_INLINE = ("select_k", "merge_topk")

_lock = threading.Lock()
_mode_override: Optional[str] = None
_table_path_override: Optional[str] = None
_table_cache: Dict[str, Optional[DispatchTable]] = {}
_measured: Dict = {}
# in-process budget ceilings learned the hard way (the resilience OOM
# ladder records the chunk size that survived a RESOURCE_EXHAUSTED here
# so later calls in the same process start safe instead of re-OOMing)
_runtime_budgets: Dict[str, int] = {}


def mode() -> str:
    """Active tuning mode: the ``set_mode`` override if any, else
    ``RAFT_TPU_TUNING`` (default "table")."""
    if _mode_override is not None:
        return _mode_override
    m = os.environ.get("RAFT_TPU_TUNING", "table").strip().lower()
    return m if m in _MODES else "table"


def set_mode(m: Optional[str]) -> None:
    """Override the env knob in-process (None restores env control)."""
    global _mode_override
    if m is not None and m not in _MODES:
        raise ValueError(f"mode must be one of {_MODES}, got {m!r}")
    _mode_override = m


def backend_name() -> str:
    """Table filename stem and kernel-choice key: the platform of the
    default device, as JAX reports it. A backend that fails to start
    raises — a search must not quietly move to the CPU."""
    import jax

    return jax.devices()[0].platform


def tables_dir() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tables")


def table_path() -> Optional[str]:
    """Resolved table path: ``set_table_path`` override, then
    ``RAFT_TPU_TUNING_TABLE``, then the packaged per-backend table.
    None when none of those files exist."""
    if _table_path_override is not None:
        return _table_path_override
    env = os.environ.get("RAFT_TPU_TUNING_TABLE", "").strip()
    if env:
        return env
    packaged = os.path.join(tables_dir(), backend_name() + ".json")
    return packaged if os.path.exists(packaged) else None


def set_table_path(path: Optional[str]) -> None:
    """Point dispatch at a specific table file (None restores the
    default resolution) and drop the cache."""
    global _table_path_override
    _table_path_override = path
    reload()


def reload() -> None:
    """Drop the cached table, in-process measurements, and runtime
    budgets (tests, or after re-capturing a table)."""
    with _lock:
        _table_cache.clear()
        _measured.clear()
        _runtime_budgets.clear()


def get_table() -> Optional[DispatchTable]:
    """The active DispatchTable, or None when no table file resolves or
    the file is unreadable (dispatch then always falls back)."""
    path = table_path()
    if path is None:
        return None
    with _lock:
        if path not in _table_cache:
            try:
                _table_cache[path] = DispatchTable.load(path)
            except Exception:  # noqa: BLE001 - bad table == no table
                _table_cache[path] = None
        return _table_cache[path]


def _tracing() -> bool:
    """True while under a jax trace — measure mode must not launch
    microbenchmarks from inside someone else's jit."""
    try:
        import jax

        return not jax.core.trace_state_clean()
    except Exception:  # noqa: BLE001  # graft-lint: allow-unclassified-swallow trace-state probe only gates measure mode; not-tracing is the safe fallback
        return False


def _freeze_key(op: str, key: Dict) -> tuple:
    return (op,) + tuple(sorted(key.items()))


def _measure_inline(op: str, key: Dict,
                    candidates: List[str]) -> Optional[str]:
    fk = _freeze_key(op, key)
    with _lock:
        if fk in _measured:
            return _measured[fk]
    try:
        from raft_tpu.tuning import microbench

        times = microbench.measure_op(op, key, candidates)
        winner = min(times, key=times.get) if times else None
    except Exception:  # noqa: BLE001 - measurement failure => fallback
        winner = None
    with _lock:
        _measured[fk] = winner
    return winner


def choose(op: str, key: Dict, candidates: List[str],
           fallback: Optional[str]) -> Optional[str]:
    """Pick an implementation for ``op`` at static shape ``key``.

    ``candidates`` is the ELIGIBLE set at this call site (dtype/layout
    constraints already applied); a table winner outside it is ignored.
    ``fallback`` is the caller's analytic projection — returned verbatim
    in ``off`` mode, on a table miss, or on any error. ``key`` values
    must be static python scalars (shapes at trace time are), so a
    choice is a pure trace-time decision.
    """
    from raft_tpu import obs

    m = mode()
    if m == "off" or not candidates:
        obs.counter("tuning.dispatch", op=op, impl=str(fallback),
                    source="off" if m == "off" else "no_candidates")
        return fallback
    t = get_table()
    if t is not None:
        w = t.lookup(op, key, candidates)
        if w in candidates:
            obs.counter("tuning.dispatch", op=op, impl=str(w),
                        source="table")
            return w
    # only genuinely UNCOVERED keys get measured in measure mode — a
    # persisted measurement always wins over an ad-hoc in-process one
    if (m == "measure" and op in MEASURABLE_INLINE and len(candidates) > 1
            and not _tracing()):
        w = _measure_inline(op, key, candidates)
        if w in candidates:
            obs.counter("tuning.dispatch", op=op, impl=str(w),
                        source="measured")
            return w
    obs.counter("tuning.dispatch", op=op, impl=str(fallback),
                source="fallback")
    return fallback


def fused_topk_candidate_impls(k: int, approx_ok: bool) -> List[str]:
    """The fused brute-force impl strings eligible at ``k`` —
    ``fused_<variant>:<tile>`` over :data:`FUSED_TOPK_TILES` within
    each variant's extraction budget (exact k <= 128, fold k <= 256;
    fold only for approx-opted callers). The shared enumeration behind
    brute_force's dispatch and microbench's race."""
    out: List[str] = []
    if k <= 128:
        out += [f"fused_exact:{t}" for t in FUSED_TOPK_TILES]
    if approx_ok and k <= 256:
        out += [f"fused_fold:{t}" for t in FUSED_TOPK_TILES]
    return out


def _winner_tiles(table, op: str, prefix: str) -> set:
    """Integer tile suffixes of an op's ``<prefix><tile>`` winner
    strings in an active table (``fused_exact:1024``, ``pallas:16``)."""
    tiles: set = set()
    if table is None:
        return tiles
    try:
        for entry in table.data.get("ops", {}).get(op, {}).get(
                "entries", []):
            w = str(entry.get("winner", ""))
            if w.startswith(prefix):
                tail = w[len(prefix):].split(":", 1)[0]
                if tail.isdigit():
                    tiles.add(int(tail))
    except Exception:  # noqa: BLE001 — malformed table entries only shrink the audited domain to the canonical set
        pass
    return tiles


def kernel_shape_candidates() -> Dict[str, tuple]:
    """Shape-parameter domains reachable through ``tuning.choose``
    winners, keyed by kernel parameter NAME — consumed by the
    graft-kern static verifier (docs/static_analysis.md §engine-4) so
    table-dispatched tile geometry is audited at every value it can
    take, not just the analytic default. Includes any extra tiles an
    active site-captured table carries in its ``fused_topk_tile`` /
    ``graph_join`` / ``beam_step_tile`` winner strings."""
    t = get_table()
    tiles = set(FUSED_TOPK_TILES)
    tiles.add(FUSED_TOPK_TILE_FLOOR)          # analytic halving floor
    for variant in ("fused_exact:", "fused_fold:"):
        tiles |= _winner_tiles(t, "fused_topk_tile", variant)
    join_tiles = set(GRAPH_JOIN_TILES) | _winner_tiles(
        t, "graph_join", "pallas:")
    beam_tiles = set(BEAM_STEP_TILES) | _winner_tiles(
        t, "beam_step_tile", "pallas:")
    return {
        "tile_n": tuple(sorted(tiles)),
        # tile_geometry rounds the query tile to a pow2 in [8, 128];
        # the corners bound both the VMEM max and the alignment screen
        "tile_q": (8, 128),
        "variant": ("exact", "fold"),
        # graph_join node tiles / beam_step query tiles: the contracts
        # pin the canonical values in explicit cases; these domains let
        # a site-captured winner outside them still enter the audit
        "tile_b": tuple(sorted(join_tiles)),
        "g": tuple(sorted(beam_tiles)),
    }


def record_budget(name: str, value: int) -> None:
    """Record a runtime budget CEILING for ``name`` (in-process only).

    The resilience OOM ladder calls this with the chunk/batch size that
    survived a RESOURCE_EXHAUSTED; :func:`budget` then clamps every
    later lookup of ``name`` to the recorded minimum so subsequent
    dispatches in this process start at a size known to fit. Repeated
    records keep the minimum. Cleared by :func:`reload`.
    """
    v = int(value)
    with _lock:
        prior = _runtime_budgets.get(name)
        _runtime_budgets[name] = v if prior is None else min(prior, v)
        recorded = _runtime_budgets[name]
    from raft_tpu import obs

    obs.gauge("runtime_budget", recorded, budget=name)
    obs.event("budget_record", budget=name, value=v, effective=recorded)


def runtime_budget(name: str) -> Optional[int]:
    """The recorded runtime ceiling for ``name``, if any."""
    with _lock:
        return _runtime_budgets.get(name)


def budget(name: str, default: int) -> int:
    """A tuned byte budget (e.g. ``cagra_inline_bytes``), or ``default``
    when tuning is off or the table has no entry. A runtime ceiling
    recorded by :func:`record_budget` (an OOM survivor size) clamps the
    answer in every mode — a learned hard limit outranks projections."""
    out = int(default)
    if mode() != "off":
        t = get_table()
        if t is not None:
            v = t.budget(name)
            if v is not None:
                out = int(v)
    ceil = runtime_budget(name)
    return out if ceil is None else min(out, ceil)


__all__ = [
    "BEAM_STEP_TILES", "DispatchTable", "FUSED_TOPK_TILES",
    "FUSED_TOPK_TILE_FLOOR", "GRAPH_JOIN_TILES", "MEASURABLE_INLINE",
    "backend_name", "budget", "choose", "fused_topk_candidate_impls",
    "get_table", "kernel_shape_candidates", "mode", "record_budget",
    "reload", "runtime_budget", "set_mode", "set_table_path",
    "table_path", "tables_dir",
]
