"""Share of the filtered IVF-Flat searches of the window that reused a
cached per-slot keep-mask, from the program's ``filter.slot_keep_hits``
and ``filter.slot_keep_misses`` counters: hits / (hits + misses). A
program without the counters, or a window with no filtered search,
reads nothing."""

from __future__ import annotations


def _total(snapshot: dict, name: str) -> float:
    pts = snapshot.get("metrics", {}).get(name, {}).get("points", [])
    return sum(float(p.get("value", 0.0)) for p in pts)


def read(run):
    if not run.obs:
        return None
    hits = _total(run.obs, "filter.slot_keep_hits")
    misses = _total(run.obs, "filter.slot_keep_misses")
    if hits + misses == 0:
        return None
    return hits / (hits + misses)
