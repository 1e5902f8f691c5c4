"""Share of the window's sharded searches that found their jitted
program held, from the program's ``sharded.program_hits`` and
``sharded.program_misses`` counters: hits / (hits + misses). A program
without the counters, or a window with no sharded search, reads
nothing."""

from __future__ import annotations


def _total(snapshot: dict, name: str) -> float:
    pts = snapshot.get("metrics", {}).get(name, {}).get("points", [])
    return sum(float(p.get("value", 0.0)) for p in pts)


def read(run):
    if not run.obs:
        return None
    hits = _total(run.obs, "sharded.program_hits")
    misses = _total(run.obs, "sharded.program_misses")
    if hits + misses == 0:
        return None
    return hits / (hits + misses)
