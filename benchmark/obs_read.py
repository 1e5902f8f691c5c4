"""Reading the program's ``obs`` counters from a snapshot."""

from __future__ import annotations

from typing import Optional


def histogram_mean(snapshot: Optional[dict], name: str) -> Optional[float]:
    """Mean of every point of the histogram ``name`` (all label sets), or
    None where it recorded nothing."""
    if not snapshot:
        return None
    pts = snapshot.get("metrics", {}).get(name, {}).get("points", [])
    count = sum(p.get("count", 0) for p in pts)
    if count == 0:
        return None
    return sum(p["sum"] for p in pts) / count
