"""Peaks of each chip, keyed by ``device_kind`` as JAX reports it, and the
roofline arithmetic. Copied from ``raft_tpu.bench.harness`` (``PEAK_SPECS``,
``roofline``) so that no change to the program can move the yardstick.

A device missing from the table is an error: there is no default peak.
"""

from __future__ import annotations

PEAK_SPECS = {
    "TPU v5 lite": {
        "flops_peak": 197.0e12, "hbm_gbps": 819.0,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip"},
}


def peak_spec(device_kind: str) -> dict:
    if device_kind not in PEAK_SPECS:
        raise ValueError(
            f"no peak spec for device_kind {device_kind!r}; known: "
            f"{sorted(PEAK_SPECS)}")
    return PEAK_SPECS[device_kind]


def roofline_share(flops: float, bytes_moved: float, seconds: float,
                   device_kind: str) -> dict:
    """The least time the chip could take for the work, max(flops / peak
    FLOP/s, bytes / peak bytes/s), as a percentage of ``seconds``, and
    which of the two bounds it."""
    spec = peak_spec(device_kind)
    if seconds <= 0:
        raise ValueError(f"kernel time must be > 0, got {seconds}")
    t_flops = flops / spec["flops_peak"]
    t_bytes = bytes_moved / (spec["hbm_gbps"] * 1e9)
    return {"percent": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory"}
