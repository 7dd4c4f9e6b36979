"""The roofline's arithmetic, the same for every family and kernel; the
peaks are ``peaks.json``'s, the operations and bytes a family's
``counts.py``'s."""

from __future__ import annotations


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """The roofline's least time for a call, and which bound sets it."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
