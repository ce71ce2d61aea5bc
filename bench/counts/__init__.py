"""Operations and bytes of the measured work, computed from shapes.

A kernel has a module of its own here, named as the kernel: its
``PATTERN`` finds the kernel's operations in a device trace by their HLO
text, and its ``count(counters)`` gives the operations and bytes the
window's work required of it.  A per-layer metric that names a ``KERNEL``
is that kernel's share of its roofline (:func:`roofline`); the harness
hands each such pattern to the trace, so a later kernel needs only its
module here and its metric's reader.
"""
from __future__ import annotations

import importlib


def kernel(name: str):
    """The counting module of kernel ``name``: ``bench/counts/<name>.py``."""
    return importlib.import_module(f"bench.counts.{name}")


def roofline(rec: dict, name: str):
    """Kernel ``name``'s share of its roofline, in percent: the least time
    the chip could take for the window's work (the larger of its operations
    over the peak rate and its bytes over the memory bandwidth) over the
    device time of the kernel's operations in the trace.  ``None`` off the
    chip, or where the trace holds none of its operations."""
    t, peaks = rec["trace"], rec["peaks"]
    if peaks is None or not t or not t["kernel_s"].get(name):
        return None
    c = kernel(name).count(rec["counters"])
    bound = max(c["flops"] / peaks["flops_bf16_per_s"],
                c["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t["kernel_s"][name]
