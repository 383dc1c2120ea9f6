"""The least time the chip could take for a piece of work, and the table
of peaks it is held against.

The work of a request is its reference's to count (``work(ref)`` in
``references/<name>.py``): bytes read once and operations, from the
benchmark's own corpus and never from the program's tables, so that a
packed codec, pruning or a fused kernel changes the time and not the
count, and a kernel's roofline reads the same work whatever implements
it. The bound is the larger of bytes over the chip's memory bandwidth
and operations over the peak the work names (``"peak"``: an entry of
``peaks.json``, the rate of the data type the configuration states).
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}: an unknown device is an error")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict, chips: int) -> float:
    """The least time ``chips`` chips could take for ``work``
    (``{"bytes", "flops", "peak"}``; no ``peak`` where there are no
    operations to hold against one)."""
    seconds = work["bytes"] / peaks["hbm_bytes_per_s"]
    if work.get("flops"):
        seconds = max(seconds, work["flops"] / peaks[work["peak"]])
    return seconds / chips

