"""The work a request needs, counted from the benchmark's own corpus and
never from the program's tables: a packed codec, pruning or a fused
kernel changes the time and not the count.

For a ``match`` query the work is its postings: the sum over the query's
terms of the term's document frequency in the corpus, times 8 bytes (one
i32 doc id and one f32 impact: the raw codec's posting), read once. The
FLOPs (a multiply-add or two per posting) are negligible beside that on
any chip whose FLOP/s exceed its bytes/s, so the bound is bytes over the
chip's memory bandwidth.
"""

from __future__ import annotations

import json
import os

POSTING_BYTES = 8
PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}: an unknown device is an error")
    return table[device_kind]


def match_postings_bytes(field, terms) -> int:
    return int(field.doc_freq(terms).sum()) * POSTING_BYTES


def least_seconds(n_bytes: float, peaks: dict, chips: int) -> float:
    """The least time ``chips`` chips could take to read the bytes."""
    return n_bytes / (peaks["hbm_bytes_per_s"] * chips)
