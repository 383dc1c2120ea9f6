"""The harness's side of the comparison that decides ``correct``: the
worst reading of every number compared, each held to a limit of its own
from the configuration's ``limits``. What is compared with what is the
configuration's reference's to say (``references/<name>.py``); the
readings the limits were set from are in PERF.md section 2.
"""

from __future__ import annotations

import math


class Comparison:
    """Worst reading of every number compared, over all answers.

    ``limits`` maps a number's name to its limit; a number above its
    limit makes the run not correct. Names not in ``limits`` are
    refused: a number is never compared without a limit of its own."""

    def __init__(self, limits: dict):
        self.limits = dict(limits)
        self.worst = {name: 0.0 for name in limits}
        self.where = {}
        self.compared = 0

    def note(self, name: str, value: float, what: str) -> None:
        if name not in self.limits:
            raise KeyError(f"no limit for compared number {name!r}")
        if not value <= self.worst[name]:  # NaN counts as worse
            self.worst[name] = float(value) if value == value else math.inf
            self.where[name] = what

    def numbers(self) -> dict:
        """{name: {"value", "limit"}}; ``nothing_compared`` guards a run
        whose window returned no answer to compare."""
        out = {name: {"value": self.worst[name], "limit": self.limits[name]}
               for name in self.limits}
        out["nothing_compared"] = {
            "value": 0 if self.compared else 1, "limit": 0}
        return out

    def correct(self) -> bool:
        return all(n["value"] <= n["limit"] for n in self.numbers().values())


def compare_between(cmp: Comparison, name: str, what: str, got: dict,
                    lo: dict, hi: dict) -> None:
    """Counts that a reader under ingest may have seen: no fewer than
    before the first append, no more than after the last."""
    out = 0
    for k in set(got) | set(lo):
        v = got.get(k, 0)
        out = max(out, lo.get(k, 0) - v, v - hi.get(k, 0))
    cmp.note(name, max(out, 0), what)
