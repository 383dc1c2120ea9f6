"""The plain references: one module a kind of answer, named by the
configuration's ``"reference"`` key and added as a file.

A module ``references/<name>.py`` imports nothing of the program and
takes nothing the program has made. It holds one class, ``Reference``:

``Reference(view, config)``
    Built once a run (and once more on the final state where the cell
    writes) from the dataset's ``view``, passed whole: whatever the
    generator put there (``text_fields``, ``columns``, ``shard``, and
    for a later generator ``vectors``) is the reference's to read, the
    harness looks at none of it.

``controls``
    The names ``--control`` may take in a cell of this reference: each
    a lower precision or a fault that has to come out ``correct:
    false``. The harness refuses any other (``lost_ack`` is the
    harness's own, in cells that write).

``compare(cmp, what, answer, ref, control=None, among=None)``
    The expected answer of one request, from its ``ref`` and the view,
    against ``answer`` (``total``, ``ids``, ``scores``, ``aggs`` of one
    ``_search`` reply), noted into ``cmp`` (``harness/comparison.py``)
    under names that have a limit in the configuration's ``limits``: a
    name without one raises. ``control`` puts the reference's own answer
    at that precision in the answer's place. ``among`` (cells that
    write) is the set of documents that are there, as a mask: the
    answer is held to it exactly, scores left out.

``work(ref) -> {"bytes": int, "flops": int, "peak": str}``
    The least work of one request, counted from the benchmark's own
    corpus and never from the program's tables; ``peak`` names the
    entry of ``peaks.json`` the operations are held against
    (``harness/work.py`` ``least_seconds``).

``matched(ref)``, ``buckets(ref, matched)``
    Only where a cell writes: the documents of the view that match, as
    a mask, and ``{aggregation: {key: count}}`` over a mask, for the
    answers under ingest that lie between two states.
"""

import importlib


def named_by(config: dict):
    """The ``Reference`` class the configuration names."""
    return importlib.import_module(
        f"references.{config['reference']}").Reference


def build(config: dict, view: dict):
    """The configuration's reference on one view of its dataset."""
    return named_by(config)(view, config)
