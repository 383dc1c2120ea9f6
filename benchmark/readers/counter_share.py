"""The window's share of one ``_stats`` counter in a sum: the counter's
difference between the readings before and after the window, over that
plus the differences of the counters ``beside`` it, in percent.

``params``: ``block`` (a key of ``_stats`` ``search``: ``planes``),
``counter`` and ``beside`` (a list of names in that block). None where
the block or a counter is absent (a program from before it) or nothing
was counted in the window."""


def read(ctx, params):
    before = ctx["stats_before"].get(params["block"])
    after = ctx["stats_after"].get(params["block"])
    names = [params["counter"]] + list(params["beside"])
    if before is None or after is None or any(
            not isinstance(block.get(n), int)
            for block in (before, after) for n in names):
        return None
    deltas = [after[n] - before[n] for n in names]
    return 100.0 * deltas[0] / sum(deltas) if sum(deltas) > 0 else None
