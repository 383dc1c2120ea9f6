"""One ``_stats`` counter over another, across the window: the
difference of ``counter`` between the readings before and after it over
the difference of ``over`` (slots scanned a query).

``params``: ``block`` (a key of ``_stats`` ``search``: ``planes``),
``counter`` and ``over`` (names in that block). None where the block or
a counter is absent (a program from before it) or ``over`` did not
count in the window."""


def read(ctx, params):
    before = ctx["stats_before"].get(params["block"])
    after = ctx["stats_after"].get(params["block"])
    names = (params["counter"], params["over"])
    if before is None or after is None or any(
            not isinstance(block.get(n), int)
            for block in (before, after) for n in names):
        return None
    top, under = (after[n] - before[n] for n in names)
    return top / under if under > 0 else None
