"""A ``_stats`` byte counter across the window over the bytes the cell's
reference counts for the requests the window answered: how many times
the work as stated the program moved (padded rows, slots, copies).

``params``: ``block`` (a key of ``_stats`` ``search``: ``planes``) and
``counter`` (a name in that block). None where the block or the counter
is absent (a program from before it) or the window answered nothing."""

from readers import searches


def read(ctx, params):
    before = ctx["stats_before"].get(params["block"])
    after = ctx["stats_after"].get(params["block"])
    name = params["counter"]
    if before is None or after is None or any(
            not isinstance(block.get(name), int)
            for block in (before, after)):
        return None
    least = sum(ctx["reference"].work(ctx["refs"][tuple(r["id"])])["bytes"]
                for r in searches(ctx))
    return (after[name] - before[name]) / least if least > 0 else None
