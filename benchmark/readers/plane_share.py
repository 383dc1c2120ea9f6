"""Share of the window's ``_search`` answers served by one plane."""

from readers import searches


def read(ctx, params):
    got = searches(ctx)
    if not got:
        return None
    return 100.0 * sum(1 for r in got if r["plane"] == params["plane"]) / len(got)
