"""Mean of one phase of ``"profile": true`` over the window's searches,
in milliseconds (exact nanoseconds per phase per query)."""

from readers import searches


def read(ctx, params):
    spans = [r["phases"].get(params["phase"], 0) for r in searches(ctx)
             if "phases" in r]
    return sum(spans) / len(spans) / 1e6 if spans else None
