"""Mean queries per launch from ``_stats`` ``search.batch`` over the
window: queries served over (queries that went alone + batches). 1.0
when nothing batched."""

from readers import searches


def read(ctx, params):
    before, after = ctx["stats_before"]["batch"], ctx["stats_after"]["batch"]
    batched = after["batched_query_total"] - before["batched_query_total"]
    hist_b = before.get("batch_size_histogram", {})
    batches = sum(n - hist_b.get(k, 0)
                  for k, n in after.get("batch_size_histogram", {}).items())
    served = len(searches(ctx))
    if not served:
        return None
    launches = max(served - batched, 0) + batches
    return served / launches if launches else None
