"""Mean per request of the program's own spans over the window, in
milliseconds, from ``_stats`` ``search.spans`` before and after it
(exact nanoseconds per span name: ``count``, ``sum_ns``, ``self_ns``).

``params``: ``spans``, the names whose ``field`` (``sum_ns`` or
``self_ns``) is summed, over the window's count of the first of them.
None where ``_stats`` has no ``spans`` block (a program from before the
span tree) or the first span did not occur in the window."""


def read(ctx, params):
    before = ctx["stats_before"].get("spans")
    after = ctx["stats_after"].get("spans")
    if before is None or after is None:
        return None

    def delta(name, field):
        return (after.get(name, {}).get(field, 0)
                - before.get(name, {}).get(field, 0))

    count = delta(params["spans"][0], "count")
    if count <= 0:
        return None
    total = sum(delta(name, params["field"]) for name in params["spans"])
    return total / count / 1e6
