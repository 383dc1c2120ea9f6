"""One small reader per kind of per-layer metric. ``read(ctx, params)``
returns the number, or None where it finds nothing to read: the harness
then leaves the metric out of the line. ``ctx`` is what the traced run
gathered (``harness/run_cell.py``): ``records`` of the window, ``refs``
of the requests, ``stats_before``/``stats_after``, ``trace``,
``reference`` (the cell's, on the base's view), ``peaks``, ``chips``,
``window``."""


def searches(ctx, group=None):
    return [r for r in ctx["records"] if r["kind"] == "search"
            and r["status"] == 200 and (group is None or r["group"] == group)]
