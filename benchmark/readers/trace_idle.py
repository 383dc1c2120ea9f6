"""Device idle share of the traced interval: 1 - busy / window."""


def read(ctx, params):
    t = ctx.get("trace")
    if not t or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
