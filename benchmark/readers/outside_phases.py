"""Mean of the client's round trip less the sum of the profile's
phases: what HTTP, JSON, admission, queueing and locks add."""

from readers import searches


def read(ctx, params):
    rest = [(r["done"] - r["sent"]) * 1e3 - sum(r["phases"].values()) / 1e6
            for r in searches(ctx) if r.get("phases")]
    return sum(rest) / len(rest) if rest else None
