"""A percentile of the client's round trip of one client group, ms."""

import numpy as np

from readers import searches


def read(ctx, params):
    lat = [(r["done"] - r["due"]) * 1e3
           for r in searches(ctx, params.get("group"))]
    return float(np.percentile(lat, params["percentile"])) if lat else None
