"""Mean of the client's round trip over the window's searches less the
mean of the server's ``http.request`` span: what lies outside the
program (loopback TCP, the kernel's sockets, the load generator's own
send and parse), in milliseconds. None where the program reports no
spans."""

from readers import searches, span_mean


def read(ctx, params):
    served = span_mean.read(ctx, {"spans": ["http.request"],
                                  "field": "sum_ns"})
    trips = [(r["done"] - r["sent"]) * 1e3 for r in searches(ctx)]
    if served is None or not trips:
        return None
    return sum(trips) / len(trips) - served
