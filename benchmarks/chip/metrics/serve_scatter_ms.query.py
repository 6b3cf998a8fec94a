"""serve_scatter_ms.query: host milliseconds per consumed batch in the
scatter of its answers to their requests — the leaf slice, the sort of
each request's positions and the route-cache put (the server's
``serve/scatter`` spans).  Layer: launch/serving.py."""

from harness.spans import mean_ms


def read(run):
    if getattr(run, "lookup", None) is None:
        return None
    return mean_ms(run, "serve/scatter")
