"""serve_route_ms.query: host milliseconds per batch in the server's
route step — route keys, route-cache lookups and in-batch dedup (the
``serve/route`` spans).  Read only where there is one span for every
batch (``serve_batches_total``), so that a recorder that dropped its
oldest spans reads nothing.  Layer: launch/serving.py."""

from harness.spans import durations_s

SPAN = "serve/route"


def read(run):
    if getattr(run, "lookup", None) is None:
        return None
    durs = durations_s(run, SPAN)
    if not durs or len(durs) != run.counters.get("serve_batches_total", 0):
        return None
    return sum(durs) / len(durs) * 1e3
