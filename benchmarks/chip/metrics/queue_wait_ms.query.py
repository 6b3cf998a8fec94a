"""queue_wait_ms.query: milliseconds per batch that its oldest request
waited from admission to the batch's dispatch (the server's
``serve/queue_wait`` spans).  Layer: launch/serving.py."""

from harness.spans import mean_ms


def read(run):
    if getattr(run, "lookup", None) is None:
        return None
    return mean_ms(run, "serve/queue_wait")
