"""serve_sync_ms.query: host milliseconds per batch blocked on the
batch's device result (the server's ``serve/consume_sync`` spans).
Layer: launch/serving.py."""

from harness.spans import mean_ms


def read(run):
    if getattr(run, "lookup", None) is None:
        return None
    return mean_ms(run, "serve/consume_sync")
