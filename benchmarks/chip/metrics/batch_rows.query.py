"""batch_rows.query: real rows per dispatched batch in the window
(``serve_rows_real_total`` / ``serve_batches_total``; route-cache hits
take no row).  Layer: launch/serving.py."""


def read(run):
    if getattr(run, "lookup", None) is None:
        return None
    batches = run.counters.get("serve_batches_total", 0)
    if not batches:
        return None
    return run.counters.get("serve_rows_real_total", 0) / batches
