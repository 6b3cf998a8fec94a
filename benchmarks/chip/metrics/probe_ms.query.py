"""probe_ms.query: device milliseconds per dispatched batch in the query
engine's probe kernel (``core/query.py`` binary search through
``pattern_probe_words``), over the window's batches."""

from harness.trace import hlo_name

MARKS = ("pattern_probe",)


def is_probe(op) -> bool:
    return any(m in hlo_name(op) for m in MARKS)


def read(run):
    if getattr(run, "lookup", None) is None or run.trace is None:
        return None
    batches = run.counters.get("serve_batches_total", 0)
    secs = run.trace.op_seconds(is_probe)
    if not batches or secs <= 0:
        return None
    return secs / batches * 1e3
