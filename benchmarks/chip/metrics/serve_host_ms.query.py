"""serve_host_ms.query: host milliseconds per batch in the server's
pad/pack (the program's ``serve/pad_pack`` spans in the window).
Layer: launch/serving.py."""

SPAN = "serve/pad_pack"


def read(run):
    if getattr(run, "lookup", None) is None:
        return None
    durs = [e["dur_ns"] for e in run.spans if e["name"] == SPAN]
    if not durs:
        return None
    return sum(durs) / len(durs) * 1e-6
