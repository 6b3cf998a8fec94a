"""gather_ms.build: device milliseconds per build in the paged read
kernel of the elastic loop (``kernels/tiles.py`` under
``packed_gather.py`` / ``range_gather.py``), summed over the window's ops
whose name marks that kernel, over the window's builds."""

from harness.trace import hlo_name

MARKS = ("range_gather",)


def is_gather(op) -> bool:
    return any(m in hlo_name(op) for m in MARKS)


def read(run):
    if not run.builds or run.trace is None:
        return None
    secs = run.trace.op_seconds(is_gather)
    if secs <= 0:
        return None
    return secs / len(run.builds) * 1e3
