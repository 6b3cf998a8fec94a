"""sort_ms.build: device milliseconds per build in the XLA sort of the
elastic step (the fused sort key), summed over the window's sort ops
(HLO ``sort``), over the window's builds."""

from harness.trace import hlo_name

MARKS = ("sort",)


def is_sort(op) -> bool:
    return hlo_name(op).split(".")[0] in MARKS


def read(run):
    if not run.builds or run.trace is None:
        return None
    secs = run.trace.op_seconds(is_sort)
    if secs <= 0:
        return None
    return secs / len(run.builds) * 1e3
