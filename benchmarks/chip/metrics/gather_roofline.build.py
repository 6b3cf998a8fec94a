"""gather_roofline.build: the elastic loop's paged reads as a share of
their HBM roofline.  Bytes are those the algorithm needs
(``harness/roofline.gather_bytes``): every active row of every iteration
reads ``w`` symbols at the alphabet's packed width, its 4-byte offset,
and writes its packed key words.  Least time is those bytes over the
tabled HBM peak; measured time is the device time of the read kernel's
ops in the window."""

from harness import roofline
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
    bits = roofline.packed_bits(run.alphabet_size)
    need = sum(roofline.gather_bytes(a, w, bits)
               for b in run.builds for a, w in zip(b["active"], b["ranges"]))
    return roofline.share(need, run.peaks()["hbm_bw"], secs)
