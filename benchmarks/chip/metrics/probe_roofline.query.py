"""probe_roofline.query: the query engine's probes as a share of their
HBM roofline.  Bytes are those the algorithm needs
(``harness/roofline.probe_bytes``): each real batch row runs a lower and
an upper binary search over the suffix array, each probe reading the
pattern's length of text at a suffix, its 4-byte offset, and writing a
4-byte verdict.  Least time is those bytes over the tabled HBM peak;
measured time is the device time of the probe kernel's ops."""

from harness import roofline
from harness.trace import hlo_name

MARKS = ("pattern_probe",)


def is_probe(op) -> bool:
    return any(m in hlo_name(op) for m in MARKS)


def read(run):
    if getattr(run, "lookup", None) is None or run.trace is None:
        return None
    rows = run.counters.get("serve_rows_real_total", 0)
    secs = run.trace.op_seconds(is_probe)
    if not rows or secs <= 0:
        return None
    pool = run.lookup["pool"]
    mean_len = sum(len(p) for p in pool) / len(pool)
    probes = 2 * rows * roofline.search_probes(run.n_leaves)
    need = roofline.probe_bytes(int(probes), int(probes * mean_len),
                                roofline.packed_bits(run.alphabet_size))
    return roofline.share(need, run.peaks()["hbm_bw"], secs)
