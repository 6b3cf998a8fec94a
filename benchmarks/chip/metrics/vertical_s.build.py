"""vertical_s.build: seconds of the vertical partition per build
(``BuildReport.t_vertical``, the program's own host timing, which ends on
host results).  Layer: core/vertical.py, kernels/kmer_histogram.py."""


def read(run):
    if not run.builds:
        return None
    return sum(b["t_vertical"] for b in run.builds) / len(run.builds)
