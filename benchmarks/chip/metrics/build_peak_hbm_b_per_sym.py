"""build_peak_hbm_b_per_sym: the fullest chip's peak of HBM held after
the window, buffers in use plus the programs' reserved scratch
(``harness.runs.peak_bytes``), over the text's symbols.  It caps the text
one chip can index, so a speed-up bought with memory shows here."""


def read(run):
    if not run.builds or not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / run.n
