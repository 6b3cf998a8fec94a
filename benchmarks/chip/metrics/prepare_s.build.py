"""prepare_s.build: seconds of the elastic prepare loop per build
(``BuildReport.t_prepare``; the loop reads its active count back every
iteration, so the time ends synced).  Layer: core/prepare.py."""


def read(run):
    if not run.builds:
        return None
    return sum(b["t_prepare"] for b in run.builds) / len(run.builds)
