"""prepare_iterations.build: elastic-range iterations per build
(``PrepareStats.iterations``).  Layer: core/prepare.py."""


def read(run):
    if not run.builds:
        return None
    return sum(b["iterations"] for b in run.builds) / len(run.builds)
