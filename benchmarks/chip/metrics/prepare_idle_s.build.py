"""prepare_idle_s.build: seconds per build in which the device ran
nothing while the host was inside the elastic prepare loop (the
``prepare/batch_loop`` span, read on the profiler trace's clock): the
blocking read-back of each iteration's active counts and the host's turns
between steps.  Layer: core/prepare.py."""

from harness.spans import idle_inside_s


def read(run):
    if not run.builds:
        return None
    idle = idle_inside_s(run.trace, "prepare/batch_loop")
    return None if idle is None else idle / len(run.builds)
