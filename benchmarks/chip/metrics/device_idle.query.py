"""device_idle.query: percent of the traced lookup window in which no
operation ran on the device (1 - union of op intervals over the window,
averaged over the chips used)."""


def read(run):
    if getattr(run, "lookup", None) is None or run.trace is None:
        return None
    idle = run.trace.idle_share
    return None if idle is None else 100.0 * idle
