"""build_msym_per_s: symbols indexed per second, in millions — every
symbol of every whole build in the window over all the window's time.
Host clock, each build ended by ``block_until_ready``."""


def read(run):
    if not run.builds or run.window_s <= 0:
        return None
    return len(run.builds) * run.n / run.window_s / 1e6
