"""query_per_s: requests completed in the window over the window's
time.  Host clock."""


def read(run):
    if getattr(run, "lookup", None) is None or run.window_s <= 0:
        return None
    return len(run.latencies_s) / run.window_s
