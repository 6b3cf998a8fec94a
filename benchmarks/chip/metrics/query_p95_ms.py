"""query_p95_ms: the 95th percentile, in ms, of the requests answered in
the window, each timed from its submission (from when it was due, in an
open loop) to its answer's receipt by the loop.  Requests the server
refused at admission are counted in the run's ``failed``, not here.
Host clock."""

import numpy as np


def read(run):
    if getattr(run, "lookup", None) is None or not run.latencies_s:
        return None
    return float(np.percentile(np.asarray(run.latencies_s), 95)) * 1e3
