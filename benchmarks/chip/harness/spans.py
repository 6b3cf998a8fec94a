"""What the program's own spans say, for the per-layer readers.

Two views of one span.  ``run.spans`` holds what the program's recorder
(``repro.obs``) timed on the host in a ``--trace 1`` run.  The profiler
trace holds the same span as a host annotation of the same name, on the
device trace's clock, so the device's idle time inside it can be read.
A program that lacks a span gives nothing to read: each function then
returns None, and so does the reader.
"""

from __future__ import annotations

import bisect


def durations_s(run, name: str) -> list[float]:
    """Seconds of every span called ``name`` that the recorder kept."""
    return [e["dur_ns"] * 1e-9 for e in run.spans if e["name"] == name]


def mean_ms(run, name: str) -> float | None:
    """Mean milliseconds of the spans called ``name``."""
    durs = durations_s(run, name)
    return sum(durs) / len(durs) * 1e3 if durs else None


def idle_inside_s(trace, name: str) -> float | None:
    """Seconds in which the busiest chip ran nothing while the host was
    inside an event called ``name``, clipped to the window; summed over
    those events.  None without such an event or without a device."""
    if trace is None or not trace.device:
        return None
    events = [(max(h[1], trace.t0), min(h[1] + h[2], trace.t1))
              for h in trace.host if h[0] == name]
    if not events:
        return None
    plane = max(trace.device, key=lambda p: len(trace.device[p]))
    busy = trace._busy_intervals(trace.device[plane])   # sorted, disjoint
    ends = [b for _, b in busy]
    idle_ns = 0.0
    for a, b in events:
        covered = 0.0
        for x, y in busy[bisect.bisect_right(ends, a):]:
            if x >= b:
                break
            covered += min(b, y) - max(a, x)
        idle_ns += max(b - a, 0.0) - covered
    return idle_ns * 1e-9
