"""Capture a profiler trace of the measured window and reduce it.

``capture`` wraps the window in ``jax.profiler`` tracing and a
``bench/window`` annotation.  ``extract`` turns the ``.xplane.pb`` into
plain records (JSON-able, so a small recorded trace can be kept as a test
fixture); ``Trace`` reduces them:

* device ops are the events of the ``XLA Ops`` line of each device plane;
* busy time is the union of their intervals inside the window, averaged
  over the chips that ran anything; idle share is 1 - busy / window;
* an idle gap is attributed to the shortest host event that covers its
  midpoint (the harness's own ``bench/...`` annotations, or the runtime's
  host events), else ``host``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
NAME_CHARS = 240   # an op's name is its HLO text; its head names it


@contextlib.contextmanager
def capture(directory: str, python_calls: bool):
    """Trace the block into ``directory``; the block is the window.
    ``python_calls`` also records every Python call on the host, which
    names the program's host functions in idle gaps but slows a loop that
    the host bounds."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1 if python_calls else 0
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def newest_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def extract(path: str) -> dict:
    """Plain records of one trace: ``device`` maps a device plane to its
    ops ``[name, start_ns, dur_ns, {stat: value}]``; ``host`` holds every
    host event ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if _is_device_plane(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    stats = {k: v for k, v in e.stats
                             if isinstance(v, (int, float))
                             or (isinstance(v, str) and len(v) <= 120)}
                    ops.append([e.name[:NAME_CHARS], float(e.start_ns),
                                float(e.duration_ns), stats])
            device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append([e.name, float(e.start_ns),
                                 float(e.duration_ns)])
    return {"device": device, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@dataclasses.dataclass
class Op:
    name: str
    start: float   # ns
    dur: float     # ns
    stats: dict


class Trace:
    """The reduction of one traced window."""

    def __init__(self, records: dict):
        spans = [h for h in records["host"] if h[0] == WINDOW]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW!r} annotation")
        name, start, dur = max(spans, key=lambda h: h[2])
        self.t0, self.t1 = start, start + dur
        self.host = [h for h in records["host"]
                     if h[1] < self.t1 and h[1] + h[2] > self.t0]
        self.device: dict[str, list[Op]] = {}
        for plane, ops in records["device"].items():
            inside = [Op(o[0], o[1], o[2], o[3]) for o in ops
                      if o[1] < self.t1 and o[1] + o[2] > self.t0]
            if inside:
                self.device[plane] = inside

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def chips(self) -> int:
        return len(self.device)

    def _busy_intervals(self, ops: list[Op]) -> list[tuple[float, float]]:
        return _union([(max(o.start, self.t0), min(o.start + o.dur, self.t1))
                       for o in ops])

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        if not self.device:
            return 0.0
        total = sum(b - a for ops in self.device.values()
                    for a, b in self._busy_intervals(ops))
        return total * 1e-9 / len(self.device)

    @property
    def idle_share(self) -> float | None:
        if not self.device or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, match) -> float:
        """Device seconds of the ops for which ``match(op)`` holds, summed
        over the window and averaged over the chips used."""
        if not self.device:
            return 0.0
        total = sum(o.dur for ops in self.device.values() for o in ops
                    if match(o))
        return total * 1e-9 / len(self.device)

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` op names with most device time.  A ``while`` op spans
        the ops of its body, which are ranked too."""
        per: dict[str, float] = {}
        for ops in self.device.values():
            for o in ops:
                key = hlo_name(o)
                per[key] = per.get(key, 0.0) + o.dur * 1e-9
        n = max(1, len(self.device))
        ranked = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[name, secs / n] for name, secs in ranked]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest idle gaps of the busiest chip, each named by
        what the host was doing."""
        if not self.device:
            return []
        plane = max(self.device, key=lambda p: len(self.device[p]))
        busy = self._busy_intervals(self.device[plane])
        gaps, prev = [], self.t0
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if prev < self.t1:
            gaps.append((prev, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_at((a + b) / 2), (b - a) * 1e-9]
                for a, b in gaps[:k]]

    def _host_at(self, t: float) -> str:
        covering = [h for h in self.host
                    if h[1] <= t <= h[1] + h[2] and h[0] != WINDOW]
        if not covering:
            return "host"
        return min(covering, key=lambda h: h[2])[0]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def hlo_name(op: Op) -> str:
    """The op's HLO instruction name: the device trace names an op by its
    HLO text, ``%name = type op(...)``; a kernel's name is the jitted
    function that holds its ``pallas_call``."""
    return op.name.split(" ", 1)[0].lstrip("%")
