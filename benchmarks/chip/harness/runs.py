"""What every loop shares: the ``Run`` it fills, the ``Check`` of a
number against its limit, the hooks around its window, and the program's
cache, configuration and counters.

A loop is a file ``loops/<name>.py`` (named by a traffic mix's ``loop``)
with three functions:

* ``drive(run, seconds, hooks)`` — set-up, then the window; fills ``run``
  with what the end-to-end metrics and the per-layer readers read;
* ``check(run)``  — compares what the window produced with the plain
  reference, appending to ``run.checks``;
* ``control(run)`` — fills ``run`` as if the control (the reference with
  its shortcut) had produced the window's output, for ``check`` to judge.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import time
from pathlib import Path

# JAX's persistent compilation cache: fixed, inside the checkout, listed
# in .gitignore, so that every run after a cell's first loads its programs.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


@dataclasses.dataclass
class Check:
    """One number compared with its limit: at most ``limit``, or at least
    it where ``at_least``."""

    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if self.at_least:
            return self.value >= self.limit
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    cell: str
    config: dict
    traffic: dict
    seed: int
    n: int                        # real symbols of the text
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    builds: list = dataclasses.field(default_factory=list)
    latencies_s: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    n_leaves: int = 0
    memory_peak_bytes: int | None = None
    compiles_setup: int = 0
    compiles_in_window: int = 0
    device_kind: str = ""
    trace: object = None          # harness.trace.Trace of a --trace 1 run
    lookup: dict | None = None    # a lookup loop's answers, for its check
    text: object = None           # a lookup loop's indexed text
    gc_pauses: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)

    @property
    def alphabet_size(self) -> int:
        return len(self.config["symbols"])

    def peaks(self) -> dict:
        from harness.peaks import peaks
        return peaks(self.device_kind)


def use_cache() -> str:
    """Give the program the benchmark's cache directory, let it point
    JAX's cache there, and cache every program, however quick to compile."""
    import jax
    from repro.launch.compile_cache import use_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def era_config(config: dict):
    from repro.core.api import EraConfig
    return EraConfig(**config["era"])


def program_alphabet(config: dict):
    """The program's alphabet for the configuration; its letters have to
    be the configuration's, in the same order, so codes agree."""
    from repro.core.alphabet import ALPHABETS
    a = ALPHABETS[config["alphabet"]]
    if a.symbols != config["symbols"]:
        raise ValueError(f"alphabet {config['alphabet']!r} has letters "
                         f"{a.symbols!r}, the configuration {config['symbols']!r}")
    return a


class Hooks:
    """What the caller of a loop wants around the window: a clock for the
    set-up, compile counting, tracing and the program's own spans."""

    def __init__(self, t_start: float, compile_count, window_ctx,
                 obs_on: bool):
        self.t_start = t_start
        self.compile_count = compile_count    # () -> compilations so far
        self.window_ctx = window_ctx          # () -> context manager
        self.obs_on = obs_on


class GcPauses:
    """Python's collector, made quiet for the window and watched in it.

    ``settle`` collects and freezes what set-up made (the benchmark's own
    request pool and texts among it), so the window's collections scan
    only what the window makes; every pause in the window is still
    counted, and logged by the run."""

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self._t0 = 0.0

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            dt = time.perf_counter() - self._t0
            self.count += 1
            self.total_s += dt
            self.max_s = max(self.max_s, dt)

    def settle(self) -> None:
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._on)

    def done(self, run: "Run") -> None:
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)
        run.gc_pauses = {"count": self.count, "total_s": self.total_s,
                         "max_s": self.max_s}


def obs_counters() -> dict:
    from repro import obs
    out: dict[str, float] = {}
    for c in obs.metrics().snapshot()["counters"]:
        out[c["name"]] = out.get(c["name"], 0.0) + float(c["value"])
    return out


def obs_begin(hooks: Hooks) -> dict:
    if not hooks.obs_on:
        return {}
    from repro import obs
    obs.tracer().clear()
    return obs_counters()


def obs_end(hooks: Hooks, run: Run, before: dict) -> None:
    if not hooks.obs_on:
        return
    from repro import obs
    after = obs_counters()
    run.counters = {k: v - before.get(k, 0.0) for k, v in after.items()}
    run.spans = obs.tracer().events()


def peak_bytes() -> int | None:
    """The fullest chip's peak of HBM held: buffers in use plus the
    scratch reserved for compiled programs.  A TPU reserves a program's
    temporaries apart from the buffers it counts as in use, and the
    elastic step's temporaries can be most of a build's HBM."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None
