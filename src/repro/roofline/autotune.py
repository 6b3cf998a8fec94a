"""Roofline-driven tile autotuning for the gather-style Pallas kernels.

Every kernel in :mod:`repro.kernels` takes ``tile`` as a static argument
that never changes results — for the paged-read kernels it is the number
of reads per grid step (:func:`repro.kernels.tiles.read_block` rounds it
to a legal TPU block), for ``kmer_histogram`` the symbols per step.
Historically every call used a hard-coded ``tile=2048``.  This module
picks the tile per ``(backend, kernel, dtype-bits, n-bucket)`` instead:

* **Model pick** — a VMEM/HBM roofline model with the constants of
  :mod:`repro.roofline.analysis`: the per-step time model is
  ``max(t_dispatch, dma_bytes / HBM_BW)`` with ``2 * tile * 4`` bytes
  moved per step.  The DMA term only reaches the fixed dispatch overhead
  at tiles far larger than the VMEM budget allows, so the model selects
  the SMALLEST feasible candidate: ``tile >= w_cap``, ``tile`` large
  enough that the per-step DMA amortizes the issue overhead (``tile * 4
  >= DMA_MIN_BYTES``), and ``2 * tile * 4`` under the per-step VMEM
  budget.  The model dates from the older one-row window kernels and
  has not been refit to the paged read.  Same histogram-bucket idiom as
  :func:`repro.core.build.bucket_pad_widths` — ``n`` buckets to powers
  of two so one table entry covers a whole workload size class.
* **Measured fallback** — :func:`measured_sweep` times a caller-supplied
  thunk per candidate and keeps the argmin; used where the model's
  constants are wrong (e.g. interpret mode, exotic hosts) and by the
  ``--autotune`` driver flags.

Chosen tiles persist to a small JSON table (:class:`AutotuneTable`) that
:mod:`repro.kernels.ops` consults at dispatch via :func:`tile_for`.
Resolution order per key: explicit on-disk table entry → roofline model
(when ``REPRO_AUTOTUNE=model`` or a table is active) → the kernel's
static default.  A table is read only from the file
``REPRO_AUTOTUNE_TABLE`` names (never from a default path); dispatch
only ever READS the table — writing happens solely through
:meth:`AutotuneTable.save` (driver flags / sweeps), so imports never
touch disk.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading

from repro.roofline.analysis import HBM_BW

# Per-step VMEM budget for the two-tile halo window + output row.  VMEM
# is ~16 MB/core (pallas guide); the double-buffered pipeline wants many
# steps in flight, so one step gets a conservative slice.
VMEM_STEP_BUDGET = 1 << 20          # 1 MiB
DMA_MIN_BYTES = 2048                # below this a DMA is issue-bound
DISPATCH_OVERHEAD_S = 1e-6          # fixed per-grid-step cost model

# Candidate tiles: powers of two spanning the kernels' historical
# defaults (512 for kmer_histogram, 2048 everywhere else).
TILE_CANDIDATES = (512, 1024, 2048, 4096, 8192)

# Static per-kernel defaults — what dispatch used before autotuning.
DEFAULT_TILES = {"kmer_histogram": 512}
DEFAULT_TILE = 2048


def n_bucket(n: int) -> int:
    """Power-of-two workload-size bucket for a string of ``n`` symbols
    (one table entry covers the whole class; same idiom as the
    node-build pad-width buckets)."""
    return 1 << max(int(n) - 1, 1).bit_length()


@dataclasses.dataclass(frozen=True)
class TileScore:
    """Roofline terms for one candidate tile."""

    tile: int
    vmem_bytes: int      # two-tile int32 halo window per grid step
    dma_bytes: int       # HBM bytes moved per grid step
    t_step: float        # modeled per-step seconds

    @property
    def feasible(self) -> bool:
        return self.vmem_bytes <= VMEM_STEP_BUDGET


def score_tile(tile: int, *, out_bytes: int = 256) -> TileScore:
    vmem = 2 * tile * 4 + out_bytes
    dma = 2 * tile * 4
    t = max(DISPATCH_OVERHEAD_S, dma / HBM_BW)
    return TileScore(tile=tile, vmem_bytes=vmem, dma_bytes=dma, t_step=t)


def model_pick(kernel: str, *, w_cap: int = 0,
               candidates=TILE_CANDIDATES) -> int:
    """The VMEM/HBM-model tile choice: smallest candidate that (a) fits
    the per-step VMEM budget, (b) covers the kernel's read width
    (``w <= tile`` is asserted by every kernel), and (c) moves enough
    bytes per DMA to amortize the issue overhead.  Falls back to the
    kernel's static default when nothing qualifies."""
    feas = [score_tile(t) for t in sorted(candidates)
            if t >= max(w_cap, 1) and t * 4 >= DMA_MIN_BYTES]
    feas = [s for s in feas if s.feasible]
    if not feas:
        return max(DEFAULT_TILES.get(kernel, DEFAULT_TILE), w_cap)
    best = min(feas, key=lambda s: (s.t_step, s.tile))
    return best.tile


def measured_sweep(run_fn, candidates=TILE_CANDIDATES, *, w_cap: int = 0,
                   repeats: int = 3):
    """Measured fallback: time ``run_fn(tile)`` per feasible candidate
    and return ``(best_tile, {tile: seconds})``.  ``run_fn`` must block
    until the device result is ready (callers wrap with
    ``jax.block_until_ready``)."""
    import time

    timings: dict[int, float] = {}
    for tile in sorted(candidates):
        if tile < max(w_cap, 1) or not score_tile(tile).feasible:
            continue
        run_fn(tile)  # warmup / compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            run_fn(tile)
            best = min(best, time.perf_counter() - t0)
        timings[tile] = best
    if not timings:
        raise ValueError("no feasible tile candidate for "
                         f"w_cap={w_cap} among {candidates}")
    return min(timings, key=timings.get), timings


def table_key(backend: str, kernel: str, bits: int, nb: int) -> str:
    return f"{backend}/{kernel}/b{bits}/n{nb}"


class AutotuneTable:
    """The small on-disk tile table: ``key -> {"tile": int, "source":
    "model" | "measured"}`` plus free-form metadata per entry."""

    def __init__(self, entries: dict | None = None,
                 path: str | None = None):
        self.entries: dict[str, dict] = dict(entries or {})
        self.path = path

    # ---- persistence -------------------------------------------------------

    @classmethod
    def load(cls, path: str) -> "AutotuneTable":
        with open(path) as f:
            payload = json.load(f)
        entries = payload.get("entries", payload)
        return cls(entries=entries, path=path)

    def save(self, path: str | None = None) -> str:
        path = path or self.path
        if not path:
            raise ValueError("AutotuneTable.save needs a path")
        with open(path, "w") as f:
            json.dump({"version": 1, "entries": self.entries}, f,
                      indent=2, sort_keys=True)
        self.path = path
        return path

    # ---- population --------------------------------------------------------

    def put(self, backend: str, kernel: str, bits: int, n: int, tile: int,
            *, source: str = "model", **meta) -> None:
        entry = {"tile": int(tile), "source": source}
        entry.update(meta)
        self.entries[table_key(backend, kernel, bits, n_bucket(n))] = entry

    def get(self, backend: str, kernel: str, bits: int, n: int):
        e = self.entries.get(table_key(backend, kernel, bits, n_bucket(n)))
        return int(e["tile"]) if e else None

    def fill_model(self, backend: str, kernels_w: dict[str, int],
                   bits: int, n: int) -> None:
        """Model-pick an entry per kernel for one workload class.
        ``kernels_w``: kernel name -> read-width cap."""
        for kernel, w_cap in kernels_w.items():
            self.put(backend, kernel, bits, n,
                     model_pick(kernel, w_cap=w_cap), source="model",
                     w_cap=int(w_cap))


# ---------------------------------------------------------------------------
# Dispatch-side resolution (consulted by repro.kernels.ops)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_ACTIVE: AutotuneTable | None = None
_LOADED_FROM: str | None = None


def default_table_path() -> str | None:
    """The table named by ``REPRO_AUTOTUNE_TABLE``, or None: dispatch
    never reads a file the environment did not name."""
    return os.environ.get("REPRO_AUTOTUNE_TABLE") or None


def set_active_table(table: AutotuneTable | None) -> None:
    """Install (or clear) the process-wide table — the driver-flag hook;
    also used by tests to pin a choice without touching disk."""
    global _ACTIVE, _LOADED_FROM
    with _LOCK:
        _ACTIVE = table
        _LOADED_FROM = getattr(table, "path", None) if table else None


def active_table() -> AutotuneTable | None:
    """The installed table, lazily loading the named file once.  A
    missing file is remembered as 'no table' — dispatch stays one dict
    probe, no per-call stat."""
    global _ACTIVE, _LOADED_FROM
    with _LOCK:
        if _ACTIVE is not None:
            return _ACTIVE
        path = default_table_path()
        if path is None or _LOADED_FROM == path:  # unnamed, or missing
            return None
        _LOADED_FROM = path
        if os.path.exists(path):
            _ACTIVE = AutotuneTable.load(path)
        return _ACTIVE


def tile_for(kernel: str, *, backend: str, bits: int, n: int,
             w_cap: int = 0) -> int:
    """The tile :mod:`repro.kernels.ops` uses for one dispatch.

    Table entry → model pick (when ``REPRO_AUTOTUNE=model`` or a table
    is active) → static default.  The result always satisfies the
    kernels' ``w <= tile`` contract."""
    table = active_table()
    if table is not None:
        tile = table.get(backend, kernel, bits, n)
        if tile is not None:
            return max(tile, w_cap)
    mode = os.environ.get("REPRO_AUTOTUNE", "")
    if mode == "model" or table is not None:
        return model_pick(kernel, w_cap=w_cap)
    if mode not in ("", "off", "table"):
        raise ValueError(f"unknown REPRO_AUTOTUNE={mode!r}; "
                         "choose 'off', 'table' or 'model'")
    return max(DEFAULT_TILES.get(kernel, DEFAULT_TILE), w_cap)
