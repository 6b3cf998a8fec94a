"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each per-layer metric is
named in ``per_layer``.  Each resolves to one file under the benchmark's
directory:

* ``configs/<config>.json``  — the deployment (text, size, EraConfig);
* ``traffic/<traffic>.json`` — the traffic mix's parameters, which name
  its ``loop``;
* ``loops/<loop>.py``        — the loop that drives the system under the
  mix, checks what it produced and reads the control
  (``drive``, ``check``, ``control``; see ``harness/runs.py``);
* ``metrics/<metric>.py``    — one reader, ``read(run) -> float | None``.

A later change adds a cell, a configuration or a metric by adding files
and entries, never by editing a file that is already here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


class SpecError(ValueError):
    """A name in BENCHMARK.json that resolves to nothing, or a bad file."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]   # the cell's end-to-end metric entries
    per_layer: list[dict]    # the cell's per-layer metric entries


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _json_file(path: Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    return json.loads(path.read_text())


def config_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "configs" / f"{name}.json"


def traffic_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "traffic" / f"{name}.json"


def metric_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "metrics" / f"{name}.py"


def loop_file(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "loops" / f"{name}.py"


def applies(metric: dict, cell: str) -> bool:
    """A metric with a ``workloads`` key is reported only in those cells."""
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(bench: dict, workload: str,
                 bench_dir: Path = BENCH_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = _json_file(config_file(w["config"], bench_dir),
                        f"config {w['config']!r}")
    traffic = _json_file(traffic_file(w["traffic"], bench_dir),
                         f"traffic {w['traffic']!r}")
    if not loop_file(traffic.get("loop", ""), bench_dir).is_file():
        raise SpecError(f"traffic {w['traffic']!r} names loop "
                        f"{traffic.get('loop')!r}, which has no file "
                        f"{loop_file(traffic.get('loop', ''), bench_dir)}")
    e2e = [m for m in bench["end_to_end"] if applies(m, workload)]
    layer = [m for m in bench["per_layer"] if applies(m, workload)]
    for m in layer:
        if not metric_file(m["name"], bench_dir).is_file():
            raise SpecError(f"per-layer metric {m['name']!r}: no reader "
                            f"{metric_file(m['name'], bench_dir)}")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def _load(path: Path, kind: str, name: str, needs: tuple[str, ...]):
    if not path.is_file():
        raise SpecError(f"no {kind} {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in needs if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"{path} defines no {', '.join(missing)}")
    return mod


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _load(metric_file(name, bench_dir), "metric", name, ("read",)).read


def load_loop(name: str, bench_dir: Path = BENCH_DIR):
    """The module ``loops/<name>.py``: ``drive``, ``check``, ``control``."""
    return _load(loop_file(name, bench_dir), "loop", name,
                 ("drive", "check", "control"))
