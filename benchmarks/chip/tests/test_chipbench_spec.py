"""Every name in BENCHMARK.json resolves to its file; a file added with
its entry is picked up with no edit to any file already there; the
command refuses to measure without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench_util import BENCH_DIR, ROOT
from harness import spec
from harness.runs import Run

BENCH = spec.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = spec.resolve_cell(BENCH, cell)
    loop = spec.load_loop(c.traffic["loop"])
    assert all(callable(getattr(loop, f)) for f in ("drive", "check",
                                                    "control"))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.load_reader(m["name"]))


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_file(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    path = ROOT / entry["file"]
    assert path == spec.config_file(config)
    body = json.loads(path.read_text())
    assert body["name"] == config
    assert set(entry["reduced"]) == set(body["reduced"])
    assert any(w["config"] == config for w in BENCH["workloads"])


def test_names_units_and_moves():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.resolve_cell(BENCH, "no_such.cell")
    bad = json.loads(json.dumps(BENCH))
    bad["workloads"][0]["traffic"] = "no_such_traffic"
    with pytest.raises(spec.SpecError):
        spec.resolve_cell(bad, bad["workloads"][0]["name"])
    with pytest.raises(spec.SpecError):
        spec.load_loop("no_such_loop")


def test_added_files_are_picked_up_without_edits(tmp_path):
    """A later change adds a configuration, a traffic mix with a loop of
    its own, a metric and a cell as new files and entries; the harness
    finds them by name."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = json.loads(spec.config_file("dna_chr").read_text())
    cfg.update(name="dna_chr_small", n=1 << 20)
    (bench_dir / "configs" / "dna_chr_small.json").write_text(json.dumps(cfg))
    traffic = json.loads(spec.traffic_file("seeds").read_text())
    traffic.update(loop="burst_lookup", outstanding=1024)
    (bench_dir / "traffic" / "seeds_deep.json").write_text(json.dumps(traffic))
    (bench_dir / "loops" / "burst_lookup.py").write_text(
        "from harness import lookup\n"
        "check, control = lookup.check, lookup.control\n"
        "def drive(run, seconds, hooks):\n    run.window_s = seconds\n")
    (bench_dir / "metrics" / "queue_depth.query.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="dna_chr_small",
                                 file="benchmarks/chip/configs/dna_chr_small.json"))
    bench["workloads"].append({"name": "dna_chr_small.seeds_deep",
                               "config": "dna_chr_small",
                               "traffic": "seeds_deep", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "queue_depth.query", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "server", "moves": "query_per_s",
                               "workloads": ["dna_chr_small.seeds_deep"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("query_per_s", "query_p95_ms"):
            m["workloads"].append("dna_chr_small.seeds_deep")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.resolve_cell(spec.load_benchmark(tmp_path),
                             "dna_chr_small.seeds_deep", bench_dir)
    assert cell.config["n"] == 1 << 20
    assert cell.traffic["outstanding"] == 1024
    loop = spec.load_loop(cell.traffic["loop"], bench_dir)
    run = Run(cell=cell.name, config=cell.config, traffic=cell.traffic,
              seed=1, n=cell.config["n"])
    loop.drive(run, 3.0, None)
    assert run.window_s == 3.0
    assert [m["name"] for m in cell.per_layer] == ["queue_depth.query"]
    assert spec.load_reader("queue_depth.query", bench_dir)(None) == 42.0
    assert {m["name"] for m in cell.end_to_end} == {
        "query_per_s", "query_p95_ms", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before, "no file that was there was edited"


def _run_cmd(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = _run_cmd(ROOT)
    assert out.returncode != 0
    assert "nothing was measured" in out.stderr
    assert '"correct"' not in out.stdout


def test_refuses_in_a_bare_benchmark_directory(tmp_path):
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run_cmd(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
