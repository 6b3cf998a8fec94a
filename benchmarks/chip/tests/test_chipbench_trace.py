"""The trace reduction: busy time, idle share, kernel time and idle gaps,
on hand-made records and on a small trace recorded on a v5e chip."""

import gzip
import json
from pathlib import Path

import pytest

import chipbench_util  # noqa: F401  (puts the harness on sys.path)
from harness import spec
from harness.trace import WINDOW, Trace

RECORDED = Path(__file__).parent / "data" / "trace_v5e_small.json.gz"


def _records():
    ms = 1e6
    return {
        "host": [[WINDOW, 0.0, 100 * ms],
                 ["bench/pump", 10 * ms, 30 * ms],
                 ["bench/submit", 60 * ms, 15 * ms],
                 ["outside", 200 * ms, 5 * ms]],
        "device": {
            "/device:TPU:0": [
                ["%fusion.1 = u32[8] fusion(u32[8] %p)", 0.0, 10 * ms, {}],
                ["fusion.2", 5 * ms, 10 * ms, {}],          # overlaps
                ["sort.3", 50 * ms, 10 * ms, {"hlo_category": "sort"}],
                ["late", 150 * ms, 10 * ms, {}],           # after window
            ],
        },
    }


def test_busy_idle_and_window():
    tr = Trace(_records())
    assert tr.window_s == pytest.approx(0.1)
    assert tr.busy_s == pytest.approx(0.025)          # union, not sum
    assert tr.idle_share == pytest.approx(0.75)
    assert tr.chips == 1


def test_op_seconds_and_top_ops():
    tr = Trace(_records())
    assert tr.op_seconds(lambda o: o.name.startswith("sort")) == \
        pytest.approx(0.01)
    top = dict(tr.top_ops())
    assert top["fusion.2"] == pytest.approx(0.01)
    assert top["fusion.1"] == pytest.approx(0.01)     # named by its HLO head
    assert "late" not in top


def test_idle_gaps_named_by_host():
    gaps = Trace(_records()).idle_gaps()
    assert gaps[0] == ["host", pytest.approx(0.04)]    # 60-100 ms; submit ends at 75
    names = [g[0] for g in gaps]
    assert "bench/pump" in names                       # 15-50 ms
    assert len(gaps) == 2


def test_window_annotation_required():
    rec = _records()
    rec["host"] = rec["host"][1:]
    with pytest.raises(ValueError):
        Trace(rec)


def test_devices_averaged():
    rec = _records()
    rec["device"]["/device:TPU:1"] = [["x", 0.0, 50e6, {}]]
    tr = Trace(rec)
    assert tr.chips == 2
    assert tr.busy_s == pytest.approx((0.025 + 0.05) / 2)


@pytest.mark.parametrize("part", ["build", "lookup"])
def test_recorded_v5e_trace(part):
    """Slices of real traced windows on a v5e chip (an elastic-loop stretch
    of a 2^22 DNA build, and a tenth of a second of seed lookups): the
    device-trace readers find their kernels there, and the reduction gives
    what it gave when the slice was cut."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)[part]
    tr = Trace(rec["records"])
    assert tr.chips == 1
    assert tr.busy_s == pytest.approx(rec["busy_s"], rel=1e-12)
    assert tr.window_s == pytest.approx(rec["window_s"], rel=1e-12)
    assert 0 < tr.busy_s <= tr.window_s
    for name, expect in rec["expect"].items():
        mod = spec.load_reader(name).__globals__
        match = next(v for k, v in mod.items() if k.startswith("is_"))
        assert expect > 0
        assert tr.op_seconds(match) == pytest.approx(expect, rel=1e-12), name
    top = [name for name, _ in tr.top_ops()]
    kernel = {"build": "vmap_jit_range_gather_words__.2",
              "lookup": "pattern_probe_words.7"}[part]
    assert kernel in top
