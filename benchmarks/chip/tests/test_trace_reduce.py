"""``trace_reduce`` on hand-built planes and on a trace recorded on a
TPU v5e (a trimmed copy of the first traced round)."""

import gzip
import json
from pathlib import Path

import pytest

import trace_reduce as tr

HERE = Path(__file__).resolve().parent


def _ev(name, start, dur):
    return {"name": name, "start_ns": float(start), "duration_ns": float(dur)}


def _planes():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        _ev("bench.window", 100, 1000),
        _ev("bench.train_all", 100, 500),
        _ev("bench.merge", 600, 400),
    ]}]}
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        _ev("fusion.1", 0, 150),      # starts before the window
        _ev("compose_pallas.3", 200, 100),
        _ev("fusion.2", 250, 100),    # overlaps the kernel
        _ev("fusion.4", 700, 100),
        _ev("copy.9", 1200, 50),      # after the window
    ]}]}
    return [host, dev]


def test_busy_window_and_gaps_by_hand():
    red = tr.reduce_planes(_planes())
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy within [100, 1100]: [100,150] + [200,350] + [700,800]
    assert red["busy_s"] == pytest.approx((50 + 150 + 100) * 1e-9)
    # idle gaps [150,200] and [350,700] have their midpoints in
    # train_all ([100,600]); [800,1100] has its midpoint in merge
    assert red["gaps"]["bench.train_all"] == pytest.approx((50 + 350) * 1e-9)
    assert red["gaps"]["bench.merge"] == pytest.approx(300e-9)
    assert red["ops"]["compose_pallas.3"]["count"] == 1
    assert "copy.9" not in red["ops"]


def test_breakdown_groups_instances():
    bd = tr.breakdown(tr.reduce_planes(_planes()))
    assert bd["device_ops"][0][0] == "fusion"
    assert bd["device_ops"][0][1] == pytest.approx(350e-9)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_no_window_reads_nothing():
    planes = _planes()
    planes[0]["lines"][0]["events"] = planes[0]["lines"][0]["events"][1:]
    assert tr.reduce_planes(planes) is None


def test_same_name_different_shapes_are_kept_apart():
    """Instruction numbering restarts in every program: two programs'
    ``%_compose_pallas_3d.51`` of different shapes are two ops, each
    with its own count and time."""
    def text(n):
        return (f"%_compose_pallas_3d.51 = f32[1,{n},2304]{{2,1,0}} "
                f"custom-call(f32[1,{n},128]{{2,1,0}} %a, "
                f"f32[128,2304]{{1,0}} %b), "
                f'custom_call_target="tpu_custom_call"')
    planes = _planes()
    planes[1]["lines"][0]["events"] += [
        _ev(text(256), 400, 10), _ev(text(256), 420, 10),
        _ev(text(1024), 440, 30)]
    red = tr.reduce_planes(planes)
    assert red["ops"][text(256)]["count"] == 2
    assert red["ops"][text(256)]["seconds"] == pytest.approx(20e-9)
    assert red["ops"][text(1024)]["count"] == 1
    assert red["ops"][text(1024)]["seconds"] == pytest.approx(30e-9)
    groups = dict(tr.breakdown(red)["device_ops"])
    assert groups["_compose_pallas_3d"] == pytest.approx(50e-9)


def test_recorded_v5e_trace():
    path = HERE / "trace_v5e_small.json.gz"
    with gzip.open(path, "rt") as f:
        planes = json.load(f)
    red = tr.reduce_planes(planes)
    assert red is not None and red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    bd = tr.breakdown(red)
    assert sum(v for _, v in bd["idle_gaps"]) <= red["window_s"] - red["busy_s"] + 1e-9
