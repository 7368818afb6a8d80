"""``program_spans``: idle gaps charged to the program's own spans on the
window's thread, and the per-layer readers of the program's spans and
byte counters, on hand-built planes and contexts and on the recorded
v5e trace."""

import gzip
import json
from pathlib import Path

import pytest

import harness
import program_spans as ps
import trace_reduce as tr

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
NEW = ("merge.host_prep_ms", "merge.h2d_mb", "trainer.d2h_mb",
       "trainer.estimate_ms")


def reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py", name)


def _ev(name, start, dur):
    return {"name": name, "start_ns": float(start), "duration_ns": float(dur)}


def _planes(program: bool):
    """The window [100, 1100]: train_all [100, 600], merge [600, 1000];
    with ``program``, the program's spans inside them on the main
    thread and a prefetch-thread span across the whole window."""
    main = [_ev("bench.window", 100, 1000), _ev("bench.train_all", 100, 500),
            _ev("bench.merge", 600, 400)]
    lines = [{"name": "python", "events": main}]
    if program:
        main += [_ev("trainer.local_train", 110, 480),
                 _ev("trainer.estimate", 380, 200),
                 _ev("aggregate.merge", 610, 380),
                 _ev("merge.prep", 620, 200)]
        lines.append({"name": "prefetch", "events": [
            _ev("trainer.host_stage", 0, 2000)]})
    host = {"name": "/host:CPU", "lines": lines}
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        _ev("fusion.1", 0, 150), _ev("compose_pallas.3", 200, 100),
        _ev("fusion.2", 250, 100), _ev("fusion.4", 700, 100),
        _ev("copy.9", 1200, 50)]}]}
    return [host, dev]


def test_gaps_go_to_innermost_program_span_on_the_window_thread():
    red = ps.reduce_planes(_planes(program=True))
    # gaps [150,200] (mid 175) and [350,700] (mid 525) lie in
    # trainer.local_train, the latter inside trainer.estimate; [800,1100]
    # (mid 950) lies in aggregate.merge, past merge.prep's end (820)
    assert red["gaps"] == pytest.approx({
        "trainer.local_train": 50e-9, "trainer.estimate": 350e-9,
        "aggregate.merge": 300e-9})
    assert "trainer.host_stage" not in red["gaps"]


def test_program_spans_leave_busy_window_and_ops_unchanged():
    base = tr.reduce_planes(_planes(program=False))
    red = ps.reduce_planes(_planes(program=True))
    for key in ("busy_s", "window_s", "ops", "devices"):
        assert red[key] == base[key]
    # without program spans the gaps are trace_reduce's own
    assert ps.reduce_planes(_planes(program=False)) == base


def test_no_window_thread_reads_nothing():
    planes = _planes(program=True)
    planes[0]["lines"][0]["events"] = planes[0]["lines"][0]["events"][1:]
    assert ps.reduce_planes(planes) is None


def test_recorded_v5e_trace_reads_as_trace_reduce():
    with gzip.open(HERE / "trace_v5e_small.json.gz", "rt") as f:
        planes = json.load(f)
    assert ps.reduce_planes(planes) == tr.reduce_planes(planes)


def _ctx(spans, rounds=2):
    return {"kind": "train", "rounds": rounds,
            "obs": {"spans": [{"type": "span", "clock": "wall", "name": n,
                               "t0": t0, "t1": t1, "parent": None,
                               "attrs": attrs}
                              for n, t0, t1, attrs in spans]}}


def test_new_readers_by_hand():
    ctx = _ctx([("merge.prep", 0.0, 2.0, {}),
                ("merge.prep", 10.0, 13.0, {}),
                ("merge.compiled", 2.0, 3.0, {"h2d_bytes": 2_000_000}),
                ("merge.compiled", 13.0, 14.0, {"h2d_bytes": 2_000_000}),
                ("trainer.pull", 4.0, 4.5, {"d2h_bytes": 1_500_000}),
                ("trainer.pull", 5.0, 5.5, {"d2h_bytes": 500_000}),
                ("trainer.estimate", 6.0, 6.25, {}),
                ("trainer.local_train", 5.5, 7.0, {})])
    assert reader("merge.host_prep_ms").read(ctx) == pytest.approx(2500.0)
    assert reader("merge.h2d_mb").read(ctx) == pytest.approx(2.0)
    assert reader("trainer.d2h_mb").read(ctx) == pytest.approx(1.0)
    assert reader("trainer.estimate_ms").read(ctx) == pytest.approx(125.0)


def test_new_readers_read_nothing_without_their_spans():
    """A program that records none of the new spans (telemetry off, or
    a build without them) leaves the metrics out, without raising."""
    older = _ctx([("trainer.local_train", 0.0, 1.0, {}),
                  ("aggregate.merge", 1.0, 2.0, {})])
    for ctx in ({"kind": "train", "obs": None, "rounds": 1}, older):
        for name in NEW:
            assert reader(name).read(ctx) is None, name
