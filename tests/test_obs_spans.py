"""The engine's wall spans on the profiler clock, their nesting, and the
host<->device byte counters (``repro.obs.spans``).

A one-round Heroes run with ``telemetry="memory"`` under
``jax.profiler.trace``: every span of the round shows up in the xplane
host plane with the nesting the recorder's ``parent`` fields give; the
merge's ``merge.h2d_bytes`` equals the bytes of the client blocks, ids
and bases it uploads, its ``merge.prep`` counts every client as
scattered on the device (``device_scatter``), the trainer's
``trainer.d2h_bytes`` equals the bytes of the params it returned and
every client's estimate runs as the compiled program (``compiled`` on
``trainer.estimate``, counter ``trainer.estimate_compiled_clients``); with
telemetry off no span is opened at all.
"""

import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.fl import FLConfig, build_image_setup, build_runner
from repro.fl import client as client_lib
from repro.fl.engine import ProximalTrainer, SequentialTrainer
from repro.obs import NOOP, SPANS, validate_events
from repro.obs import recorder as recorder_lib
from repro.obs.report import render_report

SRC = Path(__file__).resolve().parents[1] / "src"
# the spans one synchronous Heroes round opens (sequential trainer,
# collective merge, round 1 evaluates)
ROUND_SPANS = {"round.assign", "round.evaluate", "trainer.client_params",
               "trainer.local_train", "trainer.sgd", "trainer.loss",
               "trainer.estimate", "trainer.pull", "aggregate.merge",
               "merge.prep", "merge.compiled"}
NESTED = {"merge.prep": "aggregate.merge", "merge.compiled": "aggregate.merge",
          "trainer.sgd": "trainer.local_train",
          "trainer.loss": "trainer.local_train",
          "trainer.estimate": "trainer.local_train"}


@pytest.fixture(scope="module")
def image_setup():
    return build_image_setup(num_clients=10, seed=0)


def _cfg(**kw):
    base = dict(num_clients=10, clients_per_round=2, eval_every=1000,
                tau_fixed=1, tau_max=2, estimate=True,
                forward_impl="materialize")
    base.update(kw)
    return FLConfig(**base)


def _wall(events):
    return [e for e in events if e.get("type") == "span"
            and e["clock"] == "wall"]


@pytest.fixture(scope="module")
def traced_round(image_setup, tmp_path_factory):
    """One Heroes round under the profiler: (recorder events, the host
    plane's program-span events per thread line)."""
    from jax.profiler import ProfileData

    model, px, py, test = image_setup
    eng = build_runner("heroes", model, px, py, test,
                       cfg=_cfg(telemetry="memory"))
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        eng.run_round()
        jax.block_until_ready(eng.state.params)
    eng.close()
    (path,) = out.glob("plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name in SPANS]
            if evs:
                lines.append(evs)
    return eng.obs.sinks[0].events, lines


def test_profiler_trace_holds_every_span_nested(traced_round):
    _, lines = traced_round
    names = {n for evs in lines for n, _, _ in evs}
    assert ROUND_SPANS <= names
    for evs in lines:
        for child, parent in NESTED.items():
            for _, a, b in (e for e in evs if e[0] == child):
                assert any(n == parent and pa <= a and b <= pb
                           for n, pa, pb in evs), (child, parent)


def test_recorded_spans_carry_parent(traced_round):
    events, _ = traced_round
    wall = _wall(events)
    assert ROUND_SPANS <= {e["name"] for e in wall}
    for e in wall:
        assert e["parent"] == NESTED.get(e["name"]), e
    validate_events(events)
    bad = dict(wall[0], parent=5)
    with pytest.raises(ValueError, match="parent"):
        validate_events([events[0], bad])


def test_every_wall_span_of_the_engine_is_in_the_table():
    """``SPANS`` names every span the program opens, so readers and
    docs work from one list."""
    opened = set()
    for path in SRC.rglob("*.py"):
        opened |= set(re.findall(r'wall_span\(\s*"([^"]+)"',
                                 path.read_text()))
    assert opened == set(SPANS)


def test_report_lists_new_spans_with_self_time(traced_round):
    events, _ = traced_round
    text = render_report(events).split("host wall time")[1]
    for name in ("merge.prep", "trainer.estimate", "round.evaluate"):
        assert re.search(rf"{re.escape(name)}: n=.*self=", text), name


def test_byte_counters_match_shapes(image_setup):
    model, px, py, test = image_setup
    eng = build_runner("heroes", model, px, py, test,
                       cfg=_cfg(telemetry="memory", clients_per_round=3))
    state, assigns = eng.assignment.assign(eng.state, [0, 4, 7])
    results = eng.trainer.train_all(state, assigns)
    pulled = sum(int(v.nbytes) for r in results.values()
                 for v in jax.tree_util.tree_leaves(r.params))
    eng.aggregator.aggregate(state, results, assigns)
    sink = eng.obs.sinks[0]
    counters = eng.obs.counters
    assert counters["trainer.d2h_bytes"] == pulled
    assert sum(e["attrs"]["d2h_bytes"]
               for e in sink.spans("trainer.pull")) == pulled
    assert counters["merge.h2d_bytes"] == _merge_upload_bytes(
        eng, results, assigns)
    (compiled,) = sink.spans("merge.compiled")
    assert compiled["attrs"]["h2d_bytes"] == counters["merge.h2d_bytes"]
    eng.close()


def _merge_upload_bytes(eng, results, assigns):
    """What the Heroes merge sends to the device: each client's trained
    coefficient blocks and its int32 block ids, per tensor, plus the
    stacked bases; the dense contributions are made on the device."""
    total = 0
    for n, r in results.items():
        for name, spec in eng.model.specs.items():
            key = "hidden_ids" if spec.mode == "square" else "anchored_ids"
            total += r.params[name]["coeff"].nbytes
            total += 4 * len(assigns[n][key])
            total += r.params[name]["basis"].nbytes
    return total


def test_merge_prep_counts_clients_scattered_on_the_device(image_setup):
    model, px, py, test = image_setup
    eng = build_runner("heroes", model, px, py, test,
                       cfg=_cfg(telemetry="memory", clients_per_round=3))
    for clients in ([0, 4, 7], [1, 2]):
        state, assigns = eng.assignment.assign(eng.state, clients)
        results = eng.trainer.train_all(state, assigns)
        eng.aggregator.aggregate(state, results, assigns)
    preps = eng.obs.sinks[0].spans("merge.prep")
    assert [e["attrs"]["clients"] for e in preps] == [3, 2]
    for e in preps:
        assert e["attrs"]["device_scatter"] == e["attrs"]["clients"]
    assert eng.obs.counters["merge.device_scatter_clients"] == 5
    eng.close()


def test_every_client_estimate_runs_compiled(image_setup):
    model, px, py, test = image_setup
    eng = build_runner("heroes", model, px, py, test,
                       cfg=_cfg(telemetry="memory", clients_per_round=3))
    for clients in ([0, 4, 7], [1, 2]):
        state, assigns = eng.assignment.assign(eng.state, clients)
        eng.trainer.train_all(state, assigns)
    spans = eng.obs.sinks[0].spans("trainer.estimate")
    assert [e["attrs"]["compiled"] for e in spans] == [1] * 5
    assert eng.obs.counters["trainer.estimate_compiled_clients"] == 5
    eng.close()


def test_telemetry_off_opens_no_span(image_setup, monkeypatch):
    """Off, nothing reads the recorder's clock or enters an annotation:
    ``local_train`` without ``obs`` and a whole engine round."""
    def refuse(*a, **kw):
        raise AssertionError("a wall span was opened with telemetry off")

    monkeypatch.setattr(recorder_lib._WallSpan, "__init__", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    model, px, py, test = image_setup
    eng = build_runner("heroes", model, px, py, test, cfg=_cfg())
    assert eng.obs is NOOP
    state, assigns = eng.assignment.assign(eng.state, [1])
    a = assigns[1]
    params = eng.aggregator.client_params(state, 1, a)
    res = client_lib.local_train(model, params, a["width"], 1, px[1], py[1],
                                 0.05, np.random.default_rng(0),
                                 forward_impl="materialize")
    assert np.isfinite(res.loss_after)
    eng.run_round()
    assert NOOP.snapshot() == {"counters": {}, "gauges": {},
                               "histograms": {}, "tallies": {}}


def test_sequential_and_proximal_emit_the_same_span_tree(image_setup):
    model, px, py, test = image_setup
    trees = []
    for trainer in (SequentialTrainer(), ProximalTrainer(mu=0.0)):
        eng = build_runner("heroes", model, px, py, test,
                           cfg=_cfg(telemetry="memory"))
        trainer.setup(eng)
        state, assigns = eng.assignment.assign(eng.state, [2, 5])
        n0 = len(eng.obs.sinks[0].events)
        trainer.train_all(state, assigns)
        trees.append([(e["name"], e["parent"], e["attrs"].get("compiled"))
                      for e in _wall(eng.obs.sinks[0].events[n0:])])
        eng.close()
    assert trees[0] == trees[1]
    assert ("trainer.estimate", "trainer.local_train", 1) in trees[0]
