"""The trainer -> merge hand-off on the device.

The sequential and proximal trainers return each client's trained
params as the device pytree its last step produced, and the collective
merge scatters the coefficient blocks and stacks the bases on the
device with no host copy.  On one device the server state after every
round must be bitwise equal to the host aggregators' and to the same
run with every result degraded to numpy through
``ClientResult.host_params()``; a synchronous round counts every client
in ``merge.device_input_clients`` and pulls nothing (no
``trainer.pull``); semi-async stragglers hold numpy and checkpoint as
before.
"""

import dataclasses
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.fl import FLConfig, build_image_setup, build_runner, build_setup
from repro.fl.engine import payload_to_state, state_to_payload
from repro.fl.transformer import make_mla_moe_transformer

SINGLE_DEVICE = len(jax.devices()) == 1
CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if SINGLE_DEVICE:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-5, rtol=1e-5)


def _is_device_tree(tree) -> bool:
    return all(isinstance(v, jax.Array)
               for v in jax.tree_util.tree_leaves(tree))


def _setup(model_name: str):
    """(model, parts_x, parts_y, test, FLConfig kwargs) of a tiny run."""
    if model_name == "cnn":
        model, px, py, test = build_image_setup(num_clients=8, seed=0)
        return model, px, py, test, dict(num_clients=8)
    if model_name == "transformer":
        model, px, py, test = build_setup("synthetic_text", "transformer",
                                          num_clients=6, max_width=3,
                                          seed=0)
        return model, px, py, test, dict(num_clients=6, batch_size=4)
    tiny = dict(json.loads((CHIP / "tests" / "tiny-mla-moe.json")
                           .read_text())["model"], max_width=3)
    model = make_mla_moe_transformer(**tiny)
    rng = np.random.default_rng(3)
    x = rng.integers(0, tiny["vocab"], (48, 16)).astype(np.int32)
    y = rng.integers(0, tiny["vocab"], (48, 16)).astype(np.int32)
    parts = np.array_split(np.arange(48), 6)
    test = {"tokens": x[:4], "labels": y[:4]}
    return (model, [x[p] for p in parts], [y[p] for p in parts], test,
            dict(num_clients=6, batch_size=4, lr=0.05))


def _cfg(base, **kw):
    cfg = dict(clients_per_round=3, eval_every=1000, tau_fixed=2,
               tau_max=2, estimate=True, trainer="sequential")
    cfg.update(base)
    cfg.update(kw)
    return FLConfig(**cfg)


def _degrade_results(eng):
    """Route the engine's trained results through ``host_params()``, so
    its merge sees the numpy trees a host consumer would."""
    train_all = eng.trainer.train_all

    def degraded(state, assigns):
        results = train_all(state, assigns)
        assert all(_is_device_tree(r.params) for r in results.values())
        return {n: dataclasses.replace(r, params=r.host_params(eng.obs))
                for n, r in results.items()}

    eng.trainer.train_all = degraded


@pytest.mark.parametrize("scheme,model_name,forward_impl", [
    ("heroes", "cnn", "materialize"), ("heroes", "cnn", "auto"),
    ("heroes", "transformer", "materialize"),
    ("heroes", "transformer", "auto"),
    ("heroes", "mla_moe", "materialize"),
    # the proximal trainer's device results through the dense mean
    ("fedprox", "cnn", "materialize"),
    # the dense, masked and Flanc preps take device results through
    # host_params()
    ("fedavg", "cnn", "materialize"), ("heterofl", "cnn", "materialize"),
    ("flanc", "cnn", "materialize"),
])
def test_rounds_bitwise_across_the_handoff(scheme, model_name,
                                           forward_impl):
    model, px, py, test, base = _setup(model_name)
    runs = {}
    for key, agg in (("device", "collective"), ("host", "host"),
                     ("degraded", "collective")):
        runs[key] = build_runner(
            scheme, model, px, py, test,
            cfg=_cfg(base, agg_backend=agg, forward_impl=forward_impl),
            seed=0)
    _degrade_results(runs["degraded"])
    if model_name == "mla_moe":
        # an expert bank's 4-d coefficient goes through _scatter_client
        assert any(t["coeff"].ndim == 4
                   for t in runs["device"].params.values())
    for _ in range(3):
        logs = {k: eng.run_round() for k, eng in runs.items()}
        assert len({log.wall_time for log in logs.values()}) == 1
        _leaves_equal(runs["device"].params, runs["host"].params)
        _leaves_equal(runs["device"].params, runs["degraded"].params)
    for eng in runs.values():
        eng.close()


def test_sync_round_reads_every_client_on_the_device():
    model, px, py, test, base = _setup("cnn")
    eng = build_runner("heroes", model, px, py, test,
                       cfg=_cfg(base, telemetry="memory"), seed=0)
    for _ in range(3):
        eng.run_round()
    sink = eng.obs.sinks[0]
    preps = sink.spans("merge.prep")
    assert len(preps) == 3
    for e in preps:
        assert e["attrs"]["device_input"] == e["attrs"]["clients"] == 3
    assert eng.obs.counters["merge.device_input_clients"] == 9
    assert sink.spans("trainer.pull") == []
    assert "trainer.d2h_bytes" not in eng.obs.counters
    eng.close()


def test_numpy_results_are_not_counted_as_device_input():
    """Degraded (numpy) results are uploaded by the prep, and counted
    as scattered on the device but not as read there."""
    model, px, py, test, base = _setup("cnn")
    eng = build_runner("heroes", model, px, py, test,
                       cfg=_cfg(base, telemetry="memory"), seed=0)
    _degrade_results(eng)
    eng.run_round()
    (prep,) = eng.obs.sinks[0].spans("merge.prep")
    assert prep["attrs"]["device_input"] == 0
    assert prep["attrs"]["device_scatter"] == 3
    assert eng.obs.counters["merge.device_input_clients"] == 0
    # host_params() pulled under trainer.pull, one span a client
    assert len(eng.obs.sinks[0].spans("trainer.pull")) == 3
    eng.close()


SEMI_ASYNC = dict(round_mode="semi_async", async_k=2, clients_per_round=4)


@pytest.mark.parametrize("scheme,kw", [
    ("fedavg", {}), ("heterofl", {}), ("flanc", {}),
    ("heroes", SEMI_ASYNC),
])
def test_host_merge_inputs_are_pulled_under_trainer_pull(scheme, kw):
    """The preps that read numpy (dense, masked, Flanc, and Heroes where
    staleness weights are blended) copy each device result to the host
    through ``host_params()``: one ``trainer.pull`` a client inside
    ``merge.prep``, its bytes in ``trainer.d2h_bytes``."""
    model, px, py, test, base = _setup("cnn")
    eng = build_runner(scheme, model, px, py, test,
                       cfg=_cfg(base, telemetry="memory", **kw), seed=0)
    for _ in range(3):
        eng.run_round()
    sink = eng.obs.sinks[0]
    pulls = sink.spans("trainer.pull")
    in_prep = [e for e in pulls if e["parent"] == "merge.prep"]
    merged = sum(e["attrs"]["clients"] for e in sink.spans("merge.prep"))
    if kw:
        # landed stragglers were degraded to numpy after their dispatch
        # event; only the fresh results are pulled in the prep
        assert 0 < len(in_prep) < merged
        assert len(pulls) > len(in_prep)
    else:
        assert len(in_prep) == len(pulls) == merged == 9
    nbytes = sum(e["attrs"]["d2h_bytes"] for e in pulls)
    assert nbytes > 0
    assert eng.obs.counters["trainer.d2h_bytes"] == nbytes
    eng.close()


def test_semi_async_stragglers_hold_numpy_and_resume_bitwise():
    model, px, py, test, base = _setup("cnn")
    kw = SEMI_ASYNC
    host = build_runner("heroes", model, px, py, test,
                        cfg=_cfg(base, agg_backend="host", **kw), seed=0)
    coll = build_runner("heroes", model, px, py, test,
                        cfg=_cfg(base, agg_backend="collective", **kw),
                        seed=0)
    stale = 0
    for _ in range(4):
        a, b = host.run_round(), coll.run_round()
        assert a.wall_time == b.wall_time
        stale += b.stale
        _leaves_equal(host.params, coll.params)
        assert coll.state.in_flight
        for t in coll.state.in_flight:
            assert all(isinstance(v, np.ndarray) for v in
                       jax.tree_util.tree_leaves(t.result.params))
    assert stale > 0, "no staleness events: the weighted path was not hit"

    resumed = build_runner("heroes", model, px, py, test,
                           cfg=_cfg(base, agg_backend="collective", **kw),
                           seed=0)
    resumed.state = payload_to_state(state_to_payload(coll.state),
                                     resumed.state.params)
    for _ in range(3):
        logs = [eng.run_round() for eng in (host, coll, resumed)]
        assert logs[1] == logs[2]
        assert logs[0].wall_time == logs[1].wall_time
        _leaves_equal(coll.params, resumed.params)
        _leaves_equal(host.params, coll.params)
    for eng in (host, coll, resumed):
        eng.close()

