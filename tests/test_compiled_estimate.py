"""The client estimate step as one compiled program
(``repro.fl.client.client_step(...).estimate``) against the eager
estimator it replaced.

The eager path — ``estimator.client_estimates`` run op by op around
the step's ``grad`` program — lives only here, as the oracle: the
compiled estimates match it to float re-association, a Heroes history
under ``materialize`` is the same field for field, the expert model's
forward counts are the same, and a width compiles its estimate once.
"""

import dataclasses
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import estimator
from repro.fl import FLConfig, build_image_setup, run_scheme
from repro.fl import client as client_lib
from repro.fl.client import data_batch
from repro.fl.engine.trainers import _cache_size
from repro.fl.transformer import make_mla_moe_transformer, make_transformer
from repro.obs import MemorySink, Recorder

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
TINY_MLA_MOE = dict(json.loads((CHIP / "tests" / "tiny-mla-moe.json")
                               .read_text())["model"], max_width=3)


def _eager_estimate(grad_fn):
    """The estimate step as the sequential trainer ran it before it was
    compiled: four jitted gradients (``grad_fn``, the step's ``grad``
    program), the estimator op by op."""

    def estimate(params0, params, batches):
        stats = []

        def grad(p, b):
            g, s = grad_fn(p, b)
            stats.append(s)
            return g

        return estimator.client_estimates(grad, params0, params,
                                          batches), stats

    return estimate


def _use_eager_estimates(monkeypatch):
    """Every client step from here on estimates op by op."""
    compiled = client_lib.client_step

    def step(*key):
        s = compiled(*key)
        return s._replace(estimate=_eager_estimate(s.grad))

    monkeypatch.setattr(client_lib, "client_step", step)


def _reduced(model, width):
    params = model.init_factorized(jax.random.PRNGKey(0))
    sq = next(s for s in model.specs.values() if s.mode == "square")
    return model.reduce(params, width,
                        np.arange(sq.blocks_for_width(width)),
                        np.arange(width))


def _cnn_case():
    model, px, py, _ = build_image_setup(num_clients=4, seed=0)
    return model, px[0], py[0]


def _transformer_case():
    model = make_transformer()
    rng = np.random.default_rng(1)
    x = rng.integers(0, 64, (40, 32)).astype(np.int32)
    y = rng.integers(0, 64, (40, 32)).astype(np.int32)
    return model, x, y


def _mla_moe_case():
    model = make_mla_moe_transformer(**TINY_MLA_MOE)
    rng = np.random.default_rng(2)
    v = TINY_MLA_MOE["vocab"]
    x = rng.integers(0, v, (24, 16)).astype(np.int32)
    y = rng.integers(0, v, (24, 16)).astype(np.int32)
    return model, x, y


@pytest.mark.parametrize("case", [_cnn_case, _transformer_case],
                         ids=["cnn", "transformer"])
def test_compiled_estimates_match_the_eager_estimator(case):
    model, x, y = case()
    width = 2
    params0 = _reduced(model, width)
    rng = np.random.default_rng(0)
    batches = [data_batch(model, x, y, rng.integers(0, len(y), 8))
               for _ in range(3)]
    step = client_lib.client_step(model, width, True, "materialize")
    params, _ = step.sgd(params0, batches[0], 0.05)
    want, _ = _eager_estimate(step.grad)(params0, params, batches)
    got, stats = step.estimate(params0, params, batches)
    assert len(stats) == 4
    assert set(got) == {"L", "sigma_sq", "grad_sq"}
    for k in want:
        assert float(want[k]) > 0
        np.testing.assert_allclose(float(got[k]), float(want[k]),
                                   rtol=1e-5)


@pytest.mark.parametrize("scheme", ["heroes", "adp"])
def test_history_equals_the_eager_estimators(scheme, monkeypatch):
    """The estimates steer the adaptive policies: widths, taus, the
    virtual clock and the accuracies come out the same."""
    model, px, py, test = build_image_setup(num_clients=10, seed=0)
    cfg = FLConfig(num_clients=10, clients_per_round=4, eval_every=2,
                   tau_fixed=4, tau_max=15, estimate=True,
                   forward_impl="materialize")
    got = run_scheme(scheme, model, px, py, test, rounds=4, cfg=cfg)
    _use_eager_estimates(monkeypatch)
    want = run_scheme(scheme, model, px, py, test, rounds=4, cfg=cfg)
    assert [dataclasses.asdict(h) for h in got] == [
        dataclasses.asdict(h) for h in want]


@pytest.fixture(scope="module")
def mla_moe():
    return _mla_moe_case()


def _train(case, seed, obs):
    model, x, y = case
    return client_lib.local_train(
        model, _reduced(model, 2), 2, 2, x, y, 0.05,
        np.random.default_rng(seed), batch_size=4,
        forward_impl="materialize", obs=obs)


def test_expert_counts_equal_the_eager_paths(mla_moe, monkeypatch):
    got = _train(mla_moe, 3, Recorder([MemorySink()]))
    _use_eager_estimates(monkeypatch)
    want = _train(mla_moe, 3, Recorder([MemorySink()]))
    for key in ("moe.routed_pairs", "backward.moe.routed_pairs",
                "moe.pairs_total", "moe.expert_load_max",
                "backward.moe.expert_load_max"):
        assert key in got.stats, key
    assert got.stats == want.stats
    # the gradients are the same bits; the squared norms sum this
    # model's larger leaves in another order inside one program
    for k in want.estimates:
        np.testing.assert_allclose(got.estimates[k], want.estimates[k],
                                   rtol=1e-4)


def test_a_width_compiles_its_estimate_once(mla_moe):
    model, _, _ = mla_moe
    obs = Recorder([MemorySink()])
    _train(mla_moe, 4, obs)
    program = client_lib.client_step(model, 2, True, "materialize",
                                     None).estimate
    before = _cache_size(program)
    assert before is not None and before >= 1
    _train(mla_moe, 5, obs)
    assert _cache_size(program) == before
    assert obs.counters["trainer.estimate_compiled_clients"] == 2
