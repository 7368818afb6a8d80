"""Rank-space client compute: factorized application correctness.

Covers the tentpole contracts of the rank-space execution path:

* ``apply_factors`` reproduces compose-then-apply for every spec mode,
  dense and conv, at every width (forward values);
* gradient parity: local SGD under ``forward_impl="rank_space"`` /
  ``"auto"`` tracks the materialize path within float-reassociation
  tolerance for all three models at every width, same seeds;
* ``forward_impl="materialize"`` reproduces the recorded seed histories
  BITWISE (fixtures/golden_materialize_histories.json, captured from
  the pre-rank-space code);
* the out-of-range block-id gather now raises instead of silently
  clamping (regression for the anchored-layer id bug).
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.composition import (CompositionPlan, CompositionSpec,
                                    apply_factors, apply_flops, compose,
                                    compose_flops, dense_apply_flops,
                                    gather_blocks, init_factors,
                                    rank_space_wins)
from repro.fl import FLConfig, build_image_setup, run_scheme
from repro.fl.client import client_step
from repro.fl.models import make_cnn, make_resnet, make_rnn

FIXTURES = Path(__file__).parent / "fixtures"


# ---------------------------------------------------------------------------
# apply_factors vs compose-then-apply
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["square", "grow_out", "grow_in"])
@pytest.mark.parametrize("p", [1, 2, 3])
def test_apply_factors_dense_matches_compose(mode, p):
    spec = CompositionSpec(3, 8, 6, 5, ksq=1, mode=mode)
    v, u = init_factors(jax.random.PRNGKey(0), spec)
    red = gather_blocks(u, np.arange(spec.blocks_for_width(p)))
    w = compose(v, red, p, spec)
    x = jax.random.normal(jax.random.PRNGKey(p), (4, 7, w.shape[1]))
    got = apply_factors(x, v, red, p, spec, "dense")
    np.testing.assert_allclose(np.asarray(x @ w[0]), np.asarray(got),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("mode", ["square", "grow_out", "grow_in"])
@pytest.mark.parametrize("p", [1, 2, 3])
@pytest.mark.parametrize("stride", [1, 2])
def test_apply_factors_conv_matches_compose(mode, p, stride):
    spec = CompositionSpec(3, 8, 6, 5, ksq=9, mode=mode)
    v, u = init_factors(jax.random.PRNGKey(1), spec)
    red = gather_blocks(u, np.arange(spec.blocks_for_width(p)))
    w = compose(v, red, p, spec)
    x = jax.random.normal(jax.random.PRNGKey(p + 10), (2, 8, 8, w.shape[1]))
    wk = w.reshape(3, 3, w.shape[1], w.shape[2])
    want = jax.lax.conv_general_dilated(
        x, wk, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    got = apply_factors(x, v, red, p, spec, "conv", stride=stride)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               atol=1e-4, rtol=1e-4)


def test_flops_model_orders_paths_sensibly():
    """The static FLOPs model: rank space wins where pI >> R and the
    compose is amortised, loses at width 1 / for gather-style layers."""
    spec = CompositionSpec(3, 8, 8, 8, ksq=9)  # the CNN hidden conv
    apps = 16 * 16  # batch 16, 4x4 output positions
    assert rank_space_wins(3, spec, applications=apps)
    assert rank_space_wins(2, spec, applications=apps)
    assert not rank_space_wins(1, spec, applications=apps)
    # embedding: materialised application is a free gather, and the
    # rank path's basis projection is a gather too (_apply_embed), so
    # the contest is the per-token R->pO contraction vs the one-off
    # vocab-sized compose: rank wins exactly below vocab tokens
    emb = CompositionSpec(3, 8, 64, 16, ksq=1, mode="grow_out")
    assert not rank_space_wins(3, emb, applications=apps,
                               dense_apply_free=True)
    assert rank_space_wins(3, emb, applications=16, dense_apply_free=True,
                           basis_is_gather=True)
    assert not rank_space_wins(3, emb, applications=apps,
                               dense_apply_free=True, basis_is_gather=True)
    assert apply_flops(3, emb, applications=1, basis_is_gather=True) == \
        2 * 3 * emb.rank * emb.base_out  # coefficient contraction only
    # the numbers the benchmark records stay positive and consistent
    for p in (1, 2, 3):
        assert apply_flops(p, spec, applications=2) == \
            2 * apply_flops(p, spec)
        assert dense_apply_flops(p, spec) > 0 and compose_flops(p, spec) > 0


# ---------------------------------------------------------------------------
# gradient parity: materialize vs rank_space local updates
# ---------------------------------------------------------------------------


def _reduced(model, width, key=jax.random.PRNGKey(0)):
    params = model.init_factorized(key)
    sq = next(s for s in model.specs.values() if s.mode == "square")
    return model.reduce(params, width,
                        np.arange(sq.blocks_for_width(width)),
                        np.arange(width))


def _batch(model, key, n=8):
    if model.name == "rnn":
        return {"tokens": jax.random.randint(key, (n, 32), 0, 64),
                "labels": jax.random.randint(key, (n, 32), 0, 64)}
    return {"x": jax.random.normal(key, (n, 8, 8, 3)),
            "labels": jax.random.randint(key, (n,), 0, 10)}


@pytest.mark.parametrize("make", [make_cnn, make_resnet, make_rnn])
@pytest.mark.parametrize("width", [1, 2, 3])
@pytest.mark.parametrize("impl", ["rank_space", "auto"])
def test_gradient_parity_rank_space_vs_materialize(make, width, impl):
    model = make()
    red = _reduced(model, width)
    batch = _batch(model, jax.random.PRNGKey(3))
    mat = client_step(model, width, True, "materialize")
    rank = client_step(model, width, True, impl)
    grad_mat, step_mat = mat.grad, mat.sgd
    grad_rank, step_rank = rank.grad, rank.sgd
    # the programs return the forward's counts beside their
    # results (none for these models)
    g_mat, _ = grad_mat(red, batch)
    g_rank, _ = grad_rank(red, batch)
    for a, b in zip(jax.tree_util.tree_leaves(g_mat),
                    jax.tree_util.tree_leaves(g_rank)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)
    # a few SGD steps stay on the same trajectory
    pa, pb = red, red
    for i in range(3):
        b = _batch(model, jax.random.PRNGKey(10 + i))
        pa, _ = step_mat(pa, b, 0.05)
        pb, _ = step_rank(pb, b, 0.05)
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3)


# ---------------------------------------------------------------------------
# bitwise: materialize reproduces the recorded seed histories
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", ["heroes", "flanc"])
def test_materialize_reproduces_seed_histories_bitwise(scheme):
    golden = json.loads(
        (FIXTURES / "golden_materialize_histories.json").read_text())[scheme]
    model, px, py, test = build_image_setup(num_clients=10, seed=0)
    cfg = FLConfig(num_clients=10, clients_per_round=4, eval_every=2,
                   tau_fixed=4, tau_max=15, estimate=True,
                   forward_impl="materialize")
    hist = run_scheme(scheme, model, px, py, test, rounds=4, cfg=cfg)
    assert len(hist) == len(golden)
    for h, g in zip(hist, golden):
        assert h.round == g["round"]
        assert h.wall_time == g["wall_time"]
        assert h.traffic_bytes == g["traffic_bytes"]
        assert h.makespan == g["makespan"]
        assert h.avg_wait == g["avg_wait"]
        assert h.mean_tau == g["mean_tau"]
        assert (h.accuracy is None) == (g["accuracy"] is None)
        if h.accuracy is not None:
            assert h.accuracy == g["accuracy"]


# ---------------------------------------------------------------------------
# fused path parity + measured-calibration dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["square", "grow_out", "grow_in"])
@pytest.mark.parametrize("p", [1, 2, 3])
@pytest.mark.parametrize("stride", [1, 2])
def test_apply_factors_conv_fused_matches_unfused(mode, p, stride):
    """The fused conv rank primitive (production default) vs the kept
    separate-ops reference path inside apply_factors itself."""
    spec = CompositionSpec(3, 8, 6, 5, ksq=9, mode=mode)
    v, u = init_factors(jax.random.PRNGKey(2), spec)
    red = gather_blocks(u, np.arange(spec.blocks_for_width(p)))
    g = 1 if mode == "grow_out" else p
    x = jax.random.normal(jax.random.PRNGKey(p + 20), (2, 8, 8, g * 6))
    fused = apply_factors(x, v, red, p, spec, "conv", stride=stride)
    unfused = apply_factors(x, v, red, p, spec, "conv", stride=stride,
                            fused=False)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               atol=1e-4, rtol=1e-4)


def _cal(ovh, gain):
    from repro.core.calibration import RankPathCalibration
    return RankPathCalibration(conv_rank_overhead=ovh,
                               fused_compose_gain=gain)


def test_layer_impls_calibration_drives_choices():
    """Pinned calibrations make the auto choice deterministic: a cheap
    measured conv rank path enables rank_space on the hidden convs, an
    expensive one disables it; fused_compose_gain < 1 swaps the dense
    head (a materialize-regime layer) to the fused compose+apply."""
    cnn = make_cnn()
    cheap = cnn.layer_impls(3, 16, "auto", calibration=_cal(0.5, 2.0))
    assert cheap["conv2"] == "rank_space"
    assert cheap["conv3"] == "rank_space"
    assert cheap["fc"] == "materialize"  # gain >= 1: no fusion
    dear = cnn.layer_impls(3, 16, "auto", calibration=_cal(30.0, 0.5))
    assert dear["conv1"] == "materialize"
    assert dear["conv2"] == "materialize"
    assert dear["conv3"] == "materialize"
    assert dear["fc"] == "fused_compose"  # ksq == 1, gain < 1
    # the embedding's free-gather apply never fuses, whatever the gain
    rnn = make_rnn()
    auto = rnn.layer_impls(3, 16, "auto", calibration=_cal(1.0, 0.5))
    assert auto["embed"] == "materialize"
    assert auto["wh"] == "materialize"  # rank_capable=False pin holds


def test_calibration_config_pins_and_dispatch_gate():
    """FLConfig overrides pin the calibration without measuring, and
    non-auto configs never trigger the micro-benchmarks at all."""
    from repro.core.calibration import for_dispatch, from_config

    pinned = FLConfig(forward_impl="auto", conv_rank_overhead=1.5,
                      fused_compose_gain=0.8)
    cal = for_dispatch(pinned)
    assert cal is not None and not cal.measured
    assert cal.conv_rank_overhead == 1.5
    assert cal.fused_compose_gain == 0.8
    assert from_config(pinned) == cal
    # materialize / rank_space dispatch short-circuits to None (no
    # measurement, no calibration in the jit-cache key)
    assert for_dispatch(FLConfig(forward_impl="materialize")) is None
    assert for_dispatch(FLConfig(forward_impl="rank_space")) is None


def test_fused_compose_impl_gradient_parity():
    """End-to-end: an auto client whose pinned calibration routes the
    dense head through compose_dense_apply ("fused_compose") computes
    the same gradients as the materialize client."""
    model = make_cnn()
    cal = _cal(30.0, 0.5)
    # width 3 / batch 16: the head sits in the materialize regime (at
    # width 2 / batch 8 its rank path wins FLOPs outright)
    impls = model.layer_impls(3, 16, "auto", calibration=cal)
    assert impls["fc"] == "fused_compose"
    red = _reduced(model, 3)
    batch = _batch(model, jax.random.PRNGKey(5), n=16)
    grad_mat = client_step(model, 3, True, "materialize").grad
    grad_fus = client_step(model, 3, True, "auto", cal).grad
    for a, b in zip(jax.tree_util.tree_leaves(grad_mat(red, batch)[0]),
                    jax.tree_util.tree_leaves(grad_fus(red, batch)[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


def test_unknown_forward_impl_rejected():
    model = make_cnn()
    with pytest.raises(ValueError, match="forward_impl"):
        model.layer_impls(2, 16, "fused")


def test_layer_impls_pin_scan_recurrence_and_embedding():
    """The scan-carried wh never goes rank-space (composed once, reused
    T times); the embedding's materialised apply is a free gather so
    auto keeps it composed; the input projection wins in rank space."""
    rnn = make_rnn()
    forced = rnn.layer_impls(3, 16, "rank_space")
    assert forced["wh"] == "materialize"
    auto = rnn.layer_impls(3, 16, "auto")
    assert auto["wh"] == "materialize"
    assert auto["embed"] == "materialize"
    assert auto["wx"] == "rank_space"
    cnn = make_cnn()
    assert all(v == "materialize"
               for v in cnn.layer_impls(3, 16, "materialize").values())


# ---------------------------------------------------------------------------
# out-of-range block-id gathers raise (regression: silent jnp.take clamp)
# ---------------------------------------------------------------------------


def test_gather_blocks_rejects_out_of_range_ids():
    spec = CompositionSpec(3, 4, 4, 4, ksq=1, mode="grow_out")  # 3 blocks
    _, u = init_factors(jax.random.PRNGKey(0), spec)
    with pytest.raises(ValueError, match="out of range"):
        gather_blocks(u, np.array([0, 5]))  # 5 >= 3 used to clamp to 2
    with pytest.raises(ValueError, match="out of range"):
        gather_blocks(u, np.array([-1]))
    got = gather_blocks(u, np.array([2, 0]))  # in-range still works
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(u[2]))


def test_composition_plan_reduce_validates_per_layer():
    """Anchored-mode layers hold P blocks; handing them the shared
    P^2-counter ids must raise, not silently gather clamped blocks."""
    plan = CompositionPlan(
        {"hidden": CompositionSpec(3, 4, 4, 4, mode="square"),
         "head": CompositionSpec(3, 4, 4, 4, mode="grow_in")},
        max_width=3)
    params = plan.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="head"):
        plan.reduce(params, np.array([0, 4, 8]))  # valid for P^2=9, not P=3
    out = plan.reduce(params, np.array([0, 1, 2]))  # valid everywhere
    assert out["head"]["coeff"].shape[0] == 3
