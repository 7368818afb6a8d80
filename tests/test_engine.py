"""Tests for the layered FL engine (repro.fl.engine).

Covers: bitwise parity against the golden legacy-history fixtures for
every scheme, the deprecated legacy entry-point shims, the
batched-cohort vs sequential trainer equivalence, the semi-async round
loop, registry extensibility, and the model-identity jit-cache fix.
"""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from repro.fl import FLConfig, build_image_setup, build_runner, run_scheme
from repro.fl.engine import (CohortTrainer, SchemeBundle, SequentialTrainer,
                             register_scheme)
from repro.fl.engine.registry import SCHEMES
from repro.fl.models import make_cnn

GOLDEN = json.loads(
    (Path(__file__).parent / "fixtures"
     / "golden_legacy_histories.json").read_text())


def _golden_view(hist, fixture):
    """RoundLog dicts restricted to the fields the fixture predates.

    The fixture was captured before RoundLog grew the up/down traffic
    split; every field it *does* record must still match bitwise.
    """
    keys = set(fixture[0])
    return [{k: v for k, v in dataclasses.asdict(h).items() if k in keys}
            for h in hist]


@pytest.fixture(scope="module")
def image_setup():
    return build_image_setup(num_clients=10, seed=0)


def _cfg(**kw):
    # forward_impl pinned: the golden fixtures were captured under the
    # legacy compose-then-apply path ("materialize" reproduces it
    # bitwise); "auto" now consults a measured per-host calibration, so
    # its impl mix is allowed to differ between hosts.
    base = dict(num_clients=10, clients_per_round=4, eval_every=2,
                tau_fixed=4, tau_max=15, estimate=True,
                forward_impl="materialize")
    base.update(kw)
    return FLConfig(**base)


def _assert_history_parity(ha, hb, acc_atol=1e-4):
    assert len(ha) == len(hb)
    for a, b in zip(ha, hb):
        # traffic / virtual clock must match exactly
        assert a.round == b.round
        assert a.wall_time == b.wall_time
        assert a.traffic_bytes == b.traffic_bytes
        assert a.makespan == b.makespan
        assert a.avg_wait == b.avg_wait
        assert a.mean_tau == b.mean_tau
        assert (a.accuracy is None) == (b.accuracy is None)
        if a.accuracy is not None:
            assert abs(a.accuracy - b.accuracy) <= acc_atol


# ---------------------------------------------------------------------------
# bitwise parity: engine histories vs the golden legacy fixtures
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", sorted(k for k in GOLDEN if k != "_meta"))
def test_engine_matches_golden_fixture(scheme, image_setup):
    """The engine must reproduce the retired legacy runners' histories
    bitwise (the fixture was captured from the legacy tree before it was
    deleted; JSON round-trips floats exactly)."""
    model, px, py, test = image_setup
    rounds = len(GOLDEN[scheme])
    hist = run_scheme(scheme, model, px, py, test, rounds=rounds, cfg=_cfg())
    assert _golden_view(hist, GOLDEN[scheme]) == GOLDEN[scheme]
    # the new split must reproduce the combined fixture traffic bitwise
    # (traffic_bytes is cumulative; the split is this round's delta)
    prev = 0.0
    for h in hist:
        assert h.up_bytes + h.down_bytes == h.traffic_bytes - prev
        prev = h.traffic_bytes


def test_legacy_shims_resolve_and_warn(image_setup):
    """repro.fl.server.RUNNERS survives as DeprecationWarning shims that
    build the equivalent engine bundle."""
    from repro.fl import RUNNERS as reexported
    from repro.fl.server import RUNNERS

    assert reexported is RUNNERS
    assert set(RUNNERS) == {"fedavg", "adp", "heterofl", "flanc", "heroes"}
    model, px, py, test = image_setup
    cfg = _cfg()
    from repro.fl.heterogeneity import HeterogeneityModel
    het = HeterogeneityModel(cfg.num_clients, seed=0,
                             tier_weights=(0.05, 0.15, 0.30, 0.50))
    with pytest.warns(DeprecationWarning, match="deprecated"):
        runner = RUNNERS["heroes"](model, px, py, test, het, cfg, 3)
    hist = runner.run(2)
    assert len(hist) == 2
    assert _golden_view(hist, GOLDEN["heroes"]) == GOLDEN["heroes"][:2]
    # the Heroes scheduler tallies live in the threaded ServerState
    assert runner.state.sched.counters.sum() > 0
    assert runner.state.sched.anchored.sum() > 0


# ---------------------------------------------------------------------------
# cohort trainer vs sequential trainer
# ---------------------------------------------------------------------------


def test_cohort_trainer_matches_sequential_results(image_setup):
    """Same assignments, same data order: the vmapped cohort step must
    reproduce the per-client sequential updates (up to float assoc)."""
    model, px, py, test = image_setup
    cfg = _cfg()
    eng = build_runner("heroes", model, px, py, test, cfg=cfg)
    _, assigns = eng.assignment.assign(eng.state, list(range(4)))

    seq, coh = SequentialTrainer(), CohortTrainer()
    seq.setup(eng)
    coh.setup(eng)
    r_seq = seq.train_all(eng.state, assigns)
    r_coh = coh.train_all(eng.state, assigns)

    assert list(r_seq) == list(r_coh)
    for n in r_seq:
        a, b = r_seq[n], r_coh[n]
        import jax
        # host_params(): on a multi-device host the cohort backend hands
        # the collective merger device-resident slices
        for la, lb in zip(jax.tree_util.tree_leaves(a.host_params()),
                          jax.tree_util.tree_leaves(b.host_params())):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       atol=1e-5, rtol=1e-4)
        assert abs(a.loss_before - b.loss_before) < 1e-4
        assert abs(a.loss_after - b.loss_after) < 1e-4
        for k in a.estimates:
            np.testing.assert_allclose(a.estimates[k], b.estimates[k],
                                       atol=1e-3, rtol=1e-2)


def test_cohort_backend_end_to_end(image_setup):
    """Full runs: cohort and sequential backends agree on the virtual
    clock/traffic exactly and on accuracy within tolerance."""
    model, px, py, test = image_setup
    h_seq = run_scheme("fedavg", model, px, py, test, rounds=3, cfg=_cfg())
    h_coh = run_scheme("fedavg", model, px, py, test, rounds=3,
                       cfg=_cfg(trainer="cohort"))
    _assert_history_parity(h_seq, h_coh, acc_atol=1e-3)


# ---------------------------------------------------------------------------
# semi-async round loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", ["fedavg", "heroes"])
def test_semi_async_round_mode(scheme, image_setup):
    model, px, py, test = image_setup
    cfg = _cfg(round_mode="semi_async", async_k=2, eval_every=4)
    hist = run_scheme(scheme, model, px, py, test, rounds=8, cfg=cfg)
    assert len(hist) == 8
    walls = [h.wall_time for h in hist]
    assert all(b > a for a, b in zip(walls, walls[1:])), "wall clock not monotone"
    assert all(h.makespan > 0 and h.avg_wait >= 0 for h in hist)
    # with K < M, stragglers must land in later rounds as stale merges
    assert sum(h.stale for h in hist) > 0, "no staleness events logged"
    accs = [h.accuracy for h in hist if h.accuracy is not None]
    assert accs and np.isfinite(accs[-1])
    traffics = [h.traffic_bytes for h in hist]
    assert all(b >= a for a, b in zip(traffics, traffics[1:]))


def test_legacy_backend_warns_and_routes_to_engine(image_setup):
    """build_runner(backend='legacy') is a deprecation shim onto the
    engine now — including configs the legacy tree never supported."""
    model, px, py, test = image_setup
    with pytest.warns(DeprecationWarning, match="legacy"):
        hist = run_scheme("fedavg", model, px, py, test, rounds=1,
                          cfg=_cfg(round_mode="semi_async", async_k=2),
                          backend="legacy")
    assert len(hist) == 1 and hist[0].traffic_bytes > 0
    with pytest.raises(ValueError, match="unknown backend"):
        build_runner("fedavg", model, px, py, test, cfg=_cfg(),
                     backend="nonsense")


# ---------------------------------------------------------------------------
# registry extensibility
# ---------------------------------------------------------------------------


def test_register_custom_scheme(image_setup):
    """A new scheme is a bundle, not a runner subclass."""
    from repro.fl.engine import (DenseMeanAggregator, DensePayload,
                                 TierWidthAssignment)

    @register_scheme("_test_tiered_fedavg")
    def _bundle():
        return SchemeBundle(
            name="_test_tiered_fedavg",
            assignment=TierWidthAssignment,
            payload=lambda: DensePayload(sliced=False),
            aggregator=DenseMeanAggregator,
            factorized=False,
            estimate=lambda cfg: False,
        )

    try:
        model, px, py, test = image_setup
        hist = run_scheme("_test_tiered_fedavg", model, px, py, test,
                          rounds=1, cfg=_cfg())
        assert len(hist) == 1 and hist[0].traffic_bytes > 0
    finally:
        SCHEMES.pop("_test_tiered_fedavg", None)


# ---------------------------------------------------------------------------
# FedProx bundle (scheme-owned local trainer)
# ---------------------------------------------------------------------------


def test_fedprox_mu_zero_matches_fedavg(image_setup):
    """mu = 0 removes the proximal pull: FedProx must reproduce FedAvg's
    history (same assignment/payload/merge, same RNG contract)."""
    model, px, py, test = image_setup
    h_avg = run_scheme("fedavg", model, px, py, test, rounds=3,
                       cfg=_cfg(prox_mu=0.0))
    h_prox = run_scheme("fedprox", model, px, py, test, rounds=3,
                        cfg=_cfg(prox_mu=0.0))
    _assert_history_parity(h_avg, h_prox)


def test_fedprox_proximal_term_pulls_toward_global(image_setup):
    """With a large mu the local updates stay closer to the global model
    than plain FedAvg's."""
    import jax
    from repro.fl import build_runner

    model, px, py, test = image_setup

    def drift(scheme, mu):
        eng = build_runner(scheme, model, px, py, test, cfg=_cfg(prox_mu=mu))
        _, assigns = eng.assignment.assign(eng.state, [0, 1])
        results = eng.trainer.train_all(eng.state, assigns)
        base = jax.tree_util.tree_leaves(eng.params)
        tot = 0.0
        for r in results.values():
            for la, lb in zip(jax.tree_util.tree_leaves(r.params), base):
                tot += float(np.sum((np.asarray(la) - np.asarray(lb)) ** 2))
        return tot

    assert drift("fedprox", mu=5.0) < drift("fedavg", mu=5.0)


def test_fedprox_bundle_trainer_overrides_cfg(image_setup):
    from repro.fl import build_runner
    from repro.fl.engine import ProximalTrainer

    model, px, py, test = image_setup
    eng = build_runner("fedprox", model, px, py, test,
                       cfg=_cfg(trainer="cohort"))
    assert isinstance(eng.trainer, ProximalTrainer)


def test_proximal_trainer_ships_estimates(image_setup):
    """Regression: with an estimate-shipping scheme (ADP/Heroes) the
    FedProx solver must compute (L, sigma^2, G^2) under the same RNG
    contract as the sequential backend — at mu=0 the trajectories agree,
    so the estimates must too."""
    from repro.fl import build_runner
    from repro.fl.engine import ProximalTrainer

    model, px, py, test = image_setup
    e_seq = build_runner("adp", model, px, py, test, cfg=_cfg())
    e_prox = build_runner("adp", model, px, py, test, cfg=_cfg())
    assert e_seq.estimate and e_prox.estimate
    seq, prox = SequentialTrainer(), ProximalTrainer(mu=0.0)
    seq.setup(e_seq)
    prox.setup(e_prox)
    _, a_seq = e_seq.assignment.assign(e_seq.state, [0, 1])
    _, a_prox = e_prox.assignment.assign(e_prox.state, [0, 1])
    r_seq = seq.train_all(e_seq.state, a_seq)
    r_prox = prox.train_all(e_prox.state, a_prox)
    for n in r_seq:
        assert r_prox[n].estimates, "FedProx dropped the estimate signals"
        for k in ("L", "sigma_sq", "grad_sq"):
            np.testing.assert_allclose(r_prox[n].estimates[k],
                                       r_seq[n].estimates[k],
                                       rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# sample-count-weighted aggregation (FLConfig.sample_weighted)
# ---------------------------------------------------------------------------


def test_sample_weighted_matches_manual_weighted_mean():
    """FedAvg with sample_weighted=True must produce exactly
    sum(s_n * u_n) / sum(s_n) — the blend-weights formulation cancels to
    the weighted mean for global-mean rules."""
    import jax

    # 8-client dirichlet partition: known-unbalanced shard sizes
    model, px, py, test = build_image_setup(num_clients=8, seed=0)
    cfg_kw = dict(num_clients=8, clients_per_round=4)
    eng = build_runner("fedavg", model, px, py, test,
                       cfg=_cfg(sample_weighted=True, **cfg_kw))
    # twin engine (same seed) to reconstruct the per-client updates
    twin = build_runner("fedavg", model, px, py, test, cfg=_cfg(**cfg_kw))
    clients = twin.rng.choice(8, 4, replace=False)
    _, assigns = twin.assignment.assign(twin.state, list(map(int, clients)))
    results = twin.trainer.train_all(twin.state, assigns)
    s = np.array([twin.data.num_samples(n) for n in results], np.float64)
    assert len(set(s)) > 1, "partition is balanced; test would be vacuous"
    w = s / s.sum()
    expected = None
    for (n, r), wn in zip(results.items(), w):
        t = jax.tree_util.tree_map(
            lambda u, wn=wn: wn * np.asarray(u, np.float64), r.host_params())
        expected = t if expected is None else \
            jax.tree_util.tree_map(np.add, expected, t)

    eng.run_round()
    for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                    jax.tree_util.tree_leaves(expected)):
        np.testing.assert_allclose(np.asarray(a, np.float64), b, atol=1e-5)


def test_sample_weighted_default_off_keeps_history(image_setup):
    model, px, py, test = image_setup
    h_def = run_scheme("heroes", model, px, py, test, rounds=2, cfg=_cfg())
    h_off = run_scheme("heroes", model, px, py, test, rounds=2,
                       cfg=_cfg(sample_weighted=False))
    _assert_history_parity(h_def, h_off, acc_atol=0.0)


@pytest.mark.parametrize("scheme", ["fedavg", "heroes"])
def test_sample_weighted_runs_all_loops(scheme, image_setup):
    """Weighted merges stay finite in both round loops (semi-async
    multiplies sample weights into the staleness discounts)."""
    model, px, py, test = image_setup
    for kw in (dict(), dict(round_mode="semi_async", async_k=2)):
        hist = run_scheme(scheme, model, px, py, test, rounds=4,
                          cfg=_cfg(sample_weighted=True, eval_every=4, **kw))
        accs = [h.accuracy for h in hist if h.accuracy is not None]
        assert accs and np.isfinite(accs[-1])


# ---------------------------------------------------------------------------
# semi-async empty-pool guard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", ["fedavg", "heroes"])
def test_semi_async_empty_pool_skips_dispatch(scheme, image_setup):
    """clients_per_round > num_clients with every client already in
    flight must aggregate what is there instead of crashing in
    rng.choice / dispatching an empty assignment."""
    model, px, py, test = image_setup
    cfg = _cfg(num_clients=10, clients_per_round=12, round_mode="semi_async",
               async_k=2, eval_every=100)
    eng = build_runner(scheme, model, px, py, test, cfg=cfg)
    # force the saturated state: every client in flight before the round
    eng.state = eng.loop._dispatch(eng.state, list(range(10)))
    assert len(eng.state.in_flight) == 10
    log = eng.run_round()  # need = 2 > 0, pool empty
    assert log.round == 1 and log.makespan > 0
    # and the loop keeps making progress afterwards
    assert eng.run_round().round == 2


# ---------------------------------------------------------------------------
# streaming evaluation (FLConfig.eval_batch_size)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", ["heterofl", "heroes"])
def test_streaming_eval_matches_full_batch(scheme, image_setup):
    model, px, py, test = image_setup
    h_full = run_scheme(scheme, model, px, py, test, rounds=2,
                        cfg=_cfg(eval_every=1))
    h_stream = run_scheme(scheme, model, px, py, test, rounds=2,
                          cfg=_cfg(eval_every=1, eval_batch_size=64))
    for a, b in zip(h_full, h_stream):
        assert abs(a.accuracy - b.accuracy) < 1e-5


def test_eval_batches_cover_test_set(image_setup):
    from repro.fl import build_runner

    model, px, py, test = image_setup
    eng = build_runner("fedavg", model, px, py, test,
                       cfg=_cfg(eval_batch_size=32))
    n = int(test["labels"].shape[0])
    batches = list(eng.eval_batches())
    assert sum(int(b["labels"].shape[0]) for b in batches) == n
    assert all(int(b["labels"].shape[0]) <= 32 for b in batches)


# ---------------------------------------------------------------------------
# jit-cache identity fix (repro.fl.client.client_step)
# ---------------------------------------------------------------------------


def test_client_jit_cache_distinguishes_model_kwargs():
    """Two CNNs differing only in a constructor kwarg the old string key
    dropped (in_ch) must not share compiled functions."""
    import jax
    from repro.fl import client as client_lib

    rng = np.random.default_rng(0)
    for in_ch in (3, 1):
        model = make_cnn(max_width=2, in_ch=in_ch)
        params = model.init_factorized(jax.random.PRNGKey(0))
        x = rng.normal(size=(8, 8, 8, in_ch)).astype(np.float32)
        y = rng.integers(0, 10, size=8)
        res = client_lib.local_train(
            model, params, 2, 2, x, y, 0.05,
            np.random.default_rng(1), batch_size=4,
            factorized=True, estimate=False)
        assert np.isfinite(res.loss_after)
