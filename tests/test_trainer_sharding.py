"""Mesh-sharded cohort training (repro.fl.engine.trainers + sharding.fl).

The cohort trainer lays its client axis out on the same 1-D device mesh
the collective merge rides (``COHORT_AXIS``).  On one device the code
path is the unchanged single-device cohort step (bitwise); on a mesh the
per-client math is identical, so the parity matrix below holds at float
tolerance and — under the 4-device CI leg — exercises the sharded
train + device-resident hand-off end to end.  Explicit 4-device cases
run in subprocesses (XLA_FLAGS must precede jax init).
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.data.streaming import stack_client_shards
from repro.sharding import fl as flsh

ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def test_cohort_mesh_uses_local_devices(monkeypatch):
    """Regression: the mesh must be built over jax.local_devices() —
    under multi-process JAX, jax.devices() lists devices other hosts
    own, which this process cannot address."""
    calls = {"local": 0}
    real_local = jax.local_devices

    def fake_global():  # pragma: no cover - failing is the assertion
        pytest.fail("cohort_mesh consulted jax.devices() (global) "
                    "instead of jax.local_devices()")

    def fake_local():
        calls["local"] += 1
        return real_local()

    monkeypatch.setattr(jax, "devices", fake_global)
    monkeypatch.setattr(jax, "local_devices", fake_local)
    mesh = flsh.cohort_mesh()
    assert calls["local"] == 1
    if len(real_local()) < 2:
        assert mesh is None
    else:
        assert mesh.devices.size == len(real_local())


class _FakeMesh:
    def __init__(self, n):
        self.devices = np.empty((n,), object)


def test_pad_cohort_rounds_to_mesh_multiple():
    assert flsh.pad_cohort(5, None) == 5
    mesh = _FakeMesh(4)
    assert flsh.pad_cohort(1, mesh) == 4
    assert flsh.pad_cohort(4, mesh) == 4
    assert flsh.pad_cohort(9, mesh) == 12


def test_stack_client_shards_matches_monolithic_stack():
    rng = np.random.default_rng(0)
    per_client = [rng.normal(size=(3, 4, 2)).astype(np.float32)
                  for _ in range(8)]
    mono = np.moveaxis(np.stack(per_client), 0, 1)
    # one chunk reproduces the monolithic stack bitwise
    (one,) = stack_client_shards(per_client, 1, step_leading=True)
    np.testing.assert_array_equal(one, mono)
    # four chunks concatenate back to it on the client axis
    four = stack_client_shards(per_client, 4, step_leading=True)
    assert len(four) == 4 and all(s.shape == (3, 2, 4, 2) for s in four)
    np.testing.assert_array_equal(np.concatenate(four, axis=1), mono)
    # non-step-leading keeps the client axis first
    chunks = stack_client_shards(per_client, 2)
    np.testing.assert_array_equal(np.concatenate(chunks, axis=0),
                                  np.stack(per_client))
    with pytest.raises(ValueError):
        stack_client_shards(per_client, 3)


def test_trainer_mesh_devices_cap():
    """trainer_mesh_devices=1 pins the single-device cohort path even on
    a multi-device host; 0 takes every local device."""
    from repro.fl import FLConfig, build_image_setup, build_runner

    model, px, py, test = build_image_setup(num_clients=6, seed=0)
    cfg = dict(num_clients=6, clients_per_round=2, tau_fixed=2,
               trainer="cohort", estimate=False)
    pinned = build_runner("fedavg", model, px, py, test,
                          cfg=FLConfig(**cfg, trainer_mesh_devices=1))
    assert pinned.trainer.mesh is None
    auto = build_runner("fedavg", model, px, py, test, cfg=FLConfig(**cfg))
    ndev = len(jax.local_devices())
    if ndev == 1:
        assert auto.trainer.mesh is None
    else:
        assert auto.trainer.mesh.devices.size == ndev


# ---------------------------------------------------------------------------
# trainer x aggregator parity matrix (sharded under the 4-device CI leg)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def image_setup():
    from repro.fl import build_image_setup

    return build_image_setup(num_clients=8, seed=0)


def _cfg(**kw):
    from repro.fl import FLConfig

    base = dict(num_clients=8, clients_per_round=3, eval_every=2,
                tau_fixed=2, tau_max=15, estimate=True)
    base.update(kw)
    return FLConfig(**base)


@pytest.mark.parametrize("scheme",
                         ["fedavg", "adp", "heterofl", "flanc", "heroes"])
def test_trainer_aggregator_parity_matrix(scheme, image_setup):
    """{sequential, cohort} x {host, collective} must agree on the
    virtual clock exactly and on accuracy to tolerance.  On one device
    every cell is the bitwise single-device path; under the 4-device CI
    leg the cohort cells run the mesh-sharded trainer (and the
    collective cell the device-resident hand-off)."""
    from repro.fl import run_scheme

    model, px, py, test = image_setup
    histories = {}
    for trainer in ("sequential", "cohort"):
        for agg in ("host", "collective"):
            histories[(trainer, agg)] = run_scheme(
                scheme, model, px, py, test, rounds=2,
                cfg=_cfg(trainer=trainer, agg_backend=agg))
    ref = histories[("sequential", "host")]
    for key, hist in histories.items():
        assert len(hist) == len(ref), key
        for a, b in zip(ref, hist):
            assert a.wall_time == b.wall_time, key
            assert a.traffic_bytes == b.traffic_bytes, key
            assert a.mean_tau == b.mean_tau, key
            if a.accuracy is not None:
                assert abs(a.accuracy - b.accuracy) <= 2e-3, key


# ---------------------------------------------------------------------------
# recompile-count regression (semi-async variable cohort sizes)
# ---------------------------------------------------------------------------


def test_semi_async_cohort_recompiles_bounded():
    """Semi-async dispatch sizes vary round to round; the power-of-two /
    mesh-multiple bucketing must keep the compiled cohort-step count at
    the handful of padded shapes, not one per cohort size.

    ``make_cnn`` memoizes model instances, so the jitted cohort step is
    shared process-wide — the regression is therefore on the cache
    *growth* across the variable-size rounds, not its absolute size.
    """
    from repro.fl import build_image_setup, build_runner
    from repro.fl.engine import trainers

    model, px, py, test = build_image_setup(num_clients=12, seed=1)
    cfg = _cfg(num_clients=12, clients_per_round=6, round_mode="semi_async",
               trainer="cohort", estimate=False, eval_every=100)
    eng = build_runner("fedavg", model, px, py, test, cfg=cfg)
    train_fn, _ = trainers._cohort_fns(eng.model, eng.P, eng.factorized,
                                       eng.trainer.mesh)
    if not hasattr(train_fn, "_cache_size"):
        pytest.skip("jit cache size introspection not available")
    before = train_fn._cache_size()
    for _ in range(10):
        eng.run_round()
    # dispatch sizes 1..6 bucket to at most {1, 2, 4, 6(full), 8} padded
    # client counts (mesh rounding can only merge buckets, not add)
    grown = train_fn._cache_size() - before
    assert grown <= 5, grown


# ---------------------------------------------------------------------------
# explicit 4-device SPMD cases (subprocess: XLA_FLAGS before jax init)
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    assert len(jax.devices()) == 4
    from repro.fl import FLConfig, build_image_setup, build_runner, run_scheme
    from repro.fl.engine.collective import CohortSlice

    model, px, py, test = build_image_setup(num_clients=8, seed=0)
    base = dict(num_clients=8, clients_per_round=3, eval_every=2,
                tau_fixed=2, tau_max=15, estimate=True)

    # the trainer mesh engages and hands the merger device-resident slices
    eng = build_runner("heroes", model, px, py, test,
                       cfg=FLConfig(**base, trainer="cohort"))
    assert eng.trainer.mesh is not None
    assert eng.trainer.mesh.devices.size == 4
    _, assigns = eng.assignment.assign(eng.state, [0, 1, 2])
    results = eng.trainer.train_all(eng.state, assigns)
    assert all(isinstance(r.params, CohortSlice) for r in results.values())
    leaves = jax.tree_util.tree_leaves(results[0].host_params())
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)

    # sharded cohort vs sequential, dense + factorized schemes
    for scheme in ("fedavg", "heroes"):
        h_seq = run_scheme(scheme, model, px, py, test, rounds=2,
                           cfg=FLConfig(**base))
        h_coh = run_scheme(scheme, model, px, py, test, rounds=2,
                           cfg=FLConfig(**base, trainer="cohort"))
        for a, b in zip(h_seq, h_coh):
            assert a.wall_time == b.wall_time
            assert a.traffic_bytes == b.traffic_bytes
            if a.accuracy is not None:
                assert abs(a.accuracy - b.accuracy) <= 2e-3, scheme

    # masked-clone parity: an odd cohort (3 of 8 on 4 devices) must give
    # the same per-client params as the 1-device-capped cohort path
    coh = build_runner("fedavg", model, px, py, test,
                       cfg=FLConfig(**base, trainer="cohort"))
    ref = build_runner("fedavg", model, px, py, test,
                       cfg=FLConfig(**base, trainer="cohort",
                                    trainer_mesh_devices=1))
    assert coh.trainer.mesh is not None and ref.trainer.mesh is None
    _, a4 = coh.assignment.assign(coh.state, [0, 1, 2])
    _, a1 = ref.assignment.assign(ref.state, [0, 1, 2])
    r4 = coh.trainer.train_all(coh.state, a4)
    r1 = ref.trainer.train_all(ref.state, a1)
    for n in r1:
        for x, y in zip(jax.tree_util.tree_leaves(r4[n].host_params()),
                        jax.tree_util.tree_leaves(r1[n].host_params())):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-5, rtol=1e-5)

    # fastest-K semi-async: an all-fresh event merges a strict SUBSET of
    # the trained stack through the weights=None device path — regression
    # for CohortStack.n_real (a stack pass-through must never leak the
    # still-in-flight stragglers' rows into the merge)
    kw = dict(num_clients=10, clients_per_round=4, eval_every=100,
              tau_fixed=3, tau_max=15, estimate=False,
              round_mode="semi_async", async_k=2)
    model, px, py, test = build_image_setup(num_clients=10, seed=0)
    for scheme in ("fedavg", "heroes"):
        host = build_runner(scheme, model, px, py, test,
                            cfg=FLConfig(**kw, agg_backend="host",
                                         trainer="cohort"))
        coll = build_runner(scheme, model, px, py, test,
                            cfg=FLConfig(**kw, agg_backend="collective",
                                         trainer="cohort"))
        for _ in range(4):
            a, b = host.run_round(), coll.run_round()
            assert a.wall_time == b.wall_time
            # stragglers must not pin device-resident stacks across
            # events (they are degraded to the numpy contract)
            assert all(not hasattr(t.result.params, "materialize")
                       for t in coll.state.in_flight)
        for x, y in zip(jax.tree_util.tree_leaves(host.params),
                        jax.tree_util.tree_leaves(coll.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-5, rtol=1e-5)
    print("SHARDED_TRAINER_OK")
""")


def _run_subprocess(script: str) -> subprocess.CompletedProcess:
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    return subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)


def test_sharded_cohort_trainer_spmd():
    r = _run_subprocess(SHARDED_SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_TRAINER_OK" in r.stdout


ONE_DEVICE_COMPOSE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.fl import make_transformer
    from repro.sharding import fl as flsh

    # the collective merge leaves the server state replicated (or
    # block-sharded) over the cohort mesh; eval and serving compose it
    # eagerly, and a Pallas compose cannot be partitioned by XLA
    model = make_transformer()
    params = model.init_factorized(jax.random.PRNGKey(0))
    mesh = flsh.cohort_mesh()
    assert mesh.devices.size == 4
    spread = jax.device_put(params, NamedSharding(mesh, P()))
    assert len(spread["head"]["coeff"].sharding.device_set) == 4
    ids = np.arange(9)
    w4 = model.compose_all(model.reduce(spread, 3, ids, ids[:3]), 3)
    w1 = model.compose_all(model.reduce(params, 3, ids, ids[:3]), 3)
    for name in w1:
        assert len(w4[name].sharding.device_set) == 1, name
        np.testing.assert_array_equal(np.asarray(w4[name]),
                                      np.asarray(w1[name]))
    print("ONE_DEVICE_COMPOSE_OK")
""")


def test_compose_all_moves_mesh_state_to_one_device():
    r = _run_subprocess(ONE_DEVICE_COMPOSE_SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ONE_DEVICE_COMPOSE_OK" in r.stdout
