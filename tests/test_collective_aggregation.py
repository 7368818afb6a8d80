"""Parity suite for the collective aggregation path.

The engine's default merge (repro.fl.engine.collective) stacks dense
zero-padded contributions + masks and merges them in one compiled call;
on a single device it must reproduce the host scatter loops *bitwise*
(weights=None — and, on CPU, the numpy staleness blends match the eager
jax blends bitwise too, which the semi-async test pins down).  On a
multi-device mesh the psum re-associates the client fold, so parity is
to float tolerance.

Multi-device cases run in subprocesses because the host-platform device
count must be configured before jax initialises.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]

SINGLE_DEVICE = len(jax.devices()) == 1


def _leaves_equal(a, b, exact):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if exact:
            np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_allclose(x, y, atol=1e-5, rtol=1e-5)


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


# ---------------------------------------------------------------------------
# engine-level parity: collective (default) vs host backend, all 5 schemes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme",
                         ["fedavg", "adp", "heterofl", "flanc", "heroes"])
def test_collective_matches_host_backend(scheme, image_setup):
    """Same seed, same rounds: the collective merge must reproduce the
    host scatter loop — bitwise on one device, tol on a mesh."""
    from repro.fl import build_runner

    model, px, py, test = image_setup
    host = build_runner(scheme, model, px, py, test,
                        cfg=_cfg(agg_backend="host"))
    coll = build_runner(scheme, model, px, py, test,
                        cfg=_cfg(agg_backend="collective"))
    assert coll.merger is not None
    for _ in range(2):
        a, b = host.run_round(), coll.run_round()
        assert a.wall_time == b.wall_time
        assert a.traffic_bytes == b.traffic_bytes
    _leaves_equal(host.params, coll.params, exact=SINGLE_DEVICE)


@pytest.mark.parametrize("scheme", ["fedavg", "heroes"])
def test_collective_semi_async_staleness_parity(scheme, image_setup):
    """Stale merges (decay**staleness weights) must blend identically on
    both backends — the collective path folds the blend into the dense
    contribution prep."""
    from repro.fl import build_runner

    model, px, py, test = image_setup
    kw = dict(round_mode="semi_async", async_k=2, eval_every=4)
    host = build_runner(scheme, model, px, py, test,
                        cfg=_cfg(agg_backend="host", **kw))
    coll = build_runner(scheme, model, px, py, test,
                        cfg=_cfg(agg_backend="collective", **kw))
    stale = 0
    for _ in range(5):
        a, b = host.run_round(), coll.run_round()
        assert a.wall_time == b.wall_time
        stale += a.stale
    assert stale > 0, "no staleness events — the weighted path was not hit"
    _leaves_equal(host.params, coll.params, exact=SINGLE_DEVICE)


@pytest.mark.parametrize("scheme", ["fedavg", "heroes"])
def test_collective_sample_weighted_parity(scheme, image_setup):
    """FLConfig.sample_weighted rides the same blend-weights path as the
    staleness discounts — both backends must merge identically."""
    from repro.fl import build_runner

    model, px, py, test = image_setup
    host = build_runner(scheme, model, px, py, test,
                        cfg=_cfg(agg_backend="host", sample_weighted=True))
    coll = build_runner(scheme, model, px, py, test,
                        cfg=_cfg(agg_backend="collective",
                                 sample_weighted=True))
    for _ in range(2):
        a, b = host.run_round(), coll.run_round()
        assert a.wall_time == b.wall_time
    _leaves_equal(host.params, coll.params, exact=SINGLE_DEVICE)


# ---------------------------------------------------------------------------
# core-level properties of the stacked merge
# ---------------------------------------------------------------------------


def test_masked_block_merge_duplicates_and_zero_blocks():
    """Duplicate ids within a client accumulate like the host scatter's
    at[ids].add, and blocks with zero trainers keep the previous value —
    bitwise on one device."""
    from repro.core import (aggregate_coefficient, masked_block_merge,
                            scatter_contributions_host)

    rng = np.random.default_rng(3)
    NB, R, O = 6, 4, 5
    prev = jnp.asarray(rng.normal(size=(NB, R, O)).astype(np.float32))
    # client 0 trains block 1 twice (duplicate id); nobody trains block 5
    ids = [np.array([0, 1, 1]), np.array([2, 3]), np.array([0, 2, 4])]
    blocks = [rng.normal(size=(len(i), R, O)).astype(np.float32)
              for i in ids]
    host = aggregate_coefficient(prev, [jnp.asarray(b) for b in blocks], ids)

    dense, mask = scatter_contributions_host(blocks, ids, NB)
    assert mask[0, 1] == 2.0  # duplicate counted twice
    assert np.all(mask[:, 5] == 0.0)
    merged = jax.jit(masked_block_merge)(jnp.asarray(dense),
                                         jnp.asarray(mask), prev)
    np.testing.assert_array_equal(np.asarray(host), np.asarray(merged))
    # untrained block keeps the previous value bitwise
    np.testing.assert_array_equal(np.asarray(merged[5]), np.asarray(prev[5]))


def test_ordered_sum_matches_sequential_adds():
    from repro.core import ordered_sum

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(9, 5, 7)).astype(np.float32) * 100)
    acc = jnp.zeros_like(x[0])
    for k in range(x.shape[0]):
        acc = acc + x[k]
    np.testing.assert_array_equal(np.asarray(acc),
                                  np.asarray(jax.jit(ordered_sum)(x)))


def test_aggregation_preserves_coeff_dtype():
    """Regression: bf16 coefficients must come back bf16 from both the
    host scatter loop and the collective merge (the counters stay f32
    internally but may not leak into the output dtype)."""
    from repro.core import (aggregate_coefficient, masked_block_merge,
                            scatter_contributions_host)

    rng = np.random.default_rng(1)
    NB, R, O = 4, 3, 3
    for dtype in (jnp.bfloat16, jnp.float16, jnp.float32):
        prev = jnp.asarray(rng.normal(size=(NB, R, O)), dtype=dtype)
        ids = [np.array([0, 2]), np.array([1, 2])]
        blocks = [jnp.asarray(rng.normal(size=(2, R, O)), dtype=dtype)
                  for _ in ids]
        host = aggregate_coefficient(prev, blocks, ids)
        assert host.dtype == dtype
        # weighted path too
        hw = aggregate_coefficient(prev, blocks, ids, weights=[0.5, 1.0])
        assert hw.dtype == dtype
        dense, mask = scatter_contributions_host(
            [np.asarray(b) for b in blocks], ids, NB)
        merged = masked_block_merge(jnp.asarray(dense), jnp.asarray(mask),
                                    prev)
        assert merged.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(host, np.float32), np.asarray(merged, np.float32),
            atol=1e-2)


def test_collective_merger_bf16_roundtrip():
    """The engine merger keeps non-f32 factorized params in their dtype."""
    from repro.fl.engine.collective import CollectiveMerger
    from repro.fl.client import ClientResult

    class Spec:
        mode = "square"

    rng = np.random.default_rng(0)
    NB, R, O = 4, 3, 3
    prev = {"l": {"basis": jnp.asarray(rng.normal(size=(2, R, 4)),
                                       dtype=jnp.bfloat16),
                  "coeff": jnp.asarray(rng.normal(size=(NB, R, O)),
                                       dtype=jnp.bfloat16)}}
    results, assigns = {}, {}
    for n in range(3):
        ids = np.sort(rng.choice(NB, size=2, replace=False))
        results[n] = ClientResult(
            {"l": {"basis": np.asarray(rng.normal(size=(2, R, 4)),
                                       np.float32).astype(jnp.bfloat16),
                   "coeff": np.asarray(rng.normal(size=(2, R, O)),
                                       np.float32).astype(jnp.bfloat16)}},
            {}, 0.0, 0.0)
        assigns[n] = {"hidden_ids": ids}
    merger = CollectiveMerger()
    out = merger.merge_factorized(prev, {"l": Spec()}, results, assigns)
    assert out["l"]["basis"].dtype == jnp.bfloat16
    assert out["l"]["coeff"].dtype == jnp.bfloat16


def _tiny_round(rng, prev):
    """Three clients of widths 1, 2 and 3 (1, 4 and 9 hidden blocks) on
    a tiny two-tensor factorized model: the width-3 client trains block 2
    twice, nobody trains block 8."""
    from repro.fl.client import ClientResult

    ids = [np.array([4]), np.array([0, 1, 5, 6]),
           np.array([0, 1, 2, 2, 3, 4, 5, 6, 7])]
    results, assigns = {}, {}
    for n, i in zip((7, 2, 5), ids):
        results[n] = ClientResult(
            {name: {"basis": rng.normal(size=t["basis"].shape)
                    .astype(np.float32),
                    "coeff": rng.normal(size=(len(i),) + t["coeff"].shape[1:])
                    .astype(np.float32)}
             for name, t in prev.items()}, {}, 0.0, 0.0)
        assigns[n] = {"hidden_ids": i}
    return results, assigns


def _tiny_model(rng):
    class Square:
        mode = "square"

    shapes = {"a": ((2, 3, 5), (9, 3, 4)), "b": ((3, 2, 6), (9, 2, 6))}
    prev = {name: {"basis": jnp.asarray(rng.normal(size=b)
                                        .astype(np.float32)),
                   "coeff": jnp.asarray(rng.normal(size=c)
                                        .astype(np.float32))}
            for name, (b, c) in shapes.items()}
    return prev, {name: Square() for name in shapes}


def test_device_scatter_stacks_match_the_host_contract():
    """The merger's per-client device scatter lays out the same dense
    contributions and masks as ``scatter_contributions_host``, and the
    bases' stack, in results order: duplicate ids accumulate and each
    counts in the mask.  Numpy factors are uploaded with the call:
    blocks, bases and ids."""
    from repro.core import scatter_contributions_host
    from repro.fl.engine.collective import CollectiveMerger

    rng = np.random.default_rng(11)
    prev, specs = _tiny_model(rng)
    results, assigns = _tiny_round(rng, prev)
    stacked, _, uploaded = CollectiveMerger()._stack_factorized(
        prev, specs, results, assigns, None, len(results))
    for name in specs:
        dense, mask = scatter_contributions_host(
            [r.params[name]["coeff"] for r in results.values()],
            [a["hidden_ids"] for a in assigns.values()], 9)
        assert isinstance(stacked[name]["dense"], jax.Array)
        np.testing.assert_array_equal(np.asarray(stacked[name]["dense"]),
                                      dense)
        np.testing.assert_array_equal(np.asarray(stacked[name]["mask"]),
                                      mask)
        assert mask[2, 2] == 2.0
        assert isinstance(stacked[name]["bases"], jax.Array)
        np.testing.assert_array_equal(
            np.asarray(stacked[name]["bases"]),
            np.stack([r.params[name]["basis"] for r in results.values()]))
    assert uploaded == sum(r.params[n]["coeff"].nbytes
                           + r.params[n]["basis"].nbytes
                           + 4 * len(assigns[c]["hidden_ids"])
                           for c, r in results.items() for n in specs)


def test_device_scatter_merge_matches_host_aggregate_bitwise():
    """Mixed widths, a duplicate id and an untrained block: the merge
    equals the host ``aggregate_factorized`` bitwise on one device, and
    the untrained block keeps ``prev`` bitwise."""
    from repro.core import aggregate_factorized
    from repro.fl.engine.collective import CollectiveMerger

    rng = np.random.default_rng(12)
    prev, specs = _tiny_model(rng)
    results, assigns = _tiny_round(rng, prev)
    host = aggregate_factorized(
        prev, [r.params for r in results.values()],
        [a["hidden_ids"] for a in assigns.values()])
    merged = CollectiveMerger().merge_factorized(prev, specs, results,
                                                 assigns)
    _leaves_equal(host, merged, exact=SINGLE_DEVICE)
    for name in specs:
        np.testing.assert_array_equal(np.asarray(merged[name]["coeff"][8]),
                                      np.asarray(prev[name]["coeff"][8]))


def test_device_scatter_builds_no_program_for_widths_seen():
    """A second round with the widths of the first builds no new
    per-client scatter program."""
    from repro.fl.engine import collective

    rng = np.random.default_rng(13)
    prev, specs = _tiny_model(rng)
    merger = collective.CollectiveMerger()
    for rnd in range(2):
        results, assigns = _tiny_round(rng, prev)
        prev = merger.merge_factorized(prev, specs, results, assigns)
        jax.block_until_ready(prev)
        if rnd == 0:
            built = collective._scatter_client._cache_size()
    assert built >= 3
    assert collective._scatter_client._cache_size() == built


# ---------------------------------------------------------------------------
# SPMD: real multi-device meshes (subprocess so XLA_FLAGS precede jax init)
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    from repro.core import (aggregate_coefficient, masked_block_mean,
                            scatter_contribution)

    NB, R, O = 4, 3, 5
    rng = np.random.default_rng(0)
    prev = jnp.asarray(rng.normal(size=(NB, R, O)).astype(np.float32))

    # 8 clients, each training a random subset of blocks
    ids, blocks, dense, masks = [], [], [], []
    for c in range(8):
        take = np.sort(rng.choice(NB, size=rng.integers(1, NB + 1),
                                  replace=False))
        blk = jnp.asarray(rng.normal(size=(len(take), R, O)).astype(np.float32))
        ids.append(take)
        blocks.append(blk)
        d, m = scatter_contribution(blk, jnp.asarray(take), NB)
        dense.append(d)
        masks.append(m)

    host = aggregate_coefficient(prev, blocks, ids)

    mesh = jax.make_mesh((8,), ("clients",))
    dense_all = jnp.stack(dense)  # (8, NB, R, O)
    mask_all = jnp.stack(masks)  # (8, NB)

    @jax.jit
    def agg(dense_all, mask_all, prev):
        f = shard_map(
            lambda d, m, p: masked_block_mean(d[0], m[0], p, "clients"),
            mesh=mesh,
            in_specs=(P("clients"), P("clients"), P()),
            out_specs=P(),
        )
        return f(dense_all, mask_all, prev)

    spmd = agg(dense_all, mask_all, prev)
    np.testing.assert_allclose(np.asarray(host), np.asarray(spmd), atol=1e-5)
    print("SPMD_AGG_OK")
""")


ENGINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    assert len(jax.devices()) == 4
    from repro.fl import FLConfig, build_image_setup, build_runner

    model, px, py, test = build_image_setup(num_clients=8, max_width=4,
                                            seed=0)
    base = dict(num_clients=8, clients_per_round=3, eval_every=2,
                tau_fixed=2, tau_max=15, estimate=True)
    for scheme in ("fedavg", "heterofl", "flanc", "heroes"):
        host = build_runner(scheme, model, px, py, test,
                            cfg=FLConfig(**base, agg_backend="host"))
        coll = build_runner(scheme, model, px, py, test,
                            cfg=FLConfig(**base, agg_backend="collective"))
        assert coll.merger is not None and coll.merger.mesh is not None
        for _ in range(2):
            a, b = host.run_round(), coll.run_round()
            assert a.wall_time == b.wall_time
        for x, y in zip(jax.tree_util.tree_leaves(host.params),
                        jax.tree_util.tree_leaves(coll.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-5, rtol=1e-5)

    # block-sharded server state: P=4 CNN has 16 hidden / 4 anchored
    # blocks, both divisible by the 4-device mesh
    from jax.sharding import PartitionSpec
    sh = build_runner("heroes", model, px, py, test,
                      cfg=FLConfig(**base, shard_server_state=True))
    for _ in range(2):
        sh.run_round()
    for name, t in sh.params.items():
        assert t["coeff"].sharding.spec == PartitionSpec("cohort"), name
    assert np.isfinite(sh.eval_accuracy())
    print("SPMD_ENGINE_OK")
""")


def _run_subprocess(script: str) -> subprocess.CompletedProcess:
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    return subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)


def test_masked_psum_aggregation_spmd():
    r = _run_subprocess(SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SPMD_AGG_OK" in r.stdout


def test_engine_collective_spmd_parity():
    """Full engine rounds on a 4-device mesh: collective == host to float
    tolerance for all factorized/dense schemes, plus block-sharded
    server state staying sharded across rounds."""
    r = _run_subprocess(ENGINE_SCRIPT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SPMD_ENGINE_OK" in r.stdout
