"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (shape
casts Mosaic cannot lay out, strided vector slices, rank-0 refs).
Interpret-mode parity (tests/test_kernels.py) cannot see any of that.

Shapes are those ``chip_smoke.py`` runs: the ``transformer`` at
``d_base=128``, width 3, batch 16 x 32 tokens, and the ``cnn`` at width 3
on 8x8 images.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.kernels.compose import (compose_apply_pallas, compose_pallas,
                                   rank_apply_pallas)
from repro.kernels.conv_rank import conv_rank_pallas
from repro.kernels.decode_attention import decode_attention_pallas

P = 3  # composed width
C = 8  # cohort clients per round
M = 16 * 32  # dense rows: batch x sequence
D_BASE, FF_BASE, R = 128, 256, 8


@pytest.fixture(scope="module")
def topo():
    """A v5e:2x2 topology, with JAX's persistent compilation cache off:
    a TPU executable written there cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            jax.config.update("jax_enable_compilation_cache", cache_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


f32, i32 = jnp.float32, jnp.int32


# name -> (function of arrays, [(shape, dtype), ...])
KERNELS = {
    # materialize-path compose of the transformer's up projection
    "compose_pallas": (
        lambda v, u: compose_pallas(v, u, interpret=False),
        [((1, D_BASE, R), f32), ((P * P, R, FF_BASE), f32)]),
    # an expert bank's compose: one batched call over its 8 experts
    "compose_pallas-expert_bank": (
        lambda v, u: compose_pallas(v, u, interpret=False),
        [((8, 1, D_BASE, R), f32), ((8, P * P, R, FF_BASE), f32)]),
    # rank-space dense apply: square projection and the grow_in head
    "rank_apply_pallas-square": (
        lambda x, v, u: rank_apply_pallas(x, v, u, interpret=False),
        [((M, P, D_BASE), f32), ((D_BASE, R), f32),
         ((P * R, P * D_BASE), f32)]),
    "rank_apply_pallas-grow_in": (
        lambda x, v, u: rank_apply_pallas(x, v, u, interpret=False),
        [((M, P, D_BASE), f32), ((D_BASE, R), f32), ((P * R, 64), f32)]),
    "rank_apply_pallas-cohort_vmap": (
        jax.vmap(lambda x, v, u: rank_apply_pallas(x, v, u, interpret=False)),
        [((C, M, P, D_BASE), f32), ((C, D_BASE, R), f32),
         ((C, P * R, P * D_BASE), f32)]),
    # fused compose+apply: transformer projection and the cnn head
    "compose_apply_pallas-square": (
        lambda x, v, u: compose_apply_pallas(x, v, u, interpret=False),
        [((M, P, D_BASE), f32), ((D_BASE, R), f32),
         ((P, R, P * D_BASE), f32)]),
    "compose_apply_pallas-cnn_head": (
        lambda x, v, u: compose_apply_pallas(x, v, u, interpret=False),
        [((16, P, 8), f32), ((8, R), f32), ((P, R, 10), f32)]),
    # fused conv rank path: the cnn stem (grow_out), conv2 and conv3
    # (square, stride 2), a stride-1 square conv, and conv2 vmapped
    "conv_rank_pallas-grow_out_s1": (
        lambda x, v, u: conv_rank_pallas(x, v, u, p=P, mode="grow_out",
                                         stride=1, interpret=False),
        [((16, 8, 8, 3), f32), ((9, 3, R), f32), ((R, P * 8), f32)]),
    "conv_rank_pallas-square_s2": (
        lambda x, v, u: conv_rank_pallas(x, v, u, p=P, mode="square",
                                         stride=2, interpret=False),
        [((16, 8, 8, P * 8), f32), ((9, 8, R), f32), ((P * R, P * 8), f32)]),
    "conv_rank_pallas-square_s2_4x4": (
        lambda x, v, u: conv_rank_pallas(x, v, u, p=P, mode="square",
                                         stride=2, interpret=False),
        [((16, 4, 4, P * 8), f32), ((9, 8, R), f32), ((P * R, P * 8), f32)]),
    "conv_rank_pallas-square_s1": (
        lambda x, v, u: conv_rank_pallas(x, v, u, p=P, mode="square",
                                         stride=1, interpret=False),
        [((16, 8, 8, P * 8), f32), ((9, 8, R), f32), ((P * R, P * 8), f32)]),
    "conv_rank_pallas-cohort_vmap": (
        jax.vmap(lambda x, v, u: conv_rank_pallas(x, v, u, p=P, mode="square",
                                               stride=2, interpret=False)),
        [((C, 16, 8, 8, P * 8), f32), ((C, 9, 8, R), f32),
         ((C, P * R, P * 8), f32)]),
    # serving: batch 2 x 6 heads of 64 over a 16-slot cache
    "decode_attention_pallas": (
        lambda q, k, v, n: decode_attention_pallas(q, k, v, n,
                                                   interpret=False),
        [((12, 64), f32), ((12, 16, 64), f32), ((12, 16, 64), f32),
         ((12,), i32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    txt = _compiled_text(fn, shapes, one_chip)
    assert "tpu_custom_call" in txt


def test_transformer_cohort_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole cohort train step (vmap over clients, scan over tau,
    grad through every composed layer) with the kernel branch taken, as
    it runs on the chip."""
    from repro.fl import FLConfig
    from repro.core.calibration import for_dispatch
    from repro.fl.engine.trainers import _cohort_fns
    from repro.fl.transformer import make_transformer
    import repro.kernels.compose as compose_mod

    # the kernels' platform gate reads the host backend (CPU here)
    monkeypatch.setattr(compose_mod, "default_interpret", lambda: False)
    model = make_transformer(max_width=P, d_base=D_BASE, vocab=64)
    # pinned calibration: no timing run, and fused compose+apply wins
    cfg = FLConfig(conv_rank_overhead=1.0, fused_compose_gain=0.5)
    cal = for_dispatch(cfg)
    impls = model.layer_impls(P, 16, "auto", (16, 32), cal)
    assert "rank_space" in impls.values()

    def reduced():
        params = model.init_factorized(jax.random.PRNGKey(0))
        return model.reduce(params, P, np.arange(P * P), np.arange(P))

    shapes = jax.eval_shape(reduced)
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((C,) + s.shape, s.dtype,
                                       sharding=one_chip), shapes)
    tau_pad = 2
    tok = jax.ShapeDtypeStruct((tau_pad, C, 16, 32), i32, sharding=one_chip)
    taus = jax.ShapeDtypeStruct((C,), i32, sharding=one_chip)
    train_fn, _ = _cohort_fns(model, P, True, None, "auto", cal)
    txt = train_fn.lower(stacked, {"tokens": tok, "labels": tok}, taus,
                         0.05).compile().as_text()
    assert "tpu_custom_call" in txt


def test_routed_experts_compile_for_v5e(one_chip):
    """The grouped expert matmuls of ``moe_ffn`` (8 held experts of 64,
    top-6), forward and backward, lower to the chip's ragged-dot kernel."""
    from repro.fl.transformer import moe_ffn

    def loss(x, router, gate, up, down):
        y, _ = moe_ffn(x, router, gate, up, down, top_k=6)
        return jnp.sum(y * y)

    txt = _compiled_text(
        jax.grad(loss, argnums=(0, 2, 3, 4)),
        [((M, D_BASE), f32), ((D_BASE, 64), f32),
         ((8, D_BASE, FF_BASE), f32), ((8, D_BASE, FF_BASE), f32),
         ((8, FF_BASE, D_BASE), f32)], one_chip)
    assert "ragged-dot" in txt and "tpu_custom_call" in txt
