"""The latent-attention + experts decoder (``make_mla_moe_transformer``)
against its plain float32 reference, at a tiny size on the CPU.

The reference is the chip benchmark's (``benchmarks/chip/reference/
mla_moe.py``), which imports nothing of the program: plain causal softmax
attention, per-token top-k routing, every held expert applied to every
token and weighted by its gate.  Tolerances are float32 re-association:
the program streams its softmax (``flash_attention``) and sorts, gathers
and scatters the routed pairs around a grouped matmul where the
reference sums dense products, so sums run in another order.
"""

import importlib
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation
from repro.core.composition import CompositionSpec, compose, init_factors
from repro.fl import FLConfig, build_runner
from repro.fl.client import _ce
from repro.fl.transformer import (greedy_decode, make_mla_moe_transformer,
                                  moe_ffn, serving_weights, yarn_inv_freq)
from repro.models.attention import flash_attention

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
TINY = dict(json.loads((CHIP / "tests" / "tiny-mla-moe.json").read_text())
            ["model"], max_width=3)
P = TINY["max_width"]


@pytest.fixture(scope="module")
def ref():
    """``benchmarks/chip/reference/mla_moe.py``, imported as a module of
    a package of its own name (its sibling ``transformer.py`` comes with
    it)."""
    name = "chip_reference"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, CHIP / "reference" / "__init__.py",
            submodule_search_locations=[str(CHIP / "reference")])
        pkg = importlib.util.module_from_spec(spec)
        sys.modules[name] = pkg
        spec.loader.exec_module(pkg)
    return importlib.import_module(name + ".mla_moe")


def _batch(seed=0, b=2, t=16):
    toks = np.random.default_rng(seed).integers(0, TINY["vocab"], (b, t + 1))
    return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32)}


def _flat(leaf):
    """The program's leaf in the reference's layout (an expert bank's
    ``(blocks, E, R, O)`` as ``(blocks, E*R, O)``)."""
    a = np.asarray(leaf)
    return a.reshape(a.shape[0], -1, a.shape[-1]) if a.ndim == 4 else a


def _worst(got, want) -> float:
    return max(float(np.max(np.abs(_flat(got[n][k]) - np.asarray(want[n][k])))
                   / np.max(np.abs(np.asarray(want[n][k]))))
               for n in want for k in ("basis", "coeff"))


def test_factors_from_the_seed_match_the_reference(ref):
    model = make_mla_moe_transformer(**TINY)
    # eager, as the engine draws them
    prog = model.init_factorized(jax.random.PRNGKey(11))
    want = ref.init_params(TINY, 11)
    assert list(model.specs) == list(ref.layer_specs(TINY)) == list(want)
    assert prog["l1.experts.up"]["coeff"].shape == (
        P * P, TINY["experts_held"], TINY["rank"], TINY["expert_ff_base"])
    assert _worst(prog, want) == 0.0


@pytest.mark.parametrize("width", range(1, P + 1))
def test_logits_loss_and_gradients_match_the_reference(ref, width):
    model = make_mla_moe_transformer(**TINY)
    prog = jax.jit(model.init_factorized)(jax.random.PRNGKey(3))
    rp = {n: {k: _flat(v) for k, v in f.items()} for n, f in prog.items()}
    batch = _batch(width)
    # the least trained blocks need not be the leading ones
    hid = np.arange(width * width)[::-1].copy()
    anc = np.arange(width)
    red = model.reduce(prog, width, hid, anc)
    rred = ref.reduce(rp, hid, anc, ref.layer_specs(TINY))

    def loss(r):
        w = model.compose_all(r, width)
        return _ce(model.forward(w, width, batch), batch["labels"])

    def logits_and_grad(r):
        return (model.forward(model.compose_all(r, width), width, batch),
                jax.value_and_grad(loss)(r))

    def ref_logits_and_grad(r):
        with jax.default_matmul_precision("highest"):
            return (ref.forward(r, width, batch["tokens"], TINY),
                    jax.value_and_grad(ref.loss_fn)(r, width, batch, TINY,
                                                    jnp.float32))

    logits, (l, g) = jax.jit(logits_and_grad)(red)
    want, (rl, rg) = jax.jit(ref_logits_and_grad)(rred)
    # float32 re-association (module docstring): ~1e-6 of the largest
    # entry is seen, a wrong rope column, scale or gate is ~1e-1
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))
    np.testing.assert_allclose(float(l), float(rl), rtol=2e-6)
    assert _worst(g, rg) < 2e-5


def test_expert_shares_sum_to_the_uncut_layer():
    """Shares {0-3} and {4-7} of an 8-expert layer, each routing over all
    8 and computing its own 4, with the shared expert counted once, add
    up to the layer that holds all 8."""
    n, d, f, e, k = 24, 16, 8, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    x = jax.random.normal(keys[0], (n, d))
    router = jax.random.normal(keys[1], (d, e))
    gate, up = (jax.random.normal(kk, (e, d, f)) / 4 for kk in keys[2:4])
    down = jax.random.normal(keys[4], (e, f, d)) / 3
    shared = [jax.random.normal(kk, s) / 4 for kk, s in
              zip(keys[5:8], ((d, f), (d, f), (f, d)))]

    def shared_out(h):
        return (jax.nn.silu(h @ shared[0]) * (h @ shared[1])) @ shared[2]

    whole, st = moe_ffn(x, router, gate, up, down, top_k=k)
    parts = [moe_ffn(x, router, gate[s], up[s], down[s], top_k=k,
                     first_expert=s.start) for s in (slice(0, 4),
                                                     slice(4, 8))]
    np.testing.assert_allclose(
        np.asarray(shared_out(x) + parts[0][0] + parts[1][0]),
        np.asarray(shared_out(x) + whole), rtol=1e-5, atol=1e-5)
    # every pair is routed to one of the two shares, and counted there
    assert int(st["moe.routed_pairs"]) == int(st["moe.pairs_total"]) == n * k
    assert sum(int(p[1]["moe.routed_pairs"]) for p in parts) == n * k
    loads = np.concatenate([np.asarray(p[1]["moe.expert_load"])
                            for p in parts])
    np.testing.assert_array_equal(loads, np.asarray(st["moe.expert_load"]))
    # a token's pairs with absent experts add nothing: by hand
    ids = np.asarray(jax.lax.top_k(jax.nn.softmax(x @ router), k)[1])
    assert int(parts[0][1]["moe.routed_pairs"]) == int(np.sum(ids < 4))


def test_flash_attention_with_its_own_value_dim_and_scale():
    b, t, h, dqk, dv, scale = 2, 40, 3, 12, 5, 0.37
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (b, t, h, dqk))
    k = jax.random.normal(kk, (b, t, h, dqk))
    v = jax.random.normal(kv, (b, t, h, dv))
    out = flash_attention(q[:, :, :, None, :], k, v, causal=True,
                          scale=scale, q_chunk=16, kv_chunk=8)
    assert out.shape == (b, t, h, 1, dv)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out[:, :, :, 0]),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_yarn_frequencies_by_the_formulas():
    """DeepSeek-V2-Lite's rope: dim 64, base 1e4, factor 40, original
    4096, beta 32 / 1, transcribed from DeepseekV2YarnRotaryEmbedding."""
    dim, base, factor, orig = 64, 10000.0, 40.0, 4096
    i = np.arange(0, dim, 2, dtype=np.float64)
    freq_extra = 1.0 / base ** (i / dim)
    freq_inter = freq_extra / factor

    def c(n):
        return dim * np.log(orig / (2 * np.pi * n)) / (2 * np.log(base))

    low, high = np.floor(c(32)), np.ceil(c(1))
    assert (low, high) == (10, 23)
    mask = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = freq_inter * (1 - mask) + freq_extra * mask
    got = yarn_inv_freq(dim, base, factor, orig, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == 1.0 and np.isclose(got[-1], freq_inter[-1], rtol=1e-6)


def test_expert_bank_composes_each_expert_from_its_own_factors():
    spec = CompositionSpec(3, 4, 6, 5, experts=3)
    basis, coeff = init_factors(jax.random.PRNGKey(2), spec)
    assert basis.shape == (3, 6, 4) and coeff.shape == (9, 3, 4, 5)
    ids = np.array([0, 4, 8, 2])
    red = coeff[ids]
    w = compose(basis, red, 2, spec, backend="einsum")
    assert w.shape == spec.weight_shape(2) == (3, 12, 10)
    one = CompositionSpec(3, 4, 6, 5)
    for e in range(3):
        np.testing.assert_allclose(
            np.asarray(w[e]),
            np.asarray(compose(basis[e:e + 1], red[:, e], 2, one,
                               backend="einsum")[0]), rtol=1e-6)
    assert spec.params_factorized(2) == 3 * (6 * 4 + 4 * 4 * 5)


def test_block_merge_of_a_bank_is_the_merge_of_its_experts():
    """Eq. 5 on a ``(blocks, E, R, O)`` coefficient equals Eq. 5 on each
    expert's ``(blocks, R, O)``, host loop and stacked form alike."""
    rng = np.random.default_rng(0)
    prev = jnp.asarray(rng.normal(size=(4, 2, 3, 5)), jnp.float32)
    ids = [np.array([0, 1]), np.array([1, 3])]
    blocks = [jnp.asarray(rng.normal(size=(2, 2, 3, 5)), jnp.float32)
              for _ in ids]
    got = aggregation.aggregate_coefficient(prev, blocks, ids)
    dense, mask = zip(*(aggregation.scatter_contribution(b, jnp.asarray(i), 4)
                        for b, i in zip(blocks, ids)))
    stacked = aggregation.masked_block_merge(jnp.stack(dense),
                                             jnp.stack(mask), prev)
    for e in range(2):
        want = aggregation.aggregate_coefficient(
            prev[:, e], [b[:, e] for b in blocks], ids)
        np.testing.assert_array_equal(np.asarray(got[:, e]), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(stacked[:, e]),
                                      np.asarray(want))


def _round(seed):
    """One Heroes round on the tiny model through ``build_runner``: the
    sequential trainer, the collective merge, telemetry on."""
    model = make_mla_moe_transformer(**TINY)
    rng = np.random.default_rng(seed)
    x = rng.integers(0, TINY["vocab"], (48, 16)).astype(np.int32)
    y = rng.integers(0, TINY["vocab"], (48, 16)).astype(np.int32)
    parts = np.array_split(np.arange(48), 6)
    test = {"tokens": x[:4], "labels": y[:4]}
    cfg = FLConfig(num_clients=6, clients_per_round=3, lr=0.05,
                   batch_size=4, tau_fixed=2, tau_max=2, eval_every=1,
                   trainer="sequential", agg_backend="collective",
                   forward_impl="materialize", seed=seed,
                   telemetry="memory")
    eng = build_runner("heroes", model, [x[p] for p in parts],
                       [y[p] for p in parts], test, cfg=cfg, seed=0)
    eng.run_round()
    spans = [e for e in eng.obs.sinks[0].events
             if e.get("name") == "trainer.local_train"]
    params = jax.device_get(eng.state.params)
    eng.close()
    return params, spans


def test_one_heroes_round_is_finite_and_deterministic():
    a, spans = _round(4)
    b, _ = _round(4)
    leaves_a = jax.tree_util.tree_leaves(a)
    assert all(np.isfinite(v).all() for v in leaves_a)
    for u, v in zip(leaves_a, jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(u, v)
    assert a["l1.experts.gate"]["coeff"].shape == (
        P * P, TINY["experts_held"], TINY["rank"], TINY["expert_ff_base"])
    # the forward's counts ride on every client's span: per forward,
    # top_k pairs a token over the one expert layer, some of them held
    tokens = 4 * 16
    assert len(spans) == 3
    for s in spans:
        at, tau = s["attrs"], s["attrs"]["tau"]
        assert at["moe.pairs_total"] == (tau + 6) * tokens * TINY["top_k"]
        assert at["backward.moe.pairs_total"] == (
            (tau + 4) * tokens * TINY["top_k"])
        assert 0 < at["moe.routed_pairs"] < at["moe.pairs_total"]
        assert 0 < at["moe.expert_load_max"] <= at["moe.routed_pairs"]


def test_serving_refuses_the_latent_model():
    model = make_mla_moe_transformer(**TINY)
    params = model.init_factorized(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="not built by make_transformer"):
        serving_weights(model, params, 1)
    with pytest.raises(ValueError, match="not built by make_transformer"):
        greedy_decode(model, {}, 1, np.zeros((1, 2), np.int32), 1)
