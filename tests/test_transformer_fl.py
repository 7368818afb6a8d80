"""Federated composed transformer + the refactored model layer.

Covers the ComposedLayer/registry refactor and the transformer def:

* registry round-trip (lookup, modality gating, duplicate/unknown
  errors) and ``build_setup`` resolving through it;
* ComposedLayer re-expression is *identical* — cnn/rnn forwards equal
  an inline legacy implementation bitwise, factorized and dense (the
  golden engine-history fixtures in test_engine.py pin the end-to-end
  claim; this pins the layer graphs directly);
* transformer grad-parity matrix (materialize vs rank_space vs auto)
  across widths 1..3, same tolerances as the cnn/resnet/rnn matrix;
* the transformer trains through every registered scheme x both round
  modes with finite metrics and nonzero Heroes block coverage;
* serving: greedy decode through the Pallas kernel matches the inline
  XLA oracle and the full-sequence training forward;
* the rank-aware virtual clock (FLConfig.clock_model) — default stays
  bitwise, "rank_aware" charges the cheaper rank-space FLOPs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl import FLConfig, build_runner, build_setup, run_scheme
from repro.fl.client import client_step, data_batch
from repro.fl.models import (MODEL_REGISTRY, ComposedLayer, CompositionSpec,
                             LayerHint, _apply_conv, _apply_dense,
                             _apply_embed, _materialized, get_model, make_cnn,
                             make_rnn, register_model)
from repro.fl.transformer import (arch_of, greedy_decode, make_transformer,
                                  serving_weights)


def _reduced(model, width, key=jax.random.PRNGKey(0)):
    params = model.init_factorized(key)
    sq = next(s for s in model.specs.values() if s.mode == "square")
    return model.reduce(params, width,
                        np.arange(sq.blocks_for_width(width)),
                        np.arange(width))


def _text_batch(key, n=8, t=32, vocab=64):
    return {"tokens": jax.random.randint(key, (n, t), 0, vocab),
            "labels": jax.random.randint(key, (n, t), 0, vocab)}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_roundtrip():
    for name, modality in (("cnn", "image"), ("resnet", "image"),
                           ("rnn", "text"), ("transformer", "text")):
        entry = get_model(name)
        assert entry.name == name and entry.modality == modality
    with pytest.raises(ValueError, match="unknown model"):
        get_model("vit")
    with pytest.raises(ValueError, match="already registered"):
        register_model("cnn")(lambda *a, **k: None)
    assert "vit" not in MODEL_REGISTRY


def test_build_setup_resolves_through_registry():
    model, _, _, _ = build_setup("synthetic_text", "transformer",
                                 num_clients=4, max_width=3, seed=0)
    # memoized factory: the registry hands back the identical instance
    assert model is make_transformer(max_width=3, vocab=64)
    # modality defaults preserved: text -> rnn, image -> cnn
    m_text, _, _, _ = build_setup("synthetic_text", None, num_clients=4,
                                  max_width=3, seed=0)
    assert m_text.name == "rnn"
    m_img, _, _, _ = build_setup("synthetic_image", None, num_clients=4,
                                 max_width=3, seed=0)
    assert m_img.name == "cnn"
    with pytest.raises(ValueError, match="expects image data"):
        build_setup("synthetic_text", "cnn", num_clients=4, seed=0)
    with pytest.raises(ValueError, match="unknown model"):
        build_setup("synthetic_image", "vit", num_clients=4, seed=0)


def test_composed_layer_validation():
    sq = CompositionSpec(3, 8, 4, 4, ksq=1)
    with pytest.raises(ValueError, match="unknown layer kind"):
        ComposedLayer("l", sq, kind="attention")
    with pytest.raises(ValueError, match="requires kind='conv'"):
        ComposedLayer("l", CompositionSpec(3, 8, 4, 4, ksq=9), kind="dense")
    with pytest.raises(ValueError, match="grow_out"):
        ComposedLayer("l", sq, kind="embed")


def test_from_layers_projects_specs_and_hints():
    for model in (make_cnn(), make_rnn(), make_transformer()):
        assert model.layers is not None
        assert list(model.specs) == list(model.layers)
        for name, layer in model.layers.items():
            assert model.specs[name] is layer.spec
            assert model.hints[name] is layer.hint


def test_input_key_drives_batch_assembly():
    x = np.arange(12).reshape(3, 4)
    y = np.arange(3)
    for model, key in ((make_cnn(), "x"), (make_rnn(), "tokens"),
                       (make_transformer(), "tokens")):
        assert model.input_key == key
        assert set(data_batch(model, x, y, np.array([0, 2]))) == {
            key, "labels"}


# ---------------------------------------------------------------------------
# ComposedLayer re-expression is the identical graph (bitwise)
# ---------------------------------------------------------------------------


def test_cnn_forward_bitwise_vs_inline_legacy():
    model = make_cnn()
    specs = model.specs
    batch = {"x": jax.random.normal(jax.random.PRNGKey(5), (4, 8, 8, 3))}

    def legacy_forward(w, width):
        x = batch["x"]
        x = jax.nn.relu(_apply_conv(w["conv1"], x, width, specs["conv1"]))
        x = jax.nn.relu(_apply_conv(w["conv2"], x, width, specs["conv2"],
                                    stride=2))
        x = jax.nn.relu(_apply_conv(w["conv3"], x, width, specs["conv3"],
                                    stride=2))
        x = jnp.mean(x, axis=(1, 2))
        return _apply_dense(w["fc"], x, width, specs["fc"])

    for width in (1, 3):
        red = _reduced(model, width)
        for impl in ("materialize", "rank_space"):
            w = model.prepare_weights(red, width, batch, impl)
            got = np.asarray(model.forward(w, width, batch))
            want = np.asarray(legacy_forward(w, width))
            assert np.array_equal(got, want)


def test_rnn_forward_bitwise_vs_inline_legacy():
    from repro.core.composition import apply_factors

    model = make_rnn()
    specs = model.specs
    batch = _text_batch(jax.random.PRNGKey(6))

    def legacy_forward(w, width):
        tokens = batch["tokens"]
        emb = _apply_embed(w["embed"], tokens, width, specs["embed"])
        wh = _materialized(w["wh"], width, specs["wh"])[0]
        if isinstance(w["wx"], dict):
            xp = apply_factors(emb, w["wx"]["basis"], w["wx"]["coeff"],
                               width, specs["wx"], "dense")

            def step(h, x):
                h = jnp.tanh(x + h @ wh)
                return h, h

            xs = jnp.moveaxis(xp, 1, 0)
        else:
            wx = w["wx"][0]

            def step(h, x):
                h = jnp.tanh(x @ wx + h @ wh)
                return h, h

            xs = jnp.moveaxis(emb, 1, 0)
        h0 = jnp.zeros((emb.shape[0], wh.shape[0]), emb.dtype)
        _, hs = jax.lax.scan(step, h0, xs)
        hs = jnp.moveaxis(hs, 0, 1)
        return _apply_dense(w["out"], hs, width, specs["out"])

    for width in (1, 3):
        red = _reduced(model, width)
        for impl in ("materialize", "rank_space"):
            w = model.prepare_weights(red, width, batch, impl)
            got = np.asarray(model.forward(w, width, batch))
            want = np.asarray(legacy_forward(w, width))
            assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# transformer grad-parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [1, 2, 3])
@pytest.mark.parametrize("impl", ["rank_space", "auto"])
def test_transformer_gradient_parity(width, impl):
    model = make_transformer()
    red = _reduced(model, width)
    batch = _text_batch(jax.random.PRNGKey(3))
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
    pa, pb = red, red
    for i in range(3):
        b = _text_batch(jax.random.PRNGKey(10 + i))
        pa, _ = step_mat(pa, b, 0.05)
        pb, _ = step_rank(pb, b, 0.05)
    for a, b in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3)


# ---------------------------------------------------------------------------
# the transformer through the engine: every scheme x both round modes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def text_setup():
    return build_setup("synthetic_text", "transformer", num_clients=8,
                       max_width=3, seed=0)


@pytest.mark.parametrize("scheme", ["fedavg", "adp", "heterofl", "flanc",
                                    "heroes"])
@pytest.mark.parametrize("mode", ["sync", "semi_async"])
def test_transformer_trains_through_engine(text_setup, scheme, mode):
    model, px, py, tb = text_setup
    cfg = FLConfig(num_clients=8, clients_per_round=3, batch_size=8,
                   tau_fixed=2, eval_every=2, round_mode=mode, seed=0)
    hist = run_scheme(scheme, model, px, py, tb, 2, cfg=cfg, seed=0)
    assert len(hist) == 2
    assert np.isfinite(hist[-1].wall_time)
    assert hist[-1].accuracy is not None and np.isfinite(hist[-1].accuracy)


def test_transformer_heroes_coverage_nonzero(text_setup):
    model, px, py, tb = text_setup
    cfg = FLConfig(num_clients=8, clients_per_round=4, batch_size=8,
                   tau_fixed=2, eval_every=10_000, seed=0)
    with build_runner("heroes", model, px, py, tb, cfg=cfg, seed=0) as eng:
        eng.run(3)
        sched = eng.state.sched
    assert np.count_nonzero(sched.counters) == sched.counters.size
    assert np.count_nonzero(sched.anchored) == sched.anchored.size


# ---------------------------------------------------------------------------
# serving: compose once, decode through the Pallas kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [1, 2])
def test_greedy_decode_pallas_matches_xla_and_full_forward(width):
    model = make_transformer()
    params = model.init_factorized(jax.random.PRNGKey(0))
    weights = serving_weights(model, params, width)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 6),
                                           0, model.num_classes))
    steps = 5
    toks_p, logits_p = greedy_decode(model, weights, width, prompt, steps,
                                     backend="pallas")
    toks_x, logits_x = greedy_decode(model, weights, width, prompt, steps,
                                     backend="xla")
    assert toks_p.shape == (2, steps)
    assert np.array_equal(toks_p, toks_x)
    np.testing.assert_allclose(logits_p, logits_x, atol=1e-4, rtol=1e-4)
    # greedy consistency: the full-sequence training forward (flash
    # attention path) predicts exactly the generated continuation
    seq = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(toks_x)], axis=1)
    full = model.forward(weights, width, {"tokens": seq})
    pred = np.argmax(np.asarray(full), -1)[:, prompt.shape[1] - 1:-1]
    assert np.array_equal(pred, toks_x)


def test_serving_weights_dense_path():
    model = make_transformer()
    dense = model.init_dense(jax.random.PRNGKey(2))
    w = serving_weights(model, dense, 2, factorized=False)
    arch = arch_of(model)
    assert w["embed"].shape == (1, arch.vocab, 2 * arch.d_base)
    toks, _ = greedy_decode(model, w, 2, np.zeros((1, 2), np.int32), 3,
                            backend="xla")
    assert toks.shape == (1, 3)


def test_arch_of_rejects_foreign_models():
    with pytest.raises(ValueError, match="not built by make_transformer"):
        arch_of(make_cnn())


# ---------------------------------------------------------------------------
# rank-aware virtual clock (FLConfig.clock_model)
# ---------------------------------------------------------------------------


def test_clock_model_default_is_bitwise(text_setup):
    model, px, py, tb = text_setup
    kw = dict(num_clients=8, clients_per_round=3, batch_size=8, tau_fixed=2,
              eval_every=2, seed=0)
    h_def = run_scheme("heroes", model, px, py, tb, 2,
                       cfg=FLConfig(**kw), seed=0)
    h_dense = run_scheme("heroes", model, px, py, tb, 2,
                         cfg=FLConfig(clock_model="dense", **kw), seed=0)
    assert [vars(a) for a in h_def] == [vars(b) for b in h_dense]


def test_clock_model_rank_aware_charges_rank_flops(text_setup):
    model, px, py, tb = text_setup
    kw = dict(num_clients=8, clients_per_round=3, batch_size=8, tau_fixed=2,
              eval_every=10_000, seed=0)
    with build_runner("heroes", model, px, py, tb,
                      cfg=FLConfig(clock_model="rank_aware", **kw),
                      seed=0) as eng:
        for p in (1, 2, 3):
            rank = eng.flops_per_iter(p)
            dense = model.flops_per_sample(p) * eng.cfg.batch_size
            assert np.isfinite(rank) and rank > 0
            # the transformer's projections all win in rank space here
            assert rank < dense
        hist = eng.run(2)
    assert np.isfinite(hist[-1].wall_time)
    # dense schemes keep the dense clock regardless of the knob
    with build_runner("fedavg", model, px, py, tb,
                      cfg=FLConfig(clock_model="rank_aware", **kw),
                      seed=0) as eng:
        assert eng.flops_per_iter(3) == model.flops_per_sample(3) * 8


def test_clock_model_validation(text_setup):
    model, px, py, tb = text_setup
    with pytest.raises(ValueError, match="unknown clock_model"):
        build_runner("heroes", model, px, py, tb,
                     cfg=FLConfig(num_clients=8, clock_model="fast"), seed=0)
