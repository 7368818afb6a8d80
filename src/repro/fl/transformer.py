"""Federated composed transformer: the LLM stack as an ``FLModelDef``.

Heroes' neural composition *is* low-rank adaptation — every weight is a
sum of shared rank-R basis tensors and per-width coefficient blocks — so
a decoder-only transformer maps onto :class:`~repro.fl.models.ComposedLayer`
directly (FedHM's factorized-LM premise, on the Heroes block structure):

  =================  =========  =======================================
  layer              spec mode  shape at width p
  =================  =========  =======================================
  embed              grow_out   (vocab, p*d_base) — vocab-anchored
  l{i}.wq/wk/wv/wo   square     (p*d_base, p*d_base), p^2 blocks
  l{i}.up            square     (p*d_base, p*ff_base)
  l{i}.down          square     (p*ff_base, p*d_base)
  head               grow_in    (p*d_base, vocab) — vocab-anchored
  =================  =========  =======================================

Width p scales the model dimension (``d_p = p * d_base``) by scaling the
*head count* (``H_p = p * heads_base``) at fixed head_dim, so RoPE angles
and the attention kernels are width-independent.  Attention runs through
the existing flash kernel (:func:`repro.models.attention.flash_attention`,
streaming softmax, differentiable); norms are parameter-free RMSNorm so
the entire parameter set lives in composition specs and every FL scheme
(dense slicing included) applies unchanged.

:func:`make_mla_moe_transformer` is the second factory on the same
layers: DeepSeek-V2's block (latent attention with an anchored latent,
YaRN rope, a dense first layer, then shared plus routed SwiGLU experts
of which this chip holds one expert-parallel share, computed by a
grouped matmul).  Its table is in docs/TRANSFORMERS.md.

Serving closes the loop production-style: :func:`serving_weights`
composes the per-width dense weights ONCE, then :func:`greedy_decode`
runs token-by-token greedy decode with a per-layer KV cache through the
Pallas decode kernel (:func:`repro.kernels.decode_attention.
decode_attention_pallas`) — benchmarked as tokens/s by
``benchmarks/bench_transformer.py``.  See docs/TRANSFORMERS.md.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.models import (ComposedLayer, CompositionSpec, FLModelDef,
                             LayerHint, register_model)
from repro.kernels.compose import default_interpret
from repro.kernels.decode_attention import decode_attention_pallas
from repro.models.attention import apply_rotary, flash_attention, rope_angles

Array = jax.Array

ROPE_THETA = 10000.0
RMS_EPS = 1e-6


class TransformerArch(NamedTuple):
    """Static geometry the decode path needs back out of a model def."""

    d_base: int
    heads_base: int
    head_dim: int
    n_layers: int
    ff_base: int
    vocab: int
    seq_ref: int


# keyed by model identity (FLModelDef hashes by identity and the
# factories are memoized, so instances persist for the process lifetime)
_ARCH: Dict[FLModelDef, TransformerArch] = {}


def arch_of(model: FLModelDef) -> TransformerArch:
    try:
        return _ARCH[model]
    except KeyError:
        raise ValueError(
            f"model {model.name!r} was not built by make_transformer") from None


def _rms(x: Array) -> Array:
    """Parameter-free RMSNorm (keeps all params inside composition specs)."""
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + RMS_EPS)


@functools.lru_cache(maxsize=None)
def make_transformer(max_width: int = 3, d_base: int = 16,
                     heads_base: int = 2, n_layers: int = 2,
                     ff_mult: int = 2, rank: int = 8, vocab: int = 64,
                     seq_ref: int = 32) -> FLModelDef:
    """Decoder-only transformer as composed rank-R blocks.

    ``head_dim = d_base // heads_base`` must be even (RoPE rotates
    half-pairs); width scales heads, not head_dim.
    """
    if d_base % heads_base != 0:
        raise ValueError(f"d_base={d_base} not divisible by heads_base={heads_base}")
    head_dim = d_base // heads_base
    if head_dim % 2 != 0:
        raise ValueError(f"head_dim={head_dim} must be even for RoPE")
    ff_base = ff_mult * d_base

    seq_len = lambda s: s[1]  # noqa: E731 — tokens (B, T)
    proj_hint = LayerHint(seq_ref, seq_len)

    layers: Dict[str, ComposedLayer] = {
        "embed": ComposedLayer(
            "embed",
            CompositionSpec(max_width, rank, vocab, d_base, ksq=1,
                            mode="grow_out"),
            kind="embed",
            hint=LayerHint(seq_ref, seq_len, dense_apply_free=True,
                           basis_gather=True)),
    }
    for i in range(n_layers):
        for proj in ("wq", "wk", "wv", "wo"):
            layers[f"l{i}.{proj}"] = ComposedLayer(
                f"l{i}.{proj}",
                CompositionSpec(max_width, rank, d_base, d_base, ksq=1),
                hint=proj_hint)
        layers[f"l{i}.up"] = ComposedLayer(
            f"l{i}.up",
            CompositionSpec(max_width, rank, d_base, ff_base, ksq=1),
            hint=proj_hint)
        layers[f"l{i}.down"] = ComposedLayer(
            f"l{i}.down",
            CompositionSpec(max_width, rank, ff_base, d_base, ksq=1),
            hint=proj_hint)
    layers["head"] = ComposedLayer(
        "head",
        CompositionSpec(max_width, rank, d_base, vocab, ksq=1,
                        mode="grow_in"),
        hint=proj_hint)

    def forward(w: Dict[str, Any], width: int, batch) -> Array:
        tokens = batch["tokens"]  # (B, T)
        B, T = tokens.shape
        heads = width * heads_base
        x = layers["embed"].apply(w["embed"], tokens, width)  # (B,T,pD)
        pos = jnp.arange(T, dtype=jnp.int32)[None, :]
        cos, sin = rope_angles(pos, head_dim, ROPE_THETA)
        for i in range(n_layers):
            h = _rms(x)
            q = layers[f"l{i}.wq"].apply(w[f"l{i}.wq"], h, width)
            k = layers[f"l{i}.wk"].apply(w[f"l{i}.wk"], h, width)
            v = layers[f"l{i}.wv"].apply(w[f"l{i}.wv"], h, width)
            q = apply_rotary(q.reshape(B, T, heads, head_dim), cos, sin)
            k = apply_rotary(k.reshape(B, T, heads, head_dim), cos, sin)
            v = v.reshape(B, T, heads, head_dim)
            # flash layout (B, S, KV, G, D) with one query head per KV head
            att = flash_attention(q[:, :, :, None, :], k, v, causal=True)
            att = att.reshape(B, T, heads * head_dim)
            x = x + layers[f"l{i}.wo"].apply(w[f"l{i}.wo"], att, width)
            h2 = _rms(x)
            u = jax.nn.gelu(layers[f"l{i}.up"].apply(w[f"l{i}.up"], h2, width))
            x = x + layers[f"l{i}.down"].apply(w[f"l{i}.down"], u, width)
        x = _rms(x)
        return layers["head"].apply(w["head"], x, width)  # (B,T,V)

    def flops(width: int, seq: int = seq_ref) -> int:
        p = width
        d, ff = p * d_base, p * ff_base
        # per token: 4 square attn projections + QK^T/AV over the
        # sequence + MLP up/down + LM head (embedding is a gather)
        per_tok = n_layers * (8 * d * d + 4 * seq * d + 4 * d * ff)
        per_tok += 2 * d * vocab
        return 3 * per_tok * seq

    model = FLModelDef.from_layers("transformer", layers, forward, flops,
                                   vocab, input_key="tokens")
    _ARCH[model] = TransformerArch(d_base, heads_base, head_dim, n_layers,
                                   ff_base, vocab, seq_ref)
    return model


@register_model("transformer", modality="text")
def _build_transformer(max_width: int, meta: Dict[str, Any], **kw) -> FLModelDef:
    return make_transformer(max_width=max_width, vocab=meta["vocab"], **kw)


# ---------------------------------------------------------------------------
# latent attention + sparse experts (DeepSeek-V2's block)
# ---------------------------------------------------------------------------

MLA_MOE = "mla_moe"


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V2 ``yarn_get_mscale``)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """Rope frequencies ``(dim/2,)`` of DeepSeek-V2's YaRN scaling
    (``DeepseekV2YarnRotaryEmbedding``): the extrapolated frequencies
    ``base^(-2i/dim)`` below the correction range, those divided by
    ``factor`` above it, and a linear ramp between."""
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor

    def corr(rotations: float) -> float:
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def moe_ffn(x: Array, router: Array, gate: Array, up: Array, down: Array,
            *, top_k: int, first_expert: int = 0):
    """The routed experts that one expert-parallel share holds.

    ``x (N, D)`` tokens; ``router (D, E)`` over all ``E`` experts;
    ``gate``/``up`` ``(E_held, D, F)`` and ``down`` ``(E_held, F, D)`` the
    composed weights of experts ``first_expert .. first_expert+E_held-1``.
    Softmax over the ``E`` logits (float32, ``highest``), greedy top-k,
    gates not renormalised; a token's pairs with an absent expert add
    nothing.  Dropless: the ``N*top_k`` pairs are sorted by expert, every
    held pair goes through one grouped SwiGLU (``jax.lax.ragged_dot``),
    the rows past the held groups are zeroed both ways, and the results
    are combined back per token with their gates.

    Returns ``((N, D), stats)``: ``moe.routed_pairs`` (pairs computed
    here), ``moe.pairs_total`` (``N*top_k``) and ``moe.expert_load``
    (pairs per held expert).
    """
    n, d = x.shape
    held = gate.shape[0]
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    local = ids.reshape(-1) - first_expert
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held)  # absent experts sort last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    routed = jnp.sum(sizes)
    valid = (jnp.arange(n * top_k) < routed)[:, None]
    rows = jnp.where(valid, jnp.take(x, order // top_k, axis=0), 0)
    with jax.named_scope("moe.experts"):
        h = (jax.nn.silu(jax.lax.ragged_dot(rows, gate, sizes))
             * jax.lax.ragged_dot(rows, up, sizes))
        y = jnp.where(valid, jax.lax.ragged_dot(h, down, sizes), 0)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * top_k, dtype=order.dtype))
    weight = jnp.where(here, scores.reshape(-1), 0.0).astype(y.dtype)
    out = jnp.sum((jnp.take(y, back, axis=0) * weight[:, None]).reshape(
        n, top_k, d), axis=1)
    stats = {"moe.routed_pairs": routed,
             "moe.pairs_total": jnp.int32(n * top_k),
             "moe.expert_load": sizes}
    return out, stats


@functools.lru_cache(maxsize=None)
def make_mla_moe_transformer(
        max_width: int = 3, d_base: int = 16, heads_base: int = 2,
        n_layers: int = 2, first_dense: int = 1, qk_nope_head_dim: int = 8,
        qk_rope_head_dim: int = 4, v_head_dim: int = 8,
        kv_lora_rank: int = 16, dense_ff_base: int = 24,
        shared_ff_base: int = 12, expert_ff_base: int = 8,
        n_experts: int = 8, experts_held: int = 4, top_k: int = 2,
        rank: int = 8, vocab: int = 64, seq_ref: int = 32,
        rope_theta: float = 10000.0, rope_factor: float = 40.0,
        rope_original: int = 4096, beta_fast: float = 32.0,
        beta_slow: float = 1.0, mscale: float = 0.707,
        mscale_all_dim: float = 0.707) -> FLModelDef:
    """DeepSeek-V2's decoder block as composed rank-R layers.

    Width ``p`` scales the hidden size (``p*d_base``), the heads
    (``p*heads_base``) and the dense, shared and expert widths; the head
    dims, the ``kv_lora_rank`` latent, the shared rope key, the router's
    ``n_experts`` outputs, ``top_k`` and the vocabulary stay fixed.  The
    latent is anchored (``wkv_a`` grows its input, ``wkv_b`` its output,
    the modes of the embedding and the head), as is the router.  Each
    expert layer holds experts ``0 .. experts_held-1`` of the
    ``n_experts``: expert-parallel rank 0's share.  Layers below
    ``first_dense`` have a dense SwiGLU, the rest shared plus routed
    experts.  Norms are parameter-free RMSNorm (the latent's too); rope
    uses the half-split layout.
    """
    if not 0 < experts_held <= n_experts:
        raise ValueError(f"cannot hold {experts_held} of {n_experts} experts")
    if qk_rope_head_dim % 2:
        raise ValueError(f"qk_rope_head_dim={qk_rope_head_dim} must be even")
    qk_dim = qk_nope_head_dim + qk_rope_head_dim
    latent = kv_lora_rank
    inv_freq = yarn_inv_freq(qk_rope_head_dim, rope_theta, rope_factor,
                             rope_original, beta_fast, beta_slow)
    # cos/sin carry yarn_mscale(factor, mscale) / (..., mscale_all_dim);
    # the softmax scale carries the second factor squared
    rope_scale = (yarn_mscale(rope_factor, mscale)
                  / yarn_mscale(rope_factor, mscale_all_dim))
    softmax_scale = qk_dim ** -0.5 * yarn_mscale(rope_factor,
                                                 mscale_all_dim) ** 2

    seq_len = lambda s: s[1]  # noqa: E731 — tokens (B, T)
    proj = LayerHint(seq_ref, seq_len)
    # a held expert applies to top_k * held / n_experts of the tokens
    share = top_k * experts_held / n_experts
    expert_hint = LayerHint(max(int(seq_ref * share), 1),
                            lambda s: int(s[1] * share), rank_capable=False)

    def dense(name, i, o, mode="square", hint=proj, experts=1):
        return ComposedLayer(
            name, CompositionSpec(max_width, rank, i, o, ksq=1, mode=mode,
                                  experts=experts),
            kind="experts" if experts > 1 else "dense", hint=hint)

    def swiglu_layers(prefix, ff, experts=1, hint=proj):
        return {f"{prefix}.{n}": dense(f"{prefix}.{n}", i, o, hint=hint,
                                       experts=experts)
                for n, i, o in (("gate", d_base, ff), ("up", d_base, ff),
                                ("down", ff, d_base))}

    layers: Dict[str, ComposedLayer] = {
        "embed": ComposedLayer(
            "embed",
            CompositionSpec(max_width, rank, vocab, d_base, ksq=1,
                            mode="grow_out"),
            kind="embed",
            hint=LayerHint(seq_ref, seq_len, dense_apply_free=True,
                           basis_gather=True)),
    }
    for i in range(n_layers):
        l = f"l{i}"
        layers[f"{l}.wq"] = dense(f"{l}.wq", d_base, heads_base * qk_dim)
        layers[f"{l}.wkv_a"] = dense(f"{l}.wkv_a", d_base,
                                     latent + qk_rope_head_dim, "grow_in")
        layers[f"{l}.wkv_b"] = dense(
            f"{l}.wkv_b", latent,
            heads_base * (qk_nope_head_dim + v_head_dim), "grow_out")
        layers[f"{l}.wo"] = dense(f"{l}.wo", heads_base * v_head_dim, d_base)
        if i < first_dense:
            layers.update(swiglu_layers(l, dense_ff_base))
        else:
            layers[f"{l}.router"] = dense(f"{l}.router", d_base, n_experts,
                                          "grow_in")
            layers.update(swiglu_layers(f"{l}.shared", shared_ff_base))
            layers.update(swiglu_layers(f"{l}.experts", expert_ff_base,
                                        experts_held, expert_hint))
    layers["head"] = dense("head", d_base, vocab, "grow_in")

    def apply(w, name, x, width):
        return layers[name].apply(w[name], x, width)

    def swiglu(w, prefix, x, width):
        h = (jax.nn.silu(apply(w, f"{prefix}.gate", x, width))
             * apply(w, f"{prefix}.up", x, width))
        return apply(w, f"{prefix}.down", h, width)

    def mla(w, l, h, width, cos, sin):
        B, T, _ = h.shape
        heads = width * heads_base
        nope, rope = qk_nope_head_dim, qk_rope_head_dim
        q = apply(w, f"{l}.wq", h, width).reshape(B, T, heads, qk_dim)
        q = jnp.concatenate(
            [q[..., :nope], apply_rotary(q[..., nope:], cos, sin)], -1)
        kv_a = apply(w, f"{l}.wkv_a", h, width)  # (B, T, latent + rope)
        k_rope = apply_rotary(kv_a[:, :, None, latent:], cos, sin)
        kv = apply(w, f"{l}.wkv_b", _rms(kv_a[..., :latent]), width)
        kv = kv.reshape(B, T, heads, nope + v_head_dim)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, T, heads, rope))],
            -1)
        # flash layout (B, S, KV, G, D), one query head per KV head
        att = flash_attention(q[:, :, :, None, :], k, kv[..., nope:],
                              causal=True, scale=softmax_scale)
        return apply(w, f"{l}.wo", att.reshape(B, T, heads * v_head_dim),
                     width)

    def forward_stats(w: Dict[str, Any], width: int, batch):
        tokens = batch["tokens"]  # (B, T)
        B, T = tokens.shape
        x = apply(w, "embed", tokens, width)  # (B, T, pD)
        ang = jnp.arange(T, dtype=jnp.float32)[None, :, None] * inv_freq
        cos, sin = jnp.cos(ang) * rope_scale, jnp.sin(ang) * rope_scale
        stats = None
        for i in range(n_layers):
            l = f"l{i}"
            with jax.named_scope("mla"):
                x = x + mla(w, l, _rms(x), width, cos, sin)
            h = _rms(x)
            if i < first_dense:
                x = x + swiglu(w, l, h, width)
                continue
            bank = [layers[f"{l}.experts.{n}"].materialized(
                w[f"{l}.experts.{n}"], width) for n in ("gate", "up", "down")]
            routed, st = moe_ffn(
                h.reshape(B * T, -1),
                layers[f"{l}.router"].materialized(w[f"{l}.router"],
                                                   width)[0],
                *bank, top_k=top_k)
            x = x + swiglu(w, f"{l}.shared", h, width) + routed.reshape(
                B, T, -1)
            stats = st if stats is None else jax.tree_util.tree_map(
                jnp.add, stats, st)
        return apply(w, "head", _rms(x), width), stats or {}

    def forward(w, width, batch):
        return forward_stats(w, width, batch)[0]

    def flops(width: int, seq: int = seq_ref) -> int:
        d, heads = width * d_base, width * heads_base
        ff = lambda base: width * base  # noqa: E731
        attn = 2 * (d * heads * qk_dim + d * (latent + qk_rope_head_dim)
                    + latent * heads * (qk_nope_head_dim + v_head_dim)
                    + heads * v_head_dim * d)
        attn += 2 * seq * heads * (qk_dim + v_head_dim)
        per_tok = n_layers * attn + 2 * d * vocab
        per_tok += first_dense * 6 * d * ff(dense_ff_base)
        per_tok += (n_layers - first_dense) * (
            2 * d * n_experts + 6 * d * ff(shared_ff_base)
            + 6 * d * ff(expert_ff_base) * share)
        return int(3 * per_tok * seq)

    return FLModelDef.from_layers(MLA_MOE, layers, forward, flops, vocab,
                                  input_key="tokens",
                                  forward_stats=forward_stats)


@register_model(MLA_MOE, modality="text")
def _build_mla_moe(max_width: int, meta: Dict[str, Any], **kw) -> FLModelDef:
    return make_mla_moe_transformer(max_width=max_width, vocab=meta["vocab"],
                                    **kw)


# ---------------------------------------------------------------------------
# serving: compose once, decode through the Pallas kernel
# ---------------------------------------------------------------------------


def serving_weights(model: FLModelDef, params, width: int, *,
                    factorized: bool = True) -> Dict[str, Array]:
    """Per-width dense weights for serving, composed ONCE.

    ``factorized=True`` takes server-side (basis, coeff) params — the
    Heroes/Flanc state — reduces the width-p leading blocks (the same
    ids the aggregators evaluate with) and composes every layer.
    ``factorized=False`` takes dense params and slices the width-p
    sub-model (HeteroFL-style).
    """
    arch_of(model)  # decoding is written for make_transformer's block
    if not factorized:
        return model.slice_dense(params, width)
    square = next(s for s in model.specs.values() if s.mode == "square")
    hidden = np.arange(square.blocks_for_width(width))
    anchored = np.arange(min(width, square.max_width))
    reduced = model.reduce(params, width, hidden, anchored)
    return model.compose_all(reduced, width)


@functools.partial(jax.jit,
                   static_argnames=("model", "width", "backend", "interpret"))
def _decode_step(weights, ck, cv, tok, t, *, model: FLModelDef, width: int,
                 backend: str, interpret: bool):
    """One greedy decode step.

    tok (B,) int32, t scalar int32 (tokens already cached); caches are
    per-layer (B*H, Smax, head_dim) in the Pallas kernel's layout.
    Returns (next_token (B,), logits (B, V), new_ck, new_cv).
    """
    arch = _ARCH[model]
    B = tok.shape[0]
    heads = width * arch.heads_base
    hd = arch.head_dim
    x = jnp.take(weights["embed"][0], tok, axis=0)[:, None, :]  # (B,1,pD)
    pos = jnp.full((1, 1), t, dtype=jnp.int32)
    cos, sin = rope_angles(pos, hd, ROPE_THETA)
    new_ck, new_cv = [], []
    for i in range(arch.n_layers):
        h = _rms(x)
        q = (h @ weights[f"l{i}.wq"][0]).reshape(B, 1, heads, hd)
        k = (h @ weights[f"l{i}.wk"][0]).reshape(B, 1, heads, hd)
        v = (h @ weights[f"l{i}.wv"][0]).reshape(B, 1, heads, hd)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
        # cache layout (B*H, S, D): batch-of-heads rows, matching the
        # kernel's grid axis
        k_row = jnp.swapaxes(k, 1, 2).reshape(B * heads, 1, hd)
        v_row = jnp.swapaxes(v, 1, 2).reshape(B * heads, 1, hd)
        ck_i = jax.lax.dynamic_update_slice(ck[i], k_row, (0, t, 0))
        cv_i = jax.lax.dynamic_update_slice(cv[i], v_row, (0, t, 0))
        new_ck.append(ck_i)
        new_cv.append(cv_i)
        q_row = jnp.swapaxes(q, 1, 2).reshape(B * heads, hd)
        lengths = jnp.full((B * heads,), t + 1, dtype=jnp.int32)
        if backend == "pallas":
            att = decode_attention_pallas(q_row, ck_i, cv_i, lengths,
                                          interpret=interpret)
        else:  # inline XLA reference (parity oracle for the kernel)
            s = jnp.einsum("bd,bsd->bs", q_row, ck_i,
                           preferred_element_type=jnp.float32) * (hd ** -0.5)
            smax = ck_i.shape[1]
            valid = jnp.arange(smax)[None, :] < lengths[:, None]
            s = jnp.where(valid, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            att = jnp.einsum("bs,bsd->bd", p.astype(cv_i.dtype), cv_i,
                             preferred_element_type=jnp.float32)
        att = att.astype(x.dtype).reshape(B, heads, 1, hd)
        att = jnp.swapaxes(att, 1, 2).reshape(B, 1, heads * hd)
        x = x + att @ weights[f"l{i}.wo"][0]
        h2 = _rms(x)
        u = jax.nn.gelu(h2 @ weights[f"l{i}.up"][0])
        x = x + u @ weights[f"l{i}.down"][0]
    x = _rms(x)
    logits = (x @ weights["head"][0])[:, 0, :]  # (B, V)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, new_ck, new_cv


def greedy_decode(model: FLModelDef, weights: Dict[str, Array], width: int,
                  prompt, steps: int, *, backend: str = "pallas",
                  interpret: bool | None = None,
                  max_len: int | None = None) -> Tuple[np.ndarray, np.ndarray]:
    """Token-by-token greedy decode over composed width-p weights.

    prompt (B, T0) int32; generates ``steps`` tokens.  ``backend``
    selects the attention kernel: ``"pallas"`` streams the KV cache
    through :func:`decode_attention_pallas` (compiled on TPU, interpret
    elsewhere — :func:`default_interpret`), ``"xla"`` is the inline
    reference used as the parity oracle.  The prompt is prefilled
    through the same decode step, so the kernel serves every position.

    Returns ``(tokens (B, steps), last_logits (B, V))``.
    """
    arch = arch_of(model)
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown decode backend {backend!r}")
    if interpret is None:
        interpret = default_interpret()
    prompt = jnp.asarray(prompt, dtype=jnp.int32)
    B, t0 = prompt.shape
    if t0 < 1:
        raise ValueError("prompt must hold at least one token")
    total = t0 + steps
    smax = max_len or total
    if smax < total:
        raise ValueError(f"max_len={smax} < prompt+steps={total}")
    heads = width * arch.heads_base
    ck = [jnp.zeros((B * heads, smax, arch.head_dim), jnp.float32)
          for _ in range(arch.n_layers)]
    cv = [jnp.zeros((B * heads, smax, arch.head_dim), jnp.float32)
          for _ in range(arch.n_layers)]
    out = []
    logits = None
    nxt = prompt[:, 0]
    for t in range(total - 1):
        tok = prompt[:, t] if t < t0 else nxt
        nxt, logits, ck, cv = _decode_step(
            weights, ck, cv, tok, jnp.int32(t), model=model, width=width,
            backend=backend, interpret=bool(interpret))
        if t >= t0 - 1:
            out.append(nxt)
    return (np.stack([np.asarray(o) for o in out], axis=1),
            np.asarray(logits))
