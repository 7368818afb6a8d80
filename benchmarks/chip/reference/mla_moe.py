"""Plain reference of the composed latent-attention + experts decoder
(DeepSeek-V2's block): weights from basis and coefficient blocks by
einsum, then a straightforward pre-norm decoder with plain causal
softmax attention and plain per-token top-k routing.

Imports nothing of the program.  The same API as ``transformer.py``
(``layer_specs``, ``init_params``, ``reduce``, ``local_train``), so
``train_check`` follows it and ``merge.py`` merges it unchanged.  Under
``dtype=jnp.float32`` everything runs at ``highest`` matmul precision;
``dtype=jnp.bfloat16`` is the control (forward and backward in
bfloat16 from float32 master factors).

Layout of one layer's factors (Heroes, Eq. 4): basis ``(1, I, R)``,
complete coefficient ``(blocks, R, O)``.  An expert bank of ``E`` experts
keeps a basis and blocks per expert: basis ``(E, I, R)``, coefficient
``(blocks, E*R, O)`` -- expert ``e``'s block ``b`` is rows ``e*R ..
(e+1)*R - 1`` of block ``b`` (the program's ``(blocks, E, R, O)``, viewed
with its expert and rank axes merged, so that ``merge.py``'s Eq. 5 over
the leading block axis applies as it is).

Where the block departs from DeepSeek-V2, the line says so.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import transformer as dense_ref

RMS_EPS = 1e-6


def layer_specs(m: dict) -> Dict[str, Tuple[str, int, int, int]]:
    """name -> (mode, I, O, experts) in the order the factors are
    initialised."""
    d, hb = m["d_base"], m["heads_base"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    vd, lat = m["v_head_dim"], m["kv_lora_rank"]
    specs = {"embed": ("grow_out", m["vocab"], d, 1)}

    def swiglu(prefix, ff, experts=1):
        specs[f"{prefix}.gate"] = ("square", d, ff, experts)
        specs[f"{prefix}.up"] = ("square", d, ff, experts)
        specs[f"{prefix}.down"] = ("square", ff, d, experts)

    for i in range(m["n_layers"]):
        specs[f"l{i}.wq"] = ("square", d, hb * (nope + rope), 1)
        specs[f"l{i}.wkv_a"] = ("grow_in", d, lat + rope, 1)
        specs[f"l{i}.wkv_b"] = ("grow_out", lat, hb * (nope + vd), 1)
        specs[f"l{i}.wo"] = ("square", hb * vd, d, 1)
        if i < m["first_dense"]:
            swiglu(f"l{i}", m["dense_ff_base"])
        else:
            specs[f"l{i}.router"] = ("grow_in", d, m["n_experts"], 1)
            swiglu(f"l{i}.shared", m["shared_ff_base"])
            swiglu(f"l{i}.experts", m["expert_ff_base"], m["experts_held"])
    specs["head"] = ("grow_in", d, m["vocab"], 1)
    return specs


def init_params(m: dict, seed: int):
    """Factors from the seed: one key per layer in spec order, split into
    basis and coefficient keys; both normal with ``std = (1 / (I R)) **
    0.25``, so each composed weight has variance ``1 / I``."""
    P, R = m["max_width"], m["rank"]
    specs = layer_specs(m)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
    out = {}
    for k, (name, (mode, I, O, E)) in zip(keys, specs.items()):
        kb, kc = jax.random.split(k)
        std = (1.0 / I / R) ** 0.25
        nb = dense_ref.num_blocks(mode, P)
        shape = (nb, R, O) if E == 1 else (nb, E, R, O)
        out[name] = {
            "basis": std * jax.random.normal(kb, (E, I, R), jnp.float32),
            "coeff": (std * jax.random.normal(kc, shape, jnp.float32)
                      ).reshape(nb, E * R, O)}
    return out


def reduce(params, hidden_ids, anchored_ids, specs):
    """A client's factors: full basis, its assigned coefficient blocks."""
    out = {}
    for name, spec in specs.items():
        ids = np.asarray(hidden_ids if spec[0] == "square" else anchored_ids)
        out[name] = {"basis": params[name]["basis"],
                     "coeff": params[name]["coeff"][ids]}
    return out


def compose(basis, blocks, p: int, mode: str):
    """The width-p weight: ``(pI, pO)`` for a single weight (the dense
    reference's compose), ``(E, pI, pO)`` for an expert bank, each expert
    composed from its own basis and blocks."""
    E, I, R = basis.shape
    if E == 1:
        return dense_ref.compose(basis, blocks, p, mode)
    O = blocks.shape[-1]
    # expert e's square weight: block a*p + b is its (a, b) tile
    w = jnp.einsum("eir,mero->emio", basis,
                   blocks.reshape(blocks.shape[0], E, R, O))
    return jnp.transpose(w.reshape(E, p, p, I, O), (0, 1, 3, 2, 4)).reshape(
        E, p * I, p * O)


def rms(x):
    # departure: DeepSeek-V2's RMSNorms carry a gain; every norm here,
    # the latent's too, is parameter-free (every parameter is composed)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(m: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN ``inv_freq``: the extrapolated frequencies
    below the correction range, interpolated (divided by the factor)
    above it, a linear ramp between (``DeepseekV2YarnRotaryEmbedding``)."""
    dim, base = m["qk_rope_head_dim"], m["rope_theta"]
    factor, orig = m["rope_factor"], m["rope_original"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))

    def corr(n):
        return dim * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(m["beta_fast"])), 0)
    high = min(math.ceil(corr(m["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def rotary(x, pos, m: dict):
    """Half-split rope over the last axis of x ``(B, T, H, rope)``."""
    # departure: DeepSeek-V2 pairs interleaved columns (it de-interleaves
    # q and k before rotate-half); the half-split layout here is the same
    # map under a fixed permutation of the rope columns of wq and wkv_a
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        rope_frequencies(m), jnp.float32)
    # cos and sin carry yarn_mscale(f, mscale) / yarn_mscale(f, mscale_all)
    ms = (yarn_mscale(m["rope_factor"], m["mscale"])
          / yarn_mscale(m["rope_factor"], m["mscale_all_dim"]))
    c = (jnp.cos(ang) * ms)[None, :, None, :].astype(x.dtype)
    s = (jnp.sin(ang) * ms)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed(h, router, bank, m: dict):
    """The held experts' part of the routed output, token by token:
    softmax over every expert's logit, greedy top-k, gates as they are;
    experts ``0 .. experts_held-1`` are held, and the pairs of a token
    with an absent expert add nothing."""
    # router in float32 at highest precision (its top-k decides which
    # expert a token meets), in the bfloat16 control too
    with jax.default_matmul_precision("highest"):
        logits = h.astype(jnp.float32) @ router.astype(jnp.float32)
    scores, ids = jax.lax.top_k(jax.nn.softmax(logits, -1), m["top_k"])
    held = jnp.arange(m["experts_held"])
    # (..., E): each held expert's gate for each token, 0 where unrouted
    gates = jnp.sum(jnp.where(ids[..., None, :] == held[:, None],
                              scores[..., None, :], 0.0), -1)
    # every held expert on every token, weighted by its gate
    y = (jax.nn.silu(jnp.einsum("...d,edf->...ef", h, bank["gate"]))
         * jnp.einsum("...d,edf->...ef", h, bank["up"]))
    y = jnp.einsum("...ef,efd->...ed", y, bank["down"])
    return jnp.einsum("...e,...ed->...d", gates.astype(h.dtype), y)


def forward(params, width: int, tokens, m: dict, dtype=jnp.float32):
    """Logits ``(B, T, V)`` of a width-``width`` client's factors."""
    specs = layer_specs(m)
    p = width
    w = {n: compose(params[n]["basis"].astype(dtype),
                    params[n]["coeff"].astype(dtype), p, specs[n][0])
         for n in specs}
    B, T = tokens.shape
    H = p * m["heads_base"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    vd, lat = m["v_head_dim"], m["kv_lora_rank"]
    scale = ((nope + rope) ** -0.5
             * yarn_mscale(m["rope_factor"], m["mscale_all_dim"]) ** 2)
    x = jnp.take(w["embed"], tokens, axis=0)  # (B, T, pD)
    pos = jnp.arange(T)
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(m["n_layers"]):
        l = f"l{i}"
        h = rms(x)
        # latent attention, no q-LoRA (DeepSeek-V2-Lite's q_lora_rank null)
        q = (h @ w[f"{l}.wq"]).reshape(B, T, H, nope + rope)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], pos, m)],
                            -1)
        kv_a = h @ w[f"{l}.wkv_a"]
        k_rope = rotary(kv_a[:, :, None, lat:], pos, m)
        kv = (rms(kv_a[..., :lat]) @ w[f"{l}.wkv_b"]).reshape(
            B, T, H, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, T, H, rope))], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        att = jnp.einsum("bhqk,bkhd->bqhd", a, kv[..., nope:])
        x = x + att.reshape(B, T, H * vd) @ w[f"{l}.wo"]
        h = rms(x)
        if i < m["first_dense"]:
            x = x + swiglu(h, w[f"{l}.gate"], w[f"{l}.up"], w[f"{l}.down"])
            continue
        bank = {n: w[f"{l}.experts.{n}"] for n in ("gate", "up", "down")}
        # departure: DeepSeek-V2's sequence-wise balance loss is left out
        # (the objective is the token cross-entropy)
        x = x + swiglu(h, w[f"{l}.shared.gate"], w[f"{l}.shared.up"],
                       w[f"{l}.shared.down"]) + routed(
            h, w[f"{l}.router"], bank, m)
    return rms(x) @ w["head"]  # untied head, as in DeepSeek-V2


def loss_fn(params, width, batch, m, dtype):
    return dense_ref.cross_entropy(
        forward(params, width, batch["tokens"], m, dtype), batch["labels"])


@functools.lru_cache(maxsize=None)
def _step(width: int, mkey: tuple, dtype_name: str):
    """One program per width and type: an SGD step that also returns the
    loss at the factors it starts from (the step with ``lr = 0`` reads
    a loss alone), so a width compiles once: at ``highest`` precision
    the compile is most of the reference's time on the chip."""
    m = dict(mkey)
    dtype = jnp.dtype(dtype_name)
    precision = "highest" if dtype == jnp.float32 else "default"

    def step(params, batch, lr):
        with jax.default_matmul_precision(precision):
            loss, g = jax.value_and_grad(loss_fn)(params, width, batch, m,
                                                  dtype)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, params,
                                      g), loss

    return jax.jit(step)


def local_train(params, width: int, batches: List[dict], lr: float, m: dict,
                dtype=jnp.float32):
    """``len(batches)`` SGD steps from ``params``; returns the trained
    factors and the loss on the first batch before and after."""
    step = _step(width, tuple(sorted(m.items())), jnp.dtype(dtype).name)
    before = None
    for b in batches:
        params, loss = step(params, b, lr)
        before = loss if before is None else before
    _, after = step(params, batches[0], 0.0)
    return params, float(before), float(after)
