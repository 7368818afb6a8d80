"""Plain reference of the composed decoder: weights from basis and
coefficient blocks by einsum, then a straightforward pre-norm decoder.

Imports nothing of the program.  Everything runs under
``jax.default_matmul_precision("highest")`` when the caller asks for
float32 (``dtype=jnp.float32``); ``dtype=jnp.bfloat16`` computes the
forward and backward passes in bfloat16 from float32 master factors,
the control that a lower precision must fail.

Layout of one layer's factors (Heroes, Eq. 4): basis ``(1, I, R)``,
complete coefficient ``(blocks, R, O)``.  A width-p client holds the
basis and ``p*p`` gathered blocks of each hidden ("square") layer, and
``p`` blocks of the embedding ("grow_out", vocabulary rows anchored) and
of the head ("grow_in", vocabulary columns anchored).

Where the block departs from Pythia (GPT-NeoX), the line says so.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROPE_THETA = 10000.0  # Pythia's rotary_emb_base
RMS_EPS = 1e-6


def layer_specs(m: dict) -> Dict[str, Tuple[str, int, int]]:
    """name -> (mode, I, O) in the order the factors are initialised."""
    d, ff, v = m["d_base"], m["ff_mult"] * m["d_base"], m["vocab"]
    specs = {"embed": ("grow_out", v, d)}
    for i in range(m["n_layers"]):
        for p in ("wq", "wk", "wv", "wo"):
            specs[f"l{i}.{p}"] = ("square", d, d)
        specs[f"l{i}.up"] = ("square", d, ff)
        specs[f"l{i}.down"] = ("square", ff, d)
    specs["head"] = ("grow_in", d, v)
    return specs


def num_blocks(mode: str, p: int) -> int:
    return p * p if mode == "square" else p


def init_params(m: dict, seed: int):
    """Factors from the seed: one key per layer in spec order, split into
    basis and coefficient keys; both normal with the fan-in split
    ``std = (1 / (I R)) ** 0.25`` so the composed weight has variance
    ``1 / I``."""
    P, R = m["max_width"], m["rank"]
    specs = layer_specs(m)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
    out = {}
    for k, (name, (mode, I, O)) in zip(keys, specs.items()):
        kb, kc = jax.random.split(k)
        std = (1.0 / I / R) ** 0.25
        out[name] = {
            "basis": std * jax.random.normal(kb, (1, I, R), jnp.float32),
            "coeff": std * jax.random.normal(
                kc, (num_blocks(mode, P), R, O), jnp.float32)}
    return out


def reduce(params, hidden_ids, anchored_ids, specs):
    """A client's factors: full basis, its assigned coefficient blocks."""
    out = {}
    for name, (mode, _, _) in specs.items():
        ids = np.asarray(hidden_ids if mode == "square" else anchored_ids)
        out[name] = {"basis": params[name]["basis"],
                     "coeff": params[name]["coeff"][ids]}
    return out


def compose(basis, blocks, p: int, mode: str):
    """The width-p weight from ``(1, I, R)`` and ``(m, R, O)`` blocks.

    square: block ``a*p + b`` is the (input group a, output group b) tile
    of the ``(pI, pO)`` weight; grow_out: blocks side by side along the
    output (``(I, pO)``); grow_in: stacked along the input (``(pI, O)``).
    """
    w = jnp.einsum("ir,mro->mio", basis[0], blocks)  # (m, I, O)
    m, I, O = w.shape
    if mode == "grow_out":
        return jnp.transpose(w, (1, 0, 2)).reshape(I, m * O)
    if mode == "grow_in":
        return w.reshape(m * I, O)
    return jnp.transpose(w.reshape(p, p, I, O), (0, 2, 1, 3)).reshape(
        p * I, p * O)


def rms(x):
    # departure: Pythia uses LayerNorm with gain and bias; the program's
    # block uses parameter-free RMSNorm (every parameter is composed)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS)


def rotary(x, pos):
    """Rotate-half RoPE over the whole head (x: (B, T, H, D))."""
    # departure: Pythia rotates 25% of each head (rotary_pct 0.25); the
    # program rotates all of it
    d = x.shape[-1]
    half = d // 2
    inv = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv  # (T, D/2)
    c = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    s = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def forward(params, width: int, tokens, m: dict, dtype=jnp.float32):
    """Logits ``(B, T, V)`` of a width-``width`` client's factors."""
    specs = layer_specs(m)
    p = width
    w = {n: compose(params[n]["basis"].astype(dtype),
                    params[n]["coeff"].astype(dtype), p, specs[n][0])
         for n in specs}
    B, T = tokens.shape
    hd = m["d_base"] // m["heads_base"]
    H = p * m["heads_base"]
    x = jnp.take(w["embed"], tokens, axis=0)  # (B, T, pD)
    pos = jnp.arange(T)
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(m["n_layers"]):
        h = rms(x)
        q = rotary((h @ w[f"l{i}.wq"]).reshape(B, T, H, hd), pos)
        k = rotary((h @ w[f"l{i}.wk"]).reshape(B, T, H, hd), pos)
        v = (h @ w[f"l{i}.wv"]).reshape(B, T, H, hd)
        # departure: Pythia's projections carry biases; these carry none
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (hd ** -0.5)
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, H * hd)
        # departure: Pythia adds attention and MLP to the same input in
        # parallel (use_parallel_residual); the program adds them in turn
        x = x + att @ w[f"l{i}.wo"]
        # departure: Pythia's "gelu" is the exact erf form; the program
        # uses the tanh approximation (jax.nn.gelu's default)
        u = jax.nn.gelu(rms(x) @ w[f"l{i}.up"], approximate=True)
        x = x + u @ w[f"l{i}.down"]
    return rms(x) @ w["head"]  # untied head, as in Pythia


def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def loss_fn(params, width, batch, m, dtype):
    return cross_entropy(forward(params, width, batch["tokens"], m, dtype),
                         batch["labels"])


@functools.lru_cache(maxsize=None)
def _fns(width: int, mkey: tuple, dtype_name: str):
    m = dict(mkey)
    dtype = jnp.dtype(dtype_name)
    precision = "highest" if dtype == jnp.float32 else "default"

    def loss(params, batch):
        with jax.default_matmul_precision(precision):
            return loss_fn(params, width, batch, m, dtype)

    def sgd(params, batch, lr):
        with jax.default_matmul_precision(precision):
            g = jax.grad(loss_fn)(params, width, batch, m, dtype)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, params, g)

    return jax.jit(loss), jax.jit(sgd)


def local_train(params, width: int, batches: List[dict], lr: float, m: dict,
                dtype=jnp.float32):
    """``len(batches)`` SGD steps from ``params``; returns the trained
    factors and the loss on the first batch before and after."""
    loss, sgd = _fns(width, tuple(sorted(m.items())), jnp.dtype(dtype).name)
    before = float(loss(params, batches[0]))
    for b in batches:
        params = sgd(params, b, lr)
    after = float(loss(params, batches[0]))
    return params, before, after

