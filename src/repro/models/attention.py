"""Attention: GQA/MQA, RoPE / M-RoPE, flash-style chunked softmax, KV cache.

Layouts:
  q           (B, S, KV, G, D)   G = q heads per kv head (GQA groups)
  k, v        (B, S, KV, D)
  kv cache    (B, Smax, KV, D)   keys stored *post-RoPE*

The training/prefill path is a pure-JAX flash attention: an outer scan over
query chunks and an inner scan over KV chunks with streaming max/sum, so the
(S x S) score matrix never materialises — this is what makes prefill_32k
lower within per-device memory.  The Pallas kernel in
``repro.kernels.flash_attention`` implements the same schedule with explicit
VMEM tiling for TPU; this module is the portable reference path used by the
distributed launcher (XLA fuses the scan body into a pipelined loop).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import module

Array = jax.Array
Params = Dict[str, Any]
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def rope_angles(positions: Array, head_dim: int, theta: float) -> Tuple[Array, Array]:
    """cos/sin for plain RoPE.  positions (..., S) int32 -> (..., S, D/2)."""
    half = head_dim // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def mrope_angles(
    positions: Array, head_dim: int, theta: float, sections: Tuple[int, int, int]
) -> Tuple[Array, Array]:
    """Multimodal RoPE (Qwen2-VL): positions (B, 3, S) — (t, h, w) ids.

    Frequency slot i takes its position id from the section it belongs to.
    sections sum to head_dim//2.
    """
    half = head_dim // 2
    assert sum(sections) == half, f"M-RoPE sections {sections} != head_dim/2 {half}"
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    sec_id = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )  # (half,)
    # gather per-frequency positions: (B, 3, S) -> (B, S, half)
    pos = jnp.take(positions, sec_id, axis=1)  # (B, half, S)
    pos = jnp.swapaxes(pos, -1, -2).astype(jnp.float32)  # (B, S, half)
    ang = pos * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate-half convention.  x (B, S, H, D); cos/sin (B|1, S, D/2)."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    c = cos[..., None, :].astype(x.dtype)  # (B, S, 1, D/2)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def default_positions(batch: int, seq: int, offset: Array | int = 0) -> Array:
    return jnp.arange(seq, dtype=jnp.int32)[None, :] + jnp.asarray(offset, jnp.int32)


def angles_for(cfg, positions: Array) -> Tuple[Array, Array]:
    """positions: (B, S) for rope, (B, 3, S) for mrope."""
    d = cfg.resolved_head_dim
    if cfg.rope_type == "mrope":
        return mrope_angles(positions, d, cfg.rope_theta, cfg.mrope_sections)
    return rope_angles(positions, d, cfg.rope_theta)


# ---------------------------------------------------------------------------
# flash attention (chunked streaming softmax, pure JAX)
# ---------------------------------------------------------------------------


def _pad_to(x: Array, size: int, axis: int) -> Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 2048,
    kv_chunk: int = 1024,
    valid_len: Optional[Array] = None,
    skip_masked_blocks: bool = False,
    scale: Optional[float] = None,
) -> Array:
    """Streaming-softmax attention.

    Args:
      q: (B, Sq, KV, G, D);  k: (B, Sk, KV, D);  v: (B, Sk, KV, Dv) — V's
        head dim may differ from Q/K's (latent attention: 192 vs 128).
      causal: apply causal mask with q positions aligned to the *end* of k
        (standard self-attention when Sq == Sk).
      window: sliding-window size (0 = full).
      valid_len: optional (B,) — mask out k positions >= valid_len.
      skip_masked_blocks: unroll the outer loop and statically skip KV
        chunks that are entirely masked by causality/window (perf variant —
        identical output, fewer FLOPs; see EXPERIMENTS.md §Perf).
      scale: the softmax scale; ``D ** -0.5`` when None.

    Returns (B, Sq, KV, G, Dv).
    """
    B, Sq, KV, G, D = q.shape
    Dv = v.shape[-1]
    Sk = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    qpad = (-Sq) % q_chunk
    kpad = (-Sk) % kv_chunk
    q = _pad_to(q, Sq + qpad, 1)
    k = _pad_to(k, Sk + kpad, 1)
    v = _pad_to(v, Sk + kpad, 1)
    nq, nk = (Sq + qpad) // q_chunk, (Sk + kpad) // kv_chunk
    if scale is None:
        scale = D ** -0.5
    q_offset = Sk - Sq  # causal alignment (q last token attends to k last)

    kq = jnp.moveaxis(q.reshape(B, nq, q_chunk, KV, G, D), 1, 0)
    kk = jnp.moveaxis(k.reshape(B, nk, kv_chunk, KV, D), 1, 0)
    kv = jnp.moveaxis(v.reshape(B, nk, kv_chunk, KV, Dv), 1, 0)

    def _one_q_chunk(qc, qi, kk, kv, nk_eff):
        qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset

        def body(carry, xs):
            m, l, acc = carry
            kc, vc, j = xs
            kpos = j * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qc, kc, preferred_element_type=jnp.float32
            ) * scale
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window > 0:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            mask &= (kpos < Sk)[None, :]
            maskb = mask[None, None, None]  # (1,1,1,q,k)
            if valid_len is not None:
                vl = valid_len[:, None, None, None, None]
                maskb = maskb & (kpos[None, None, None, None, :] < vl)
            s = jnp.where(maskb, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, Dv), jnp.float32)
        if skip_masked_blocks:
            # static python loop; only blocks intersecting the causal/window
            # band are executed.
            carry = (m0, l0, a0)
            qi_static = int(qi)
            q_lo = qi_static * q_chunk + q_offset
            q_hi = q_lo + q_chunk - 1
            for j in range(nk_eff):
                k_lo, k_hi = j * kv_chunk, (j + 1) * kv_chunk - 1
                if causal and k_lo > q_hi:
                    continue  # entirely in the future
                if window > 0 and (q_lo - k_hi) >= window:
                    continue  # entirely out of the window
                carry, _ = body(carry, (kk[j], kv[j], jnp.int32(j)))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(
                body, (m0, l0, a0), (kk, kv, jnp.arange(nk_eff))
            )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(out, 3, 1)  # (B, q_chunk, KV, G, Dv)

    if skip_masked_blocks:
        outs = [ _one_q_chunk(kq[i], i, kk, kv, nk) for i in range(nq) ]
        out = jnp.stack(outs, axis=0)
    else:
        out = jax.lax.map(
            lambda xs: _one_q_chunk(xs[0], xs[1], kk, kv, nk),
            (kq, jnp.arange(nq)),
        )
    out = jnp.moveaxis(out, 0, 1).reshape(B, nq * q_chunk, KV, G, Dv)
    return out[:, :Sq].astype(q.dtype)


def decode_attention(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    valid_mask: Array,
) -> Array:
    """One-token attention over a KV cache.

    q: (B, 1, KV, G, D); caches (B, S, KV, D); valid_mask (B, S) bool.
    Memory-bound — the whole cache streams through once.  The Pallas
    ``decode_attention`` kernel tiles this over KV blocks in VMEM.
    """
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k_cache, preferred_element_type=jnp.float32
    ) * (q.shape[-1] ** -0.5)
    s = jnp.where(valid_mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (projections + cache plumbing)
# ---------------------------------------------------------------------------


def init_attention(key, cfg, d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": module.maybe_factorized(ks[0], d, cfg.num_heads * hd, cfg, cfg.pdtype),
        "wk": module.maybe_factorized(ks[1], d, cfg.num_kv_heads * hd, cfg, cfg.pdtype),
        "wv": module.maybe_factorized(ks[2], d, cfg.num_kv_heads * hd, cfg, cfg.pdtype),
        "wo": module.maybe_factorized(ks[3], cfg.num_heads * hd, d, cfg, cfg.pdtype),
    }


def qkv(params: Params, cfg, x: Array) -> Tuple[Array, Array, Array]:
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    KV, G = cfg.num_kv_heads, cfg.q_per_kv
    q = module.linear(params["wq"], x).reshape(B, S, KV, G, hd)
    k = module.linear(params["wk"], x).reshape(B, S, KV, hd)
    v = module.linear(params["wv"], x).reshape(B, S, KV, hd)
    return q, k, v


def self_attention(
    params: Params,
    cfg,
    x: Array,
    cos: Array,
    sin: Array,
    *,
    causal: bool = True,
    skip_masked_blocks: bool = False,
) -> Array:
    """Full-sequence self attention (train / prefill)."""
    B, S, _ = x.shape
    q, k, v = qkv(params, cfg, x)
    if cfg.rope_type != "none":
        qf = q.reshape(B, S, -1, q.shape[-1])
        q = apply_rotary(qf, cos, sin).reshape(q.shape)
        k = apply_rotary(k, cos, sin)
    from repro.sharding.context import constrain_attention_q
    q, k, v = constrain_attention_q(q, k, v)
    out = flash_attention(
        q, k, v,
        causal=causal,
        window=cfg.sliding_window,
        q_chunk=cfg.q_chunk,
        kv_chunk=cfg.kv_chunk,
        skip_masked_blocks=skip_masked_blocks,
    )
    out = out.reshape(B, S, cfg.num_heads * cfg.resolved_head_dim)
    return module.linear(params["wo"], out)


def _quantize_kv(t: Array) -> Tuple[Array, Array]:
    """Per-token-per-head int8 quantization.  t (B, 1, KV, D) ->
    (int8 values, (B, 1, KV) f32 scales)."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def decode_self_attention(
    params: Params,
    cfg,
    x: Array,
    cache_k: Array,
    cache_v: Array,
    cache_len: Array,
    cos: Array,
    sin: Array,
    cache_scales: Optional[Tuple[Array, Array]] = None,
):
    """One-token decode step.

    x: (B, 1, d); caches (B, Smax, KV, D); cache_len scalar int32 —
    number of tokens already in the cache.  With sliding-window configs the
    cache is a ring buffer of size ``window`` and all live entries are
    valid.  When ``cache_scales`` is given the caches are int8 with
    per-token-per-head scales (B, Smax, KV) — the §Perf memory-term
    iteration for decode shapes.

    Returns (out, new_cache_k, new_cache_v[, new_scales]).
    """
    B, _, _ = x.shape
    Smax = cache_k.shape[1]
    q, k, v = qkv(params, cfg, x)
    if cfg.rope_type != "none":
        qf = q.reshape(B, 1, -1, q.shape[-1])
        q = apply_rotary(qf, cos, sin).reshape(q.shape)
        k = apply_rotary(k, cos, sin)
    slot = jnp.where(cfg.sliding_window > 0, cache_len % Smax, cache_len)
    if cache_scales is not None:
        k_scale_c, v_scale_c = cache_scales
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, kq, slot, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, vq, slot, axis=1)
        k_scale_c = jax.lax.dynamic_update_slice_in_dim(k_scale_c, ks, slot, axis=1)
        v_scale_c = jax.lax.dynamic_update_slice_in_dim(v_scale_c, vs, slot, axis=1)
        k_full = cache_k.astype(cfg.cdtype) * k_scale_c[..., None].astype(cfg.cdtype)
        v_full = cache_v.astype(cfg.cdtype) * v_scale_c[..., None].astype(cfg.cdtype)
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=1)
        k_full, v_full = cache_k, cache_v
    pos = jnp.arange(Smax)
    valid = (pos[None, :] <= cache_len) if cfg.sliding_window == 0 else (
        pos[None, :] <= jnp.minimum(cache_len, Smax - 1)
    )
    valid = jnp.broadcast_to(valid, (B, Smax))
    out = decode_attention(q, k_full, v_full, valid)
    out = out.reshape(B, 1, cfg.num_heads * cfg.resolved_head_dim)
    out = module.linear(params["wo"], out)
    if cache_scales is not None:
        return out, cache_k, cache_v, (k_scale_c, v_scale_c)
    return out, cache_k, cache_v


def cross_attention(
    params: Params, cfg, x: Array, mem_k: Array, mem_v: Array,
    mem_mask: Optional[Array] = None,
) -> Array:
    """Decoder cross-attention over precomputed encoder memory K/V.

    mem_k/mem_v: (B, Sm, KV, D).  No RoPE on cross-attention (seamless
    convention).  Uses the decode kernel shape when Sq==1.
    """
    B, Sq, _ = x.shape
    hd = cfg.resolved_head_dim
    KV, G = cfg.num_kv_heads, cfg.q_per_kv
    q = module.linear(params["wq"], x).reshape(B, Sq, KV, G, hd)
    Sm = mem_k.shape[1]
    if mem_mask is None:
        mem_mask = jnp.ones((B, Sm), bool)
    if Sq == 1:
        out = decode_attention(q, mem_k, mem_v, mem_mask)
    else:
        out = flash_attention(
            q, mem_k, mem_v, causal=False,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            valid_len=jnp.sum(mem_mask, -1).astype(jnp.int32),
        )
    out = out.reshape(B, Sq, cfg.num_heads * hd)
    return module.linear(params["wo"], out)


def init_cross_attention(key, cfg) -> Params:
    """Cross-attn projections: q from decoder, k/v precomputed from memory."""
    return init_attention(key, cfg)


def encode_memory(params: Params, cfg, mem: Array) -> Tuple[Array, Array]:
    """Precompute cross-attention K/V from encoder output (B, Sm, d)."""
    B, Sm, _ = mem.shape
    hd = cfg.resolved_head_dim
    k = module.linear(params["wk"], mem).reshape(B, Sm, cfg.num_kv_heads, hd)
    v = module.linear(params["wv"], mem).reshape(B, Sm, cfg.num_kv_heads, hd)
    return k, v
