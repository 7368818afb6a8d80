"""Pallas TPU decode attention: one query over a long KV cache.

The decode_32k / long_500k hot-spot: memory-bound streaming of the cache
through VMEM with an online-softmax accumulator.  Grid (BH, nk); the KV
axis is sequential so (m, l, acc) scratch carries across tiles.  Valid
lengths arrive via scalar prefetch (SMEM) so ragged batches mask exactly.

Servers of freshly-federated models also decode through here: the
composed-transformer serving path (``repro.fl.transformer.greedy_decode``,
docs/TRANSFORMERS.md) keeps its per-layer KV caches in this kernel's
(B*H, S, D) layout and calls it once per generated token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.compose import _resolve

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, kv_block: int, nk: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (1, D)
    k = k_ref[0]  # (kb, D)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (1, kb)
    kpos = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, (1, kv_block), 1)
    valid = kpos < len_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    # running max / sum live in (1, 1) VMEM tiles: Mosaic cannot index
    # a rank-0 ref
    m_prev = m_ref[...]  # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)  # (1, D)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("kv_block", "q_per_kv", "interpret")
)
def decode_attention_pallas(q, k, v, lengths, *, kv_block: int = 512,
                            q_per_kv: int = 1, interpret: bool | None = None):
    """q (BH, D); k/v (BKV, S, D); lengths (BH,) int32 -> (BH, D).

    ``interpret=None`` resolves via :func:`repro.kernels.compose.
    default_interpret` (compiled on TPU, interpret elsewhere).
    """
    interpret = _resolve(interpret)
    BH, D = q.shape
    BKV, S, _ = k.shape
    assert BH == BKV * q_per_kv
    kb = min(kv_block, S)
    Sp = -(-S // kb) * kb
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0)))
    nk = Sp // kb
    g = q_per_kv

    kernel = functools.partial(_decode_kernel, scale=D ** -0.5, kv_block=kb,
                               nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, j, lens: (b, 0, 0)),
            pl.BlockSpec((1, kb, D), lambda b, j, lens: (b // g, j, 0)),
            pl.BlockSpec((1, kb, D), lambda b, j, lens: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, j, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q[:, None, :], kp, vp)
    return out[:, 0]
