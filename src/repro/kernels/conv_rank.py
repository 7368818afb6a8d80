"""Fused conv rank-path primitive: basis conv + coefficient contraction.

The conv rank path applies a factorized k×k weight without materialising
it: a group-batched basis conv projects every input group into rank
space (I → R) and a 1×1 coefficient contraction finishes the job
(R → pO, the paper's block reshape folded into the coefficient layout).
Run as separate XLA ops the rank-R intermediate ``t`` round-trips
through HBM and each op pays its own dispatch — historically that
overhead forced a hardcoded CPU gate that kept ``forward_impl="auto"``
off the conv rank path entirely.  This module fuses the two stages:

``conv_rank_pallas``
    one Pallas kernel over (image, output row): the basis conv runs as
    k² shifted matmuls per input group over the padded image held in
    VMEM (split into its stride phases and groups outside the kernel,
    so every window is a contiguous slice), the rank intermediate
    never leaves VMEM, and the same kernel step contracts it against
    the coefficient blocks.  Compiled on TPU, ``interpret=True``
    elsewhere (``interpret=None`` resolves through
    :func:`repro.kernels.compose.default_interpret`).

``conv_rank_apply``
    the public ``jax.custom_vjp`` primitive.  Forward: the Pallas
    kernel on compiled backends; on CPU/GPU an equivalent fused XLA
    formulation (the same k²-shifted-matmul math for group-batched
    modes, XLA's native conv + the native-layout contraction for
    ``grow_out``) — measured faster than both the separate-ops rank
    path and the Pallas interpreter there.  Backward: **stays in rank space** — the
    coefficient gradients are einsums through the R bottleneck, and
    the input/basis gradients ride ``jax.vjp`` of the basis conv alone
    (recomputing ``t``, the cheap I→R half), so no direction ever
    builds the ``(ksq, pI, pO)`` weight.

Padding follows XLA's asymmetric ``"SAME"`` convention (low = total//2)
so every formulation samples the exact positions
``lax.conv_general_dilated`` does and parity with the materialized conv
holds at any stride.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compose import _resolve, default_interpret

Array = jax.Array

CONV_MODES = ("square", "grow_out", "grow_in")


def _same_pads(size: int, k: int, stride: int) -> tuple[int, tuple[int, int]]:
    """Output size and (lo, hi) padding of XLA "SAME" for one dim."""
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return out, (total // 2, total - total // 2)


def _u2_conv_layout(u: Array, p: int, mode: str) -> Array:
    """Coefficient blocks (m, R, O) as the (g·R, D) contraction matrix.

    Row block ``a`` holds the R coefficients of input group ``a``; the
    column layout bakes in the compose block reshape, so ``t2 @ u2``
    lands directly in the composed output-channel order.
    """
    R, O = u.shape[-2], u.shape[-1]
    if mode == "grow_out":
        return jnp.transpose(u, (1, 0, 2)).reshape(R, p * O)
    if mode == "grow_in":
        return u.reshape(p * R, O)
    u4 = u.reshape(p, p, R, O)
    return jnp.transpose(u4, (0, 2, 1, 3)).reshape(p * R, p * O)


def _u2_conv_unlayout(du2: Array, p: int, R: int, O: int, mode: str) -> Array:
    """Inverse of :func:`_u2_conv_layout` for the coefficient gradient."""
    if mode == "grow_out":
        return jnp.transpose(du2.reshape(R, p, O), (1, 0, 2))
    if mode == "grow_in":
        return du2.reshape(p, R, O)
    du4 = jnp.transpose(du2.reshape(p, R, p, O), (0, 2, 1, 3))
    return du4.reshape(p * p, R, O)


def _basis_conv(x: Array, basis: Array, p: int, mode: str,
                stride: int) -> Array:
    """Group-batched basis conv: x (N, H, W, g·I) -> t2 (N, Ho, Wo, g·R).

    The linear map whose ``jax.vjp`` carries the input/basis gradients
    of the fused primitive — one XLA conv, groups folded into the
    batch.  Also the forward's first stage in the ``grow_out`` fused
    math path (g == 1: no fold, no transpose).
    """
    ksq, I, R = basis.shape
    k = int(round(ksq ** 0.5))
    vk = basis.reshape(k, k, I, R)
    dn = ("NHWC", "HWIO", "NHWC")
    g = 1 if mode == "grow_out" else p
    N, H, W, _ = x.shape
    if g == 1:
        return jax.lax.conv_general_dilated(x, vk, (stride, stride), "SAME",
                                            dimension_numbers=dn)
    xg = jnp.transpose(x.reshape(N, H, W, g, I), (0, 3, 1, 2, 4))
    xg = xg.reshape(N * g, H, W, I)
    t = jax.lax.conv_general_dilated(xg, vk, (stride, stride), "SAME",
                                     dimension_numbers=dn)
    Ho, Wo = t.shape[1], t.shape[2]
    t2 = jnp.transpose(t.reshape(N, g, Ho, Wo, R), (0, 2, 3, 1, 4))
    return t2.reshape(N, Ho, Wo, g * R)


def _fused_math(x: Array, basis: Array, u: Array, p: int, mode: str,
                stride: int) -> Array:
    """Fused XLA formulation — the CPU/GPU production forward.

    Group-batched modes run the basis conv as k² shifted matmuls over
    the SAME-padded image (the exact math of the Pallas kernel body:
    no group fold/unfold transposes, and the contraction is one flat
    matmul straight off the accumulator).  ``grow_out`` (a single
    group) has no inter-op traffic to fuse away: XLA's native conv for
    the I→R half plus the coefficient contraction in ``u``'s native
    ``(b, r, o)`` layout is the measured-fastest form, so the fused
    primitive's grow_out forward matches the separate-ops math exactly
    and its win there is the rank-space backward, not the forward.
    """
    ksq, I, R = basis.shape
    k = int(round(ksq ** 0.5))
    g = 1 if mode == "grow_out" else p
    if g == 1:
        t2 = _basis_conv(x, basis, p, mode, stride)
        y = jnp.einsum("nhwr,bro->nhwbo", t2, u)
        return y.reshape(y.shape[:3] + (y.shape[3] * y.shape[4],))
    u2 = _u2_conv_layout(u, p, mode)
    N, H, W, _ = x.shape
    Ho, (ph_lo, ph_hi) = _same_pads(H, k, stride)
    Wo, (pw_lo, pw_hi) = _same_pads(W, k, stride)
    xp = jnp.pad(x, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    xg = xp.reshape(N, xp.shape[1], xp.shape[2], g, I)
    acc = jnp.zeros((N, Ho, Wo, g, R), jnp.float32)
    for ky in range(k):
        for kx in range(k):
            win = xg[:, ky:ky + stride * (Ho - 1) + 1:stride,
                     kx:kx + stride * (Wo - 1) + 1:stride]
            acc = acc + jnp.einsum("nhwai,ir->nhwar", win,
                                   basis[ky * k + kx])
    t2 = acc.astype(x.dtype).reshape(N, Ho, Wo, g * R)
    return t2 @ u2


def _conv_rank_kernel(x_ref, v_ref, u_ref, o_ref, *, k, stride, g):
    """One output row of one image: k² shifted matmuls (I→R) per input
    group, then the coefficient contraction.

    x_ref (1, s²·g, Hq, Wq, I) — the SAME-padded image split into its
    s×s stride phases and its g input groups (see
    :func:`_phase_split`), so every window is a contiguous slice and no
    lane or sublane reshape is needed; v_ref (ksq, I, R); u_ref
    (g, R, D); o_ref (1, 1, Wo, D).  The (Wo, g·R) rank intermediate
    lives only in VMEM/registers.
    """
    h = pl.program_id(1)
    Wo = o_ref.shape[2]
    y = jnp.zeros((Wo, o_ref.shape[3]), jnp.float32)
    for a in range(g):
        acc = jnp.zeros((Wo, v_ref.shape[2]), jnp.float32)
        for ky in range(k):
            for kx in range(k):
                phase = ((ky % stride) * stride + kx % stride) * g + a
                win = x_ref[0, phase, h + ky // stride,
                            pl.ds(kx // stride, Wo), :]  # (Wo, I)
                acc = acc + jnp.dot(win, v_ref[ky * k + kx],
                                    preferred_element_type=jnp.float32)
        t = acc.astype(x_ref.dtype)
        y = y + jnp.dot(t, u_ref[a], preferred_element_type=jnp.float32)
    o_ref[0, 0] = y.astype(o_ref.dtype)


def _phase_split(xp: Array, stride: int, g: int) -> Array:
    """(N, Hp, Wp, g·I) padded image -> (N, s²·g, Hq, Wq, I).

    Row ``y`` of the padded image is row ``y // s`` of phase ``y % s``
    (likewise for columns), so the stride-s window of tap (ky, kx) is
    the contiguous slice starting at ``(ky // s, kx // s)`` of phase
    ``(ky % s, kx % s)``.
    """
    N, Hp, Wp, C = xp.shape
    s = stride
    Hq, Wq = -(-Hp // s), -(-Wp // s)
    xp = jnp.pad(xp, ((0, 0), (0, Hq * s - Hp), (0, Wq * s - Wp), (0, 0)))
    xq = xp.reshape(N, Hq, s, Wq, s, g, C // g)
    xq = jnp.transpose(xq, (0, 2, 4, 5, 1, 3, 6))
    return xq.reshape(N, s * s * g, Hq, Wq, C // g)


@functools.partial(jax.jit,
                   static_argnames=("p", "mode", "stride", "interpret"))
def conv_rank_pallas(x: Array, basis: Array, u2: Array, *, p: int,
                     mode: str = "square", stride: int = 1,
                     interpret: bool | None = None) -> Array:
    """Fused conv rank kernel: x (N, H, W, g·I) × basis (ksq, I, R) ×
    u2 (g·R, D) -> (N, Ho, Wo, D).

    Grid (image, output row); the whole phase-split padded image plus
    both factor operands sit in VMEM (the engine's model shapes are a
    few KB per image — far under the VMEM budget) and stay resident
    across an image's rows.  ``interpret=None`` resolves via
    :func:`default_interpret` (compiled on TPU, interpret elsewhere; the
    interpret path is CI's parity harness, not a production path — CPU
    production uses :func:`_fused_math`).
    """
    interpret = _resolve(interpret)
    ksq, _, R = basis.shape
    k = int(round(ksq ** 0.5))
    g = 1 if mode == "grow_out" else p
    N, H, W, _ = x.shape
    D = u2.shape[1]
    Ho, (ph_lo, ph_hi) = _same_pads(H, k, stride)
    Wo, (pw_lo, pw_hi) = _same_pads(W, k, stride)
    xp = jnp.pad(x, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    xq = _phase_split(xp, stride, g)
    u3 = u2.reshape(g, R, D)
    kern = functools.partial(_conv_rank_kernel, k=k, stride=stride, g=g)
    return pl.pallas_call(
        kern,
        grid=(N, Ho),
        in_specs=[
            pl.BlockSpec((1,) + xq.shape[1:], lambda n, h: (n, 0, 0, 0, 0)),
            pl.BlockSpec(basis.shape, lambda n, h: (0, 0, 0)),
            pl.BlockSpec(u3.shape, lambda n, h: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Wo, D), lambda n, h: (n, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Ho, Wo, D), x.dtype),
        interpret=interpret,
    )(xq, basis, u3)


@functools.lru_cache(maxsize=None)
def _conv_rank_fn(p: int, mode: str, stride: int, use_kernel: bool,
                  kernel_interpret: bool = False):
    """custom_vjp fused conv rank apply, cached per (width, mode, stride).

    Forward: the Pallas kernel when ``use_kernel`` (compiled on TPU;
    ``kernel_interpret=True`` forces the same branch through the
    interpreter so CPU CI exercises the exact wiring), the fused XLA
    formulation otherwise.  Backward: rank-space only — ``du``/``dt``
    are einsums through R, and ``dx``/``dbasis`` come from ``jax.vjp``
    of the basis conv (one cheap I→R recompute; the residual is just
    the primal operands, never the rank intermediate or the weight).
    """
    if mode not in CONV_MODES:
        raise ValueError(f"unknown conv mode {mode!r} "
                         f"(expected one of {CONV_MODES})")

    @jax.custom_vjp
    def apply(x, basis, u):
        if use_kernel:
            u2 = _u2_conv_layout(u, p, mode)
            return conv_rank_pallas(x, basis, u2, p=p, mode=mode,
                                    stride=stride,
                                    interpret=kernel_interpret)
        return _fused_math(x, basis, u, p, mode, stride)

    def fwd(x, basis, u):
        return apply(x, basis, u), (x, basis, u)

    def bwd(res, dy):
        x, basis, u = res
        R, O = u.shape[-2], u.shape[-1]
        t2, pull = jax.vjp(
            lambda x_, v_: _basis_conv(x_, v_, p, mode, stride), x, basis)
        u2 = _u2_conv_layout(u, p, mode)
        du2 = jnp.einsum("nhwk,nhwd->kd", t2, dy)
        dt2 = jnp.einsum("nhwd,kd->nhwk", dy, u2).astype(t2.dtype)
        dx, dbasis = pull(dt2)
        du = _u2_conv_unlayout(du2, p, R, O, mode).astype(u.dtype)
        return dx.astype(x.dtype), dbasis.astype(basis.dtype), du

    apply.defvjp(fwd, bwd)
    return apply


def conv_rank_apply(x: Array, basis: Array, reduced_coeff: Array, p: int,
                    mode: str = "square", *, stride: int = 1,
                    use_kernel: bool | None = None,
                    kernel_interpret: bool = False) -> Array:
    """Rank-space conv application with a rank-space backward.

    Args:
      x: ``(N, H, W, C)`` NHWC activations, ``C = g·I`` (``g = p`` for
        square/grow_in, 1 for grow_out).
      basis: ``(ksq, I, R)``; ``reduced_coeff``: ``(m, R, O)`` gathered
        blocks; ``p``: target width; ``mode``: the spec's mode.
      stride: SAME-conv stride.
      use_kernel: ``None`` routes by platform (Pallas kernel on TPU,
        fused XLA formulation elsewhere — :func:`default_interpret`).
      kernel_interpret: with ``use_kernel=True``, run the kernel branch
        through the Pallas interpreter (the CPU CI parity harness).

    Returns exactly what ``conv(x, compose(...))`` returns, up to float
    re-association, without materialising the ``(ksq, pI, pO)`` weight
    in either direction.
    """
    if use_kernel is None:
        use_kernel = not default_interpret()
    fn = _conv_rank_fn(p, mode, stride, use_kernel, kernel_interpret)
    return fn(x, basis, reduced_coeff)
