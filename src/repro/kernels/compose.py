"""Pallas kernels for the neural-composition hot path (paper Eq. 4).

Two primitives back the factorized client compute:

``compose_pallas``
    ``w[k] = basis[k] @ coeff_flat`` for every spatial slice ``k`` — the
    compose step that materialises a p-width weight from the shared
    basis and the gathered coefficient blocks.  Accepts an optional
    *leading client axis* (``basis (C, ksq, I, R)``, ``coeff (C, m, R,
    O)``) so ONE ``pallas_call`` serves a whole stacked cohort.  Each
    (bi x bj) output tile is an MXU matmul accumulated in fp32.
    Wrapped in a :func:`jax.custom_vjp` with an einsum backward:
    ``compose`` runs inside differentiated losses (every
    materialize-path layer in ``prepare_weights``, the RNN's
    scan-carried recurrence weight), and ``pallas_call`` has no
    transpose rule, so the kernel forward must carry its own VJP for
    ``jax.grad`` to work on compiled backends.

``rank_dense_apply``
    the fused rank-space application ``y = (x·v)·û`` for dense layers,
    wrapped in a :func:`jax.custom_vjp` whose backward ALSO stays in
    rank space — neither direction ever materialises the p-width
    weight.  The einsum formulation is the reference implementation and
    the CPU path; on compiled-Pallas backends the forward runs as one
    fused kernel (the rank-R intermediate lives in VMEM, never HBM).

``compose_dense_apply``
    compose+apply fusion for layers the cost model keeps on the
    *materialize* path (rank-space loses when ``R ≥ O/p``, e.g. the
    classifier heads): the per-group weights ``W_a = v · û_a`` are
    built inside the kernel (VMEM/registers) and contracted against the
    matching input group in the same invocation, so the p-width weight
    never reaches HBM even though the math is weight-shaped.  Shares
    the rank-space custom_vjp backward with ``rank_dense_apply`` — the
    two primitives compute the same function, they just associate the
    forward differently.

The conv-path sibling (fused basis conv + coefficient contraction)
lives in :mod:`repro.kernels.conv_rank`.

Platform gating: kernels compile on TPU and fall back to
``interpret=True`` everywhere Pallas lacks a compiled lowering for
*these* kernels — CPU hosts, and (for now) GPU: the block shapes and
in-kernel reshapes here are Mosaic/TPU idioms the Triton lowering does
not accept, so GPU hosts take the interpret/einsum reference paths
until a Triton-friendly variant lands.  See :func:`default_interpret`;
every ``interpret`` argument below defaults to that gate when left as
``None``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

_COMPILED_BACKENDS = ("tpu",)


def default_interpret() -> bool:
    """True where these kernels have no compiled lowering (everything
    but TPU — the kernel bodies use Mosaic idioms Triton rejects)."""
    return jax.default_backend() not in _COMPILED_BACKENDS


def _resolve(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


# ---------------------------------------------------------------------------
# compose: v · û  (materialisation)
# ---------------------------------------------------------------------------


def _compose_kernel(v_ref, u_ref, o_ref):
    # v_ref: (1, bi, R)  u_ref: (R, bj)  o_ref: (1, bi, bj)
    acc = jnp.dot(
        v_ref[0], u_ref[...], preferred_element_type=jnp.float32
    )
    o_ref[0] = acc.astype(o_ref.dtype)


def _compose_kernel_batched(v_ref, u_ref, o_ref):
    # v_ref: (1, 1, bi, R)  u_ref: (1, R, bj)  o_ref: (1, 1, bi, bj)
    acc = jnp.dot(
        v_ref[0, 0], u_ref[0], preferred_element_type=jnp.float32
    )
    o_ref[0, 0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def _compose_pallas_3d(basis: Array, coeff: Array, *, block_i: int,
                       block_j: int, interpret: bool) -> Array:
    ksq, I, R = basis.shape
    m, R2, O = coeff.shape
    assert R == R2
    MO = m * O
    u_flat = jnp.transpose(coeff, (1, 0, 2)).reshape(R, MO)
    bi = min(block_i, I)
    bj = min(block_j, MO)
    # pad to tile multiples
    Ip = -(-I // bi) * bi
    Jp = -(-MO // bj) * bj
    vp = jnp.pad(basis, ((0, 0), (0, Ip - I), (0, 0)))
    up = jnp.pad(u_flat, ((0, 0), (0, Jp - MO)))

    out = pl.pallas_call(
        _compose_kernel,
        grid=(ksq, Ip // bi, Jp // bj),
        in_specs=[
            pl.BlockSpec((1, bi, R), lambda k, i, j: (k, i, 0)),
            pl.BlockSpec((R, bj), lambda k, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bi, bj), lambda k, i, j: (k, i, j)),
        out_shape=jax.ShapeDtypeStruct((ksq, Ip, Jp), basis.dtype),
        interpret=interpret,
    )(vp, up)
    return out[:, :I, :MO]


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def _compose_pallas_4d(basis: Array, coeff: Array, *, block_i: int,
                       block_j: int, interpret: bool) -> Array:
    C, ksq, I, R = basis.shape
    C2, m, R2, O = coeff.shape
    assert R == R2 and C == C2
    MO = m * O
    u_flat = jnp.transpose(coeff, (0, 2, 1, 3)).reshape(C, R, MO)
    bi = min(block_i, I)
    bj = min(block_j, MO)
    Ip = -(-I // bi) * bi
    Jp = -(-MO // bj) * bj
    vp = jnp.pad(basis, ((0, 0), (0, 0), (0, Ip - I), (0, 0)))
    up = jnp.pad(u_flat, ((0, 0), (0, 0), (0, Jp - MO)))

    out = pl.pallas_call(
        _compose_kernel_batched,
        grid=(C, ksq, Ip // bi, Jp // bj),
        in_specs=[
            pl.BlockSpec((1, 1, bi, R), lambda c, k, i, j: (c, k, i, 0)),
            pl.BlockSpec((1, R, bj), lambda c, k, i, j: (c, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bi, bj),
                               lambda c, k, i, j: (c, k, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, ksq, Ip, Jp), basis.dtype),
        interpret=interpret,
    )(vp, up)
    return out[:, :, :I, :MO]


def _compose_dispatch(basis: Array, coeff: Array, block_i: int,
                      block_j: int, interpret: bool) -> Array:
    if basis.ndim == 4:
        return _compose_pallas_4d(basis, coeff, block_i=block_i,
                                  block_j=block_j, interpret=interpret)
    return _compose_pallas_3d(basis, coeff, block_i=block_i,
                              block_j=block_j, interpret=interpret)


@functools.lru_cache(maxsize=None)
def _compose_vjp_fn(block_i: int, block_j: int, interpret: bool):
    """custom_vjp around the compose kernel, cached per tiling/backend.

    ``pallas_call`` has no transpose rule, but ``compose`` is evaluated
    inside ``jax.grad`` whenever a materialize-path layer sits in a
    client loss (``prepare_weights``; the RNN's scan-carried ``wh``) —
    so the kernel forward pairs with an einsum backward.  The backward
    contracts through the rank-R bottleneck only (``dv: (ksq·I)×(mO)
    @ u^T``, ``du: v^T @ (ksq·I)×(mO)``), never wider than the forward.
    """

    @jax.custom_vjp
    def apply(basis, coeff):
        return _compose_dispatch(basis, coeff, block_i, block_j, interpret)

    def fwd(basis, coeff):
        return apply(basis, coeff), (basis, coeff)

    def bwd(res, g):
        basis, coeff = res
        m, O = coeff.shape[-3], coeff.shape[-1]
        g = g.reshape(g.shape[:-1] + (m, O))  # (..., ksq, I, m, O)
        if basis.ndim == 4:
            dv = jnp.einsum("ckimo,cmro->ckir", g, coeff)
            du = jnp.einsum("ckir,ckimo->cmro", basis, g)
        else:
            dv = jnp.einsum("kimo,mro->kir", g, coeff)
            du = jnp.einsum("kir,kimo->mro", basis, g)
        return dv.astype(basis.dtype), du.astype(coeff.dtype)

    apply.defvjp(fwd, bwd)
    return apply


def compose_pallas(basis: Array, coeff: Array, *, block_i: int = 128,
                   block_j: int = 128, interpret: bool | None = None) -> Array:
    """basis (ksq, I, R), coeff (m, R, O) -> (ksq, I, m*O).

    With a leading client axis — basis (C, ksq, I, R), coeff (C, m, R,
    O) — one ``pallas_call`` composes the whole cohort stack and the
    result gains the same leading axis.  The (m, R, O) coefficient
    blocks are flattened to (R, m*O): the column-blocked layout of the
    complete coefficient in the paper.

    Differentiable: the call routes through a ``jax.custom_vjp`` whose
    backward is the einsum transpose (see :func:`_compose_vjp_fn`), so
    ``jax.grad`` through ``compose(backend="pallas")`` works even
    though the Pallas forward has no automatic transpose.

    ``interpret=None`` resolves via :func:`default_interpret` (compiled
    on TPU, interpret elsewhere).
    """
    return _compose_vjp_fn(block_i, block_j, _resolve(interpret))(basis, coeff)


# ---------------------------------------------------------------------------
# fused rank-space dense apply: y = (x·v)·û
# ---------------------------------------------------------------------------


def _rank_apply_kernel(x_ref, v_ref, u_ref, o_ref):
    # x_ref (bm, g, I), v_ref (I, R), u_ref (g, R, D) -> o_ref (bm, D)
    # One (bm, R) rank slice per input group: y = sum_a (x_a·v)·û_a.
    # Mosaic cannot fold (bm, g, I) into bm·g rows, so the groups loop.
    bm, g, _ = x_ref.shape
    acc = jnp.zeros((bm, o_ref.shape[1]), jnp.float32)
    for a in range(g):
        t = jnp.dot(x_ref[:, a, :], v_ref[...],
                    preferred_element_type=jnp.float32).astype(x_ref.dtype)
        acc = acc + jnp.dot(t, u_ref[a], preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def rank_apply_pallas(xg: Array, v2: Array, u2: Array, *,
                      block_m: int = 256, interpret: bool | None = None
                      ) -> Array:
    """Fused two-stage contraction: xg (M, g, I) x v2 (I, R) x u2 (g*R, D)
    -> (M, D); the (M, g*R) rank intermediate stays in VMEM."""
    interpret = _resolve(interpret)
    M, g, I = xg.shape
    R = v2.shape[1]
    D = u2.shape[1]
    u3 = u2.reshape(g, R, D)
    bm = min(block_m, M)
    Mp = -(-M // bm) * bm
    xp = jnp.pad(xg, ((0, Mp - M), (0, 0), (0, 0)))
    out = pl.pallas_call(
        _rank_apply_kernel,
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, g, I), lambda i: (i, 0, 0)),
            pl.BlockSpec((I, R), lambda i: (0, 0)),
            pl.BlockSpec((g, R, D), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, D), xg.dtype),
        interpret=interpret,
    )(xp, v2, u3)
    return out[:M]


def _fwd_math(x2: Array, v2: Array, u: Array, p: int, mode: str):
    """Reference einsum forward on flattened rows: returns (y, t)."""
    R, O = u.shape[-2], u.shape[-1]
    if mode == "grow_out":
        t = x2 @ v2  # (M, R)
        y = jnp.einsum("mr,bro->mbo", t, u).reshape(x2.shape[0], p * O)
        return y, t
    xr = x2.reshape(x2.shape[0], p, -1)
    t = jnp.einsum("mai,ir->mar", xr, v2)  # (M, p, R)
    if mode == "grow_in":
        return jnp.einsum("mar,aro->mo", t, u), t
    u4 = u.reshape(p, p, R, O)
    y = jnp.einsum("mar,abro->mbo", t, u4).reshape(x2.shape[0], p * O)
    return y, t


def _u2_layout(u: Array, p: int, mode: str) -> Array:
    """Coefficient blocks as the (g*R, D) matrix the fused kernel eats."""
    R, O = u.shape[-2], u.shape[-1]
    if mode == "grow_out":
        return jnp.transpose(u, (1, 0, 2)).reshape(R, p * O)
    if mode == "grow_in":
        return u.reshape(p * R, O)
    u4 = u.reshape(p, p, R, O)
    return jnp.transpose(u4, (0, 2, 1, 3)).reshape(p * R, p * O)


def _rank_space_bwd(p: int, mode: str, res, dy):
    """Shared rank-space backward for ``rank_dense_apply`` and
    ``compose_dense_apply`` (same function, different forward
    associations).  Residual: ``(x2, v2, u, t)`` with ``t`` the rank
    intermediate; every contraction routes through the R bottleneck, so
    neither primitive's backward builds the p-width weight."""
    x2, v2, u, t = res
    R, O = u.shape[-2], u.shape[-1]
    if mode == "grow_out":
        dyr = dy.reshape(dy.shape[0], p, O)
        dt = jnp.einsum("mbo,bro->mr", dyr, u)
        dx = dt @ v2.T
        dv2 = x2.T @ dt
        du = jnp.einsum("mr,mbo->bro", t, dyr)
        return dx, dv2, du
    xr = x2.reshape(x2.shape[0], p, -1)
    if mode == "grow_in":
        dt = jnp.einsum("mo,aro->mar", dy, u)
        du = jnp.einsum("mar,mo->aro", t, dy)
    else:
        u4 = u.reshape(p, p, R, O)
        dyr = dy.reshape(dy.shape[0], p, O)
        dt = jnp.einsum("mbo,abro->mar", dyr, u4)
        du = jnp.einsum("mar,mbo->abro", t, dyr).reshape(p * p, R, O)
    dx = jnp.einsum("mar,ir->mai", dt, v2).reshape(x2.shape)
    dv2 = jnp.einsum("mai,mar->ir", xr, dt)
    return dx, dv2, du


@functools.lru_cache(maxsize=None)
def _rank_dense_fn(p: int, mode: str, use_kernel: bool,
                   kernel_interpret: bool = False):
    """custom_vjp rank-space dense apply, cached per (width, mode).

    Forward: the fused Pallas kernel on compiled backends, einsums
    elsewhere.  Backward: rank-space einsums in both cases — the
    transposed contractions route through the same R-dimensional
    bottleneck, so the backward pass never materialises the p-width
    weight either (this is the custom_vjp contract the Pallas forward
    relies on: Pallas kernels have no automatic transpose).

    ``kernel_interpret`` forces the ``use_kernel=True`` branch through
    the Pallas interpreter — how CPU CI exercises the exact fwd+bwd
    wiring (kernel forward + recomputed rank residual) that TPU runs
    compiled.
    """

    def _kernel_fwd(x2, v2, u):
        g = 1 if mode == "grow_out" else p
        xg = x2.reshape(x2.shape[0], g, -1)
        return rank_apply_pallas(xg, v2, _u2_layout(u, p, mode),
                                 interpret=kernel_interpret)

    @jax.custom_vjp
    def apply(x2, v2, u):
        # the primal runs on undifferentiated forwards (loss-only
        # evaluations) — it must take the same kernel branch as fwd or
        # compiled backends silently fall back to the einsum there
        if use_kernel:
            return _kernel_fwd(x2, v2, u)
        return _fwd_math(x2, v2, u, p, mode)[0]

    def fwd(x2, v2, u):
        if use_kernel:
            g = 1 if mode == "grow_out" else p
            xg = x2.reshape(x2.shape[0], g, -1)
            y = _kernel_fwd(x2, v2, u)
            # rank-space residual, recomputed cheaply (M·g·I·R MACs)
            t = jnp.einsum("mgi,ir->mgr", xg, v2)
            t = t[:, 0] if mode == "grow_out" else t
        else:
            y, t = _fwd_math(x2, v2, u, p, mode)
        return y, (x2, v2, u, t)

    def bwd(res, dy):
        return _rank_space_bwd(p, mode, res, dy)

    apply.defvjp(fwd, bwd)
    return apply


def rank_dense_apply(x: Array, basis: Array, reduced_coeff: Array, p: int,
                     mode: str = "square") -> Array:
    """Rank-space dense application with a rank-space backward.

    Args:
      x: ``(..., pI_total)`` row vectors.
      basis: ``(1, I, R)`` (dense layers have ``ksq == 1``).
      reduced_coeff: ``(m, R, O)`` gathered blocks.
      p: target width; ``mode``: the spec's square/grow_out/grow_in.

    Returns ``(..., pO_total)`` — what ``x @ compose(...)`` returns, up
    to float re-association, at ``O(R)`` instead of ``O(pI)`` cost per
    output, with the same guarantee through the backward pass.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    fn = _rank_dense_fn(p, mode, not default_interpret())
    y2 = fn(x2, basis[0], reduced_coeff)
    return y2.reshape(lead + (y2.shape[-1],))


# ---------------------------------------------------------------------------
# fused compose+apply: y = x · (v · û), weight built in VMEM
# ---------------------------------------------------------------------------


def _compose_apply_kernel(x_ref, v_ref, u_ref, o_ref):
    # x_ref (bm, g, I), v_ref (I, R), u_ref (g, R, D) -> o_ref (bm, D)
    bm, g, I = x_ref.shape
    D = u_ref.shape[2]
    acc = jnp.zeros((bm, D), jnp.float32)
    for a in range(g):
        w = jnp.dot(v_ref[...], u_ref[a],
                    preferred_element_type=jnp.float32).astype(x_ref.dtype)
        acc = acc + jnp.dot(x_ref[:, a, :], w,
                            preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def compose_apply_pallas(xg: Array, v2: Array, u3: Array, *,
                         block_m: int = 256,
                         interpret: bool | None = None) -> Array:
    """Fused compose+apply: xg (M, g, I) x v2 (I, R) x u3 (g, R, D)
    -> (M, D).

    Per input group ``a`` the kernel builds ``W_a = v2 @ u3[a]`` (an
    ``(I, D)`` tile, VMEM-resident) and accumulates ``xg[:, a] @ W_a``
    — the composed p-width weight exists only one group-slice at a
    time, on-chip.  ``u3`` is the :func:`_u2_layout` matrix reshaped to
    ``(g, R, D)``.  ``interpret=None`` resolves via
    :func:`default_interpret`.
    """
    interpret = _resolve(interpret)
    M, g, I = xg.shape
    D = u3.shape[2]
    bm = min(block_m, M)
    Mp = -(-M // bm) * bm
    xp = jnp.pad(xg, ((0, Mp - M), (0, 0), (0, 0)))
    out = pl.pallas_call(
        _compose_apply_kernel,
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, g, I), lambda i: (i, 0, 0)),
            pl.BlockSpec((I, v2.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec(u3.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, D), xg.dtype),
        interpret=interpret,
    )(xp, v2, u3)
    return out[:M]


def _compose_apply_math(x2: Array, v2: Array, u: Array, p: int,
                        mode: str) -> Array:
    """Fused XLA formulation: per-group weights as one batched einsum,
    then one grouped contraction — the CPU/GPU production forward
    (measured faster than compose-then-matmul at engine head shapes)."""
    g = 1 if mode == "grow_out" else p
    u3 = _u2_layout(u, p, mode).reshape(g, u.shape[-2], -1)
    w = jnp.einsum("ir,arj->aij", v2, u3)
    xg = x2.reshape(x2.shape[0], g, -1)
    return jnp.einsum("nai,aij->nj", xg, w)


@functools.lru_cache(maxsize=None)
def _compose_dense_fn(p: int, mode: str, use_kernel: bool,
                      kernel_interpret: bool = False):
    """custom_vjp fused compose+apply, cached per (width, mode).

    Same function as ``_rank_dense_fn`` with the forward associated the
    other way: ``x · (v·û)`` instead of ``(x·v)·û`` — the right
    association when the layer applies its weight to few rows (the cost
    model's materialize regime).  The backward is the identical shared
    rank-space VJP (:func:`_rank_space_bwd`): gradients don't care
    which way the forward associated, and rank space is always the
    cheaper side there.
    """

    def _run(x2, v2, u):
        if use_kernel:
            g = 1 if mode == "grow_out" else p
            xg = x2.reshape(x2.shape[0], g, -1)
            u3 = _u2_layout(u, p, mode).reshape(g, u.shape[-2], -1)
            return compose_apply_pallas(xg, v2, u3,
                                        interpret=kernel_interpret)
        return _compose_apply_math(x2, v2, u, p, mode)

    @jax.custom_vjp
    def apply(x2, v2, u):
        return _run(x2, v2, u)

    def fwd(x2, v2, u):
        y = _run(x2, v2, u)
        g = 1 if mode == "grow_out" else p
        xg = x2.reshape(x2.shape[0], g, -1)
        # rank-space residual for the shared backward, recomputed
        # cheaply (M·g·I·R MACs) — never the composed weight
        t = jnp.einsum("mgi,ir->mgr", xg, v2)
        t = t[:, 0] if mode == "grow_out" else t
        return y, (x2, v2, u, t)

    def bwd(res, dy):
        return _rank_space_bwd(p, mode, res, dy)

    apply.defvjp(fwd, bwd)
    return apply


def compose_dense_apply(x: Array, basis: Array, reduced_coeff: Array,
                        p: int, mode: str = "square") -> Array:
    """Fused compose+apply dense application (materialize-path fusion).

    Args:
      x: ``(..., pI_total)`` row vectors.
      basis: ``(1, I, R)`` (dense layers have ``ksq == 1``).
      reduced_coeff: ``(m, R, O)`` gathered blocks.
      p: target width; ``mode``: the spec's square/grow_out/grow_in.

    Returns ``(..., pO_total)`` — exactly what ``x @ compose(...)``
    returns up to float re-association, with the composed weight living
    only in VMEM/registers in the forward and a rank-space backward.
    Used by ``auto`` dispatch when the measured
    ``fused_compose_gain < 1`` (see :mod:`repro.core.calibration`).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    fn = _compose_dense_fn(p, mode, not default_interpret())
    y2 = fn(x2, basis[0], reduced_coeff)
    return y2.reshape(lead + (y2.shape[-1],))
