"""Enhanced neural composition (Heroes, Sec. II-B / III).

Every layer weight ``w_p`` of width multiplier ``p`` is approximated as the
product of a shared *neural basis* ``v`` and a per-width *coefficient*
``u_p`` (Eq. 4 of the paper)::

    w_p ~= v . u_p       v in R^{k^2 x I x R},  u_p in R^{R x (p * pO)}

The *complete* coefficient ``u in R^{R x (P^2 O)}`` is partitioned into
``P^2`` blocks of shape ``R x O``.  A ``p``-width model takes ``p^2`` blocks
(the *least trained* ones, per the paper's enhancement), composes them with
the basis into an intermediate ``k^2 x I x (p^2 O)`` tensor and reshapes it
to the p-width weight ``k^2 x pI x pO`` (Fig. 1).

We store the complete coefficient as ``(P^2, R, O)`` so blocks are a leading
index — selection is a gather, block-wise aggregation (Eq. 5) is a segment
mean, both shardable.

Design notes
------------
* ``compose`` is a single einsum — on TPU this is an MXU matmul.  The
  Pallas kernel in :mod:`repro.kernels.compose` implements the same
  contraction with explicit VMEM tiling; this module is the reference /
  CPU path and the place where shapes are defined.
* Training operates directly on the factors (gradients flow through
  ``compose``), so no per-round decomposition is needed.  ``decompose``
  (least-squares projection) is provided for parity with the paper's
  materialised formulation and for the HeteroFL-style baselines.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CompositionSpec:
    """Static description of one factorized weight.

    Attributes:
      max_width: ``P`` — the maximum width multiplier.  The complete
        coefficient holds ``P**2`` blocks (``P`` for anchored modes).
      rank: ``R`` — the low-rank dimension shared by basis and coefficient.
      base_in: ``I`` — input channels of the width-1 weight.
      base_out: ``O`` — output channels of the width-1 weight.
      ksq: ``k^2`` — spatial size for convolutions; 1 for dense layers.
      mode: how the weight scales with width p —
        "square"   hidden weight, (pI x pO), p^2 blocks (paper Fig. 1);
        "grow_out" input-anchored (first conv / embedding): (I x pO),
                   p blocks;
        "grow_in"  output-anchored (classifier): (pI x O), p blocks.
        The anchored modes are the Flanc treatment of boundary layers.
      experts: ``E`` > 1 makes the spec an expert bank: ``E`` weights of
        the same shape, each with its own basis and its own blocks —
        basis ``(E, I, R)``, coefficient ``(P^2, E, R, O)`` (blocks stay
        on the leading axis, so block selection and the Eq. 5 merge are
        those of a single weight), weight ``(E, pI, pO)``.  Dense only.
    """

    max_width: int
    rank: int
    base_in: int
    base_out: int
    ksq: int = 1
    mode: str = "square"
    experts: int = 1

    def __post_init__(self):
        if self.experts > 1 and self.ksq != 1:
            raise ValueError("an expert bank is dense: ksq must be 1")

    @property
    def num_blocks(self) -> int:
        p = self.max_width
        return p * p if self.mode == "square" else p

    def blocks_for_width(self, p: int) -> int:
        if not 1 <= p <= self.max_width:
            raise ValueError(f"width {p} outside [1, {self.max_width}]")
        return p * p if self.mode == "square" else p

    @property
    def lead(self) -> int:
        """Leading axis of basis and weight: the expert count of a bank,
        the spatial taps of a convolution, else 1."""
        return self.experts if self.experts > 1 else self.ksq

    def basis_shape(self) -> Tuple[int, int, int]:
        return (self.lead, self.base_in, self.rank)

    def coefficient_shape(self) -> Tuple[int, ...]:
        if self.experts > 1:
            return (self.num_blocks, self.experts, self.rank, self.base_out)
        return (self.num_blocks, self.rank, self.base_out)

    def weight_shape(self, p: int) -> Tuple[int, int, int]:
        pi = p if self.mode in ("square", "grow_in") else 1
        po = p if self.mode in ("square", "grow_out") else 1
        return (self.lead, pi * self.base_in, po * self.base_out)

    def params_factorized(self, p: int) -> int:
        """Parameter count shipped to a width-``p`` client (basis + blocks)."""
        basis = self.lead * self.base_in * self.rank
        coeff = (self.experts * self.blocks_for_width(p) * self.rank
                 * self.base_out)
        return basis + coeff

    def params_materialized(self, p: int) -> int:
        lead, pi, po = self.weight_shape(p)
        return lead * pi * po


def init_factors(
    key: Array, spec: CompositionSpec, dtype: Any = jnp.float32
) -> Tuple[Array, Array]:
    """Initialise (basis, coefficient) so the composed weight has
    fan-in-scaled variance (LeCun-style) at every width.

    var(w) = var(v)*var(u)*R  — we split the target variance evenly between
    the two factors.
    """
    kb, kc = jax.random.split(key)
    fan_in = spec.ksq * spec.base_in
    target_var = 1.0 / float(fan_in)
    # var(v) * var(u) * R = target_var ; choose var(v)=var(u)=sqrt(target/R)
    factor_std = (target_var / spec.rank) ** 0.25
    basis = factor_std * jax.random.normal(kb, spec.basis_shape(), dtype)
    coeff = factor_std * jax.random.normal(kc, spec.coefficient_shape(), dtype)
    return basis, coeff


def select_blocks(counters: Array | np.ndarray, p: int, spec: CompositionSpec) -> np.ndarray:
    """Indices of the ``p^2`` *least trained* blocks (paper Sec. II-B).

    ``counters[i]`` is the total number of local iterations block ``i`` has
    received since round 1.  Ties break on the lower index for determinism.
    Host-side (numpy) — this is PS control logic, not a traced computation.
    """
    c = np.asarray(counters)
    if c.shape != (spec.num_blocks,):
        raise ValueError(f"counters shape {c.shape} != ({spec.num_blocks},)")
    k = spec.blocks_for_width(p)
    # stable argsort => deterministic tie-break on block index
    order = np.argsort(c, kind="stable")
    return np.sort(order[:k])


def gather_blocks(coefficient: Array, block_ids) -> Array:
    """Reduced coefficient ``û``: gather ``(m, R, O)`` from ``(P^2, R, O)``.

    ``block_ids`` are host-side control indices (PS logic, never traced),
    so they are validated eagerly: ``jnp.take`` clamps out-of-range
    indices silently, which turns an id-bookkeeping bug (e.g. handing an
    anchored ``P``-block layer the shared ``P^2``-counter ids) into a
    wrong-but-plausible gather instead of an error.
    """
    ids = np.asarray(block_ids)
    n = coefficient.shape[0]
    if ids.size and (ids.min() < 0 or ids.max() >= n):
        raise ValueError(
            f"block ids out of range: got ids in [{ids.min()}, {ids.max()}] "
            f"for a coefficient with {n} blocks")
    return jnp.take(coefficient, jnp.asarray(ids), axis=0)


def _pallas_compose_default() -> bool:
    """Route compose through the Pallas kernel only where it compiles
    to the platform's matrix unit; einsum (XLA) everywhere else — the
    CPU einsum is also the bitwise reference path the parity tests and
    seed histories anchor on.  The platform gate is owned by
    :func:`repro.kernels.compose.default_interpret` so the kernel and
    this router can never disagree."""
    from repro.kernels.compose import default_interpret

    return not default_interpret()


def compose(basis: Array, reduced_coeff: Array, p: int, spec: CompositionSpec,
            *, backend: str | None = None) -> Array:
    """Compose the p-width weight:  v · û  →  reshape  (Fig. 1).

    Args:
      basis: ``(ksq, I, R)``.
      reduced_coeff: ``(m, R, O)`` — the gathered blocks (m = p^2 for
        "square" mode, p for anchored modes).
      p: target width.
      backend: ``"einsum"`` (reference), ``"pallas"`` (the
        :mod:`repro.kernels.compose` kernel, interpret-gated per
        platform), or ``None`` — pallas on TPU, einsum elsewhere.

    Returns:
      the ``spec.weight_shape(p)`` weight.  For "square" the intermediate
      ``(ksq, I, p^2·O)`` tensor is viewed as ``(ksq, I, p, p·O)`` and the
      first ``p`` axis merges with ``I`` (the paper's reshape).  An expert
      bank (basis ``(E, I, R)``, blocks ``(m, E, R, O)``) composes every
      expert in one call, the expert axis in place of ``ksq``.
    """
    m = spec.blocks_for_width(p)
    if reduced_coeff.shape[0] != m:
        raise ValueError(f"expected {m} blocks, got {reduced_coeff.shape[0]}")
    if backend is None:
        backend = "pallas" if _pallas_compose_default() else "einsum"
    bank = spec.experts > 1
    if backend == "pallas":
        from repro.kernels.compose import compose_pallas

        if bank:
            # the kernel's batched form, one expert per leading row:
            # (E, 1, I, R) x (E, m, R, O) -> (E, 1, I, m*O)
            flat = compose_pallas(basis[:, None],
                                  jnp.swapaxes(reduced_coeff, 0, 1))[:, 0]
        else:
            flat = compose_pallas(basis, reduced_coeff)  # (ksq, I, m*O)
        inter = flat.reshape(flat.shape[0], flat.shape[1], m, -1)
    elif backend == "einsum":
        # (ksq, I, R) x (m, R, O) -> (ksq, I, m, O); a bank's expert axis
        # takes ksq's place
        inter = (jnp.einsum("eir,mero->eimo", basis, reduced_coeff) if bank
                 else jnp.einsum("kir,mro->kimo", basis, reduced_coeff))
    else:
        raise ValueError(f"unknown compose backend {backend!r}")
    ksq, I, _, O = inter.shape
    if spec.mode == "grow_out":
        return inter.reshape(ksq, I, m * O)
    if spec.mode == "grow_in":
        return jnp.transpose(inter, (0, 2, 1, 3)).reshape(ksq, m * I, O)
    # (ksq, I, p, p, O) -> (ksq, p, I, p, O) -> (ksq, pI, pO)
    inter = inter.reshape(ksq, I, p, p, O)
    w = jnp.transpose(inter, (0, 2, 1, 3, 4)).reshape(ksq, p * I, p * O)
    return w


def compose_flops(p: int, spec: CompositionSpec) -> int:
    """MACs*2 for the compose contraction at width p (every expert of a
    bank)."""
    m = spec.blocks_for_width(p)
    return 2 * spec.lead * spec.base_in * spec.rank * m * spec.base_out


# ---------------------------------------------------------------------------
# Rank-space application: y = x · (v·û) computed as (x·v)·û
# ---------------------------------------------------------------------------


def _coeff_blocks(reduced_coeff: Array, p: int, spec: CompositionSpec) -> Array:
    m = spec.blocks_for_width(p)
    if reduced_coeff.shape[-3] != m:
        raise ValueError(f"expected {m} blocks, got {reduced_coeff.shape[-3]}")
    if spec.mode == "square":
        # block a*p+b: a = input-group, b = output-group (the compose
        # reshape in :func:`compose`) -> (p, p, R, O)
        return reduced_coeff.reshape(
            reduced_coeff.shape[:-3] + (p, p) + reduced_coeff.shape[-2:])
    return reduced_coeff


def apply_factors(x: Array, basis: Array, reduced_coeff: Array, p: int,
                  spec: CompositionSpec, mode: str = "dense", *,
                  stride: int = 1, fused: bool = True) -> Array:
    """Apply the factorized weight to ``x`` *without materialising it*.

    Exploits ``w = v·û``: instead of composing the ``(ksq, pI, pO)``
    weight and paying a dense-width contraction, the input is projected
    into rank space through the basis (I → R per input group) and the
    cheap coefficient contraction finishes the job (R → pO).  With
    R below the composed channel widths this cuts the per-application
    FLOPs roughly ``pI/R``-fold — the low-rank trick dense-slice
    width scaling (HeteroFL/AnycostFL) cannot exploit.

    Args:
      x: ``mode="dense"``: ``(..., pI_total)`` row vectors (``pI_total``
        is ``weight_shape(p)[1]``).  ``mode="conv"``: ``(N, H, W, C)``
        NHWC activations with ``C = weight_shape(p)[1]``.
      basis: ``(ksq, I, R)``.
      reduced_coeff: ``(m, R, O)`` gathered blocks.
      p: target width.
      spec: the layer's :class:`CompositionSpec`.
      mode: how the weight is applied — ``"dense"`` (matmul, requires
        ``spec.ksq == 1``) or ``"conv"`` (k×k SAME conv: a basis conv
        I→R per input group followed by a 1×1 coefficient contraction
        R→pO, the paper's block reshape folded into the contraction).
      stride: conv stride (``mode="conv"`` only).
      fused: ``mode="conv"`` only — route through the fused
        :func:`repro.kernels.conv_rank.conv_rank_apply` primitive (one
        kernel/formulation, rank intermediate never in HBM, rank-space
        backward).  ``False`` keeps the unfused separate-ops XLA body
        below, retained as the benchmark/parity reference.

    Returns:
      exactly what ``x @ compose(...)`` / ``conv(x, compose(...))``
      returns, up to float re-association.
    """
    if spec.experts > 1:
        raise ValueError("an expert bank is applied composed, by a grouped "
                         "matmul over its routed rows")
    if mode == "dense":
        if spec.ksq != 1:
            raise ValueError("dense apply requires ksq == 1")
        _coeff_blocks(reduced_coeff, p, spec)  # validates the block count
        # the fused custom_vjp primitive: Pallas forward on compiled
        # backends, einsum reference elsewhere; backward stays in rank
        # space either way (kernels/compose.py).
        from repro.kernels.compose import rank_dense_apply

        return rank_dense_apply(x, basis, reduced_coeff, p, spec.mode)
    if mode != "conv":
        raise ValueError(f"unknown apply mode {mode!r}")
    k = int(round(spec.ksq ** 0.5))
    if k * k != spec.ksq:
        raise ValueError(f"conv apply needs square ksq, got {spec.ksq}")
    if fused:
        _coeff_blocks(reduced_coeff, p, spec)  # validates the block count
        from repro.kernels.conv_rank import conv_rank_apply

        return conv_rank_apply(x, basis, reduced_coeff, p, spec.mode,
                               stride=stride)
    # Unfused separate-ops reference: basis conv, then an einsum
    # contraction over the (N, g, Ho, Wo, R) rank intermediate.
    u = _coeff_blocks(reduced_coeff, p, spec)
    vk = basis.reshape(k, k, spec.base_in, spec.rank)
    dn = ("NHWC", "HWIO", "NHWC")
    if spec.mode == "grow_out":
        t = jax.lax.conv_general_dilated(
            x, vk, (stride, stride), "SAME", dimension_numbers=dn)
        y = jnp.einsum("nhwr,bro->nhwbo", t, u)
        return y.reshape(y.shape[:-2] + (p * spec.base_out,))
    # square / grow_in: p input groups share the basis — fold the group
    # axis into the batch so ONE dense conv (N*p, H, W, I) -> R serves
    # every group, then contract groups in rank space.
    N, H, W, _ = x.shape
    xg = x.reshape(N, H, W, p, spec.base_in)
    xg = jnp.transpose(xg, (0, 3, 1, 2, 4)).reshape(N * p, H, W, spec.base_in)
    t = jax.lax.conv_general_dilated(
        xg, vk, (stride, stride), "SAME", dimension_numbers=dn)
    Ho, Wo = t.shape[1], t.shape[2]
    t = t.reshape(N, p, Ho, Wo, spec.rank)
    if spec.mode == "grow_in":
        return jnp.einsum("nahwr,aro->nhwo", t, u)
    y = jnp.einsum("nahwr,abro->nhwbo", t, u)
    return y.reshape(N, Ho, Wo, p * spec.base_out)


def apply_flops(p: int, spec: CompositionSpec, *, applications: int = 1,
                basis_is_gather: bool = False) -> int:
    """MACs*2 of the *rank-space* application per ``applications`` output
    positions (dense row-vectors, or conv output pixels).

    Basis projection: every input group (p for square/grow_in, 1 for
    grow_out) pays ``ksq·I·R``; coefficient contraction: every block
    pays ``R·O``.  ``basis_is_gather`` marks layers whose rank-space
    basis projection is an index lookup rather than a contraction
    (token embeddings gather an R-length basis row per token —
    ``_apply_embed``), costing no MACs: only the R→pO coefficient
    contraction is charged.
    """
    groups = 1 if spec.mode == "grow_out" else p
    basis = 0 if basis_is_gather else (
        spec.ksq * groups * spec.base_in * spec.rank)
    coeff = spec.blocks_for_width(p) * spec.rank * spec.base_out
    return 2 * applications * (basis + coeff)


def dense_apply_flops(p: int, spec: CompositionSpec, *,
                      applications: int = 1) -> int:
    """MACs*2 of applying the *materialised* p-width weight per
    ``applications`` output positions."""
    _, pi, po = spec.weight_shape(p)
    return 2 * applications * spec.ksq * pi * po


def rank_space_wins(p: int, spec: CompositionSpec, *, applications: int,
                    dense_apply_free: bool = False,
                    basis_is_gather: bool = False,
                    overhead: float = 1.0) -> bool:
    """Static FLOPs decision: does rank-space application beat
    materialise-then-apply for one evaluation of the layer?

    ``applications`` is the TOTAL application count per evaluation —
    batch × output positions × any weight *reuse* (a scan-carried RNN
    weight applied T times counts T applications, amortising the one
    compose) — so reuse-heavy layers correctly tilt toward
    materialisation.  ``dense_apply_free`` marks gather-style layers
    (embeddings) whose materialised application costs no FLOPs;
    ``basis_is_gather`` marks the same layers' rank path, whose basis
    projection is also a gather (see :func:`apply_flops`) — for an
    embedding both hold, and the contest reduces to the R→pO
    coefficient contraction per token vs the one-off vocab-sized
    compose, so rank space wins exactly when the token count is below
    the vocabulary size.

    ``overhead`` scales the rank-space side: callers fold in measured
    per-platform costs the FLOPs model cannot see (the conv rank path's
    extra group-batched conv + contraction ops, which dominate on
    op-overhead-bound CPU hosts — see ``conv_rank_overhead``).
    """
    dense = 0 if dense_apply_free else dense_apply_flops(
        p, spec, applications=applications)
    rank = apply_flops(p, spec, applications=applications,
                       basis_is_gather=basis_is_gather)
    return overhead * rank < compose_flops(p, spec) + dense


def conv_rank_overhead(calibration=None) -> float:
    """Effective cost multiplier of the conv rank path on this host.

    Formerly a hardcoded platform constant (3.0 on CPU — calibrated
    against the *unfused* separate-ops rank path, which disabled the
    conv rank path everywhere on CPU including shapes where it wins).
    Now the fused :mod:`repro.kernels.conv_rank` primitive is measured
    directly: the value comes from the per-process micro-calibration in
    :mod:`repro.core.calibration` (or an ``FLConfig`` override threaded
    through as ``calibration``), so ``auto`` enables the conv rank path
    exactly where this host's measurement says it is faster,
    extrapolated by FLOPs elsewhere.
    """
    if calibration is not None:
        return float(calibration.conv_rank_overhead)
    from repro.core.calibration import get_calibration

    return float(get_calibration().conv_rank_overhead)


def decompose(
    weight: Array, basis: Array, p: int, spec: CompositionSpec
) -> Array:
    """Least-squares projection of a materialised p-width weight back onto
    the span of ``basis``:  û* = argmin_û ‖v·û − w‖²  (per ksq slice).

    Used only by parity experiments / materialised baselines — the default
    factorized training path never needs it (paper Alg. 2 line 10 is an
    identity there because the factors *are* the parameters).

    Returns ``(p^2, R, O)`` reduced-coefficient blocks.
    """
    ksq, pI, pO = weight.shape
    I, O = spec.base_in, spec.base_out
    if (pI, pO) != (p * I, p * O):
        raise ValueError("weight shape inconsistent with width/spec")
    # invert the compose reshape: (ksq, p, I, p, O) -> (ksq, I, p*p, O)
    w = weight.reshape(ksq, p, I, p, O).transpose(0, 2, 1, 3, 4)
    w = w.reshape(ksq, I, p * p * O)
    # flatten basis over (ksq, I): A (ksq*I, R), B (ksq*I, m*O)
    A = basis.reshape(ksq * I, spec.rank)
    B = w.reshape(ksq * I, p * p * O)
    sol, *_ = jnp.linalg.lstsq(A, B)
    # (R, p*p*O) -> (p*p, R, O)
    return sol.reshape(spec.rank, p * p, O).transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# Model-level composition plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One factorized weight inside a model: its spec and parameter names."""

    name: str
    spec: CompositionSpec


class CompositionPlan:
    """The set of factorized weights in a model plus shared block counters.

    Heroes tracks one update-times counter vector per factorized weight; all
    weights in a model share the *same* width assignment ``p_n`` per client,
    so we keep a single global counter (the paper's ``c_i``) of size ``P^2``
    and reuse the block indices for every layer.  This matches Fig. 1/3
    where block selection is described once for the whole model.
    """

    def __init__(self, layers: Dict[str, CompositionSpec], max_width: int):
        ps = {s.max_width for s in layers.values()}
        if ps != {max_width}:
            raise ValueError(f"all layer specs must share max_width={max_width}, got {ps}")
        self.layers = dict(layers)
        self.max_width = max_width
        self.num_blocks = max_width * max_width

    def init(self, key: Array, dtype: Any = jnp.float32) -> Dict[str, Dict[str, Array]]:
        params = {}
        keys = jax.random.split(key, len(self.layers))
        for k, (name, spec) in zip(keys, sorted(self.layers.items())):
            v, u = init_factors(k, spec, dtype)
            params[name] = {"basis": v, "coeff": u}
        return params

    def reduce(self, params, block_ids) -> Dict[str, Dict[str, Array]]:
        """Ship-to-client view: full basis + gathered coefficient blocks.

        ``block_ids`` come from the shared ``P^2`` counter, so they are
        only valid for "square" layers; anchored-mode layers hold ``P``
        blocks and need their own id set.  Ids are validated against
        each layer's ``spec.num_blocks`` — ``jnp.take`` would otherwise
        clamp out-of-range ids silently and gather the wrong block.
        """
        ids = np.asarray(block_ids)
        out = {}
        for name, spec in self.layers.items():
            if ids.size and (ids.min() < 0 or ids.max() >= spec.num_blocks):
                raise ValueError(
                    f"layer {name!r} ({spec.mode}) has {spec.num_blocks} "
                    f"blocks but got ids in [{ids.min()}, {ids.max()}] — "
                    "anchored layers need their own id set, not the "
                    "shared P^2-counter ids")
            out[name] = {
                "basis": params[name]["basis"],
                "coeff": gather_blocks(params[name]["coeff"], ids),
            }
        return out

    def compose_all(self, reduced_params, p: int) -> Dict[str, Array]:
        """Materialise every layer weight at width p from reduced factors."""
        return {
            name: compose(reduced_params[name]["basis"], reduced_params[name]["coeff"], p, spec)
            for name, spec in self.layers.items()
        }

    def traffic_bytes(self, p: int, bytes_per_param: int = 4) -> int:
        """Upload/download payload for a width-p client (basis + blocks)."""
        return bytes_per_param * sum(
            spec.params_factorized(p) for spec in self.layers.values()
        )

    def materialized_bytes(self, p: int, bytes_per_param: int = 4) -> int:
        return bytes_per_param * sum(
            spec.params_materialized(p) for spec in self.layers.values()
        )
