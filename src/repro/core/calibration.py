"""Measured one-shot calibration of the rank-path dispatch model.

The ``auto`` forward-impl choice compares FLOPs (``apply_flops`` vs
``compose_flops + dense_apply_flops``), but FLOPs alone mispredict on
op-overhead-bound hosts: the conv rank path splits one conv into a
basis conv plus a contraction, and a CPU pays per-op dispatch the FLOPs
model cannot see.  Earlier revisions hardcoded that as a platform
constant (``conv_rank_overhead() == 3.0`` on CPU), which disabled the
conv rank path everywhere on CPU — including shapes where the fused
formulation actually wins.

This module replaces the constant with a **measured** calibration: once
per process (cached), two micro-benchmarks time the real production
paths at representative engine shapes and convert the ratio into the
two numbers the cost model consumes:

``conv_rank_overhead``
    effective cost multiplier of the fused conv rank path relative to
    its FLOPs count, measured as ``(t_rank / t_mat) / (f_rank /
    f_mat)`` at the square hidden-conv shape.  With this definition the
    dispatch inequality ``overhead * rank_flops < compose + mat_flops``
    reduces to *measured-faster at the calibration shape* and
    extrapolates by FLOPs elsewhere.

``fused_compose_gain``
    ``t_fused / t_separate`` for the fused compose+apply dense kernel
    vs compose-then-matmul at the classifier-head shape; values below
    1.0 let ``auto`` swap materialize-path dense layers to the fused
    primitive (the p-width weight then lives only in registers/VMEM).

Both numbers are overridable through ``FLConfig`` (``conv_rank_overhead``
/ ``fused_compose_gain`` > 0 pin them; see :func:`from_config`) — the
override participates in the client/trainer jit-cache keys, so two
engines with different pins never share stale impl choices.

The measurement costs a few jit compiles (~1-2 s) the first time an
``auto`` dispatch needs it; ``materialize`` / ``rank_space`` runs never
trigger it.  Within a process the cached result keeps every trace's
impl choice stable.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax

__all__ = ["RankPathCalibration", "measure", "get_calibration",
           "from_config", "for_dispatch"]


@dataclasses.dataclass(frozen=True)
class RankPathCalibration:
    """The two measured knobs the auto cost model consumes.

    Frozen + hashable on purpose: instances ride in the client/trainer
    jit-cache keys (``client.client_step``, ``trainers._cohort_fns``),
    so a config override can never reuse a cache entry compiled under a
    different calibration.
    """

    conv_rank_overhead: float
    fused_compose_gain: float
    platform: str = "cpu"
    measured: bool = False


# Representative engine shapes: the square hidden conv every image model
# repeats (resnet blocks / cnn conv2), and the grow_in classifier head.
_CONV_SHAPE = dict(p=2, n=16, hw=8, base=8, rank=8, k=3, stride=1)
_DENSE_SHAPE = dict(p=2, m=32, base_in=8, base_out=10, rank=8)

# sanity clips: a wildly skewed measurement (loaded box, timer glitch)
# degrades to a conservative gate instead of poisoning every dispatch
_OVERHEAD_CLIP = (0.25, 32.0)
_GAIN_CLIP = (0.25, 4.0)


def _best_times(fns, args, reps: int = 30, warmup: int = 5) -> list[float]:
    """Min-of-reps wall time per fn, legs interleaved within each rep.

    Min is the least-interference estimate of an op's cost (medians
    drag scheduler noise into the ratio on a shared CI/edge host), and
    interleaving means load drift hits every leg equally instead of
    biasing whichever ran last.
    """
    for fn in fns:
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
    best = [float("inf")] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best[i] = min(best[i], time.perf_counter() - t0)
    return best


def _measure_conv_overhead() -> float:
    from repro.core.composition import (CompositionSpec, apply_factors,
                                        apply_flops, compose, compose_flops,
                                        dense_apply_flops)

    c = _CONV_SHAPE
    p, n, hw, base, rank, k, stride = (c["p"], c["n"], c["hw"], c["base"],
                                       c["rank"], c["k"], c["stride"])
    spec = CompositionSpec(p, rank, base, base, ksq=k * k, mode="square")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (n, hw, hw, p * base))
    v = 0.1 * jax.random.normal(ks[1], spec.basis_shape())
    u = 0.1 * jax.random.normal(ks[2], spec.coefficient_shape())

    rank_fn = jax.jit(lambda x, v, u: apply_factors(
        x, v, u, p, spec, "conv", stride=stride))

    def mat(x, v, u):
        w = compose(v, u, p, spec, backend="einsum")
        w4 = w.reshape(k, k, w.shape[1], w.shape[2])
        return jax.lax.conv_general_dilated(
            x, w4, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    mat_fn = jax.jit(mat)
    t_rank, t_mat = _best_times([rank_fn, mat_fn], (x, v, u))

    apps = n * hw * hw  # stride-1 SAME conv: every pixel is an application
    f_rank = apply_flops(p, spec, applications=apps)
    f_mat = compose_flops(p, spec) + dense_apply_flops(
        p, spec, applications=apps)
    overhead = (t_rank / t_mat) / (f_rank / f_mat)
    return float(min(max(overhead, _OVERHEAD_CLIP[0]), _OVERHEAD_CLIP[1]))


def _measure_fused_compose_gain() -> float:
    from repro.core.composition import CompositionSpec, compose
    from repro.kernels.compose import compose_dense_apply

    d = _DENSE_SHAPE
    p, m, bi, bo, rank = (d["p"], d["m"], d["base_in"], d["base_out"],
                          d["rank"])
    spec = CompositionSpec(p, rank, bi, bo, ksq=1, mode="grow_in")
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    # vmap over a cohort of independent (x, v, u) triples: a single head
    # apply is sub-µs and dispatch jitter swamps it — K clients in one
    # call keep the 1:1 compose:apply ratio while amortising dispatch,
    # matching how the ops actually run (inside one jitted client loss).
    K = 32
    x = jax.random.normal(ks[0], (K, m, p * bi))
    v = 0.1 * jax.random.normal(ks[1], (K,) + spec.basis_shape())
    u = 0.1 * jax.random.normal(ks[2], (K,) + spec.coefficient_shape())

    sep_fn = jax.jit(jax.vmap(
        lambda x, v, u: x @ compose(v, u, p, spec, backend="einsum")[0]))
    fus_fn = jax.jit(jax.vmap(
        lambda x, v, u: compose_dense_apply(x, v, u, p, "grow_in")))
    t_sep, t_fus = _best_times([sep_fn, fus_fn], (x, v, u))
    gain = t_fus / t_sep
    return float(min(max(gain, _GAIN_CLIP[0]), _GAIN_CLIP[1]))


def measure() -> RankPathCalibration:
    """Run both micro-benchmarks (uncached — callers want
    :func:`get_calibration`)."""
    return RankPathCalibration(
        conv_rank_overhead=_measure_conv_overhead(),
        fused_compose_gain=_measure_fused_compose_gain(),
        platform=jax.default_backend(),
        measured=True,
    )


@functools.lru_cache(maxsize=1)
def get_calibration() -> RankPathCalibration:
    """The per-process calibration (measured once, then cached — every
    trace in the process sees the same numbers, keeping the auto impl
    choice jit-cache-stable)."""
    return measure()


def from_config(cfg) -> RankPathCalibration:
    """Resolve a calibration from ``FLConfig`` overrides.

    ``cfg.conv_rank_overhead`` / ``cfg.fused_compose_gain`` pin the
    respective knob when > 0; 0 (the default) means *measure*.  Fully
    pinned configs never trigger the micro-benchmarks.
    """
    ovh = float(getattr(cfg, "conv_rank_overhead", 0.0) or 0.0)
    gain = float(getattr(cfg, "fused_compose_gain", 0.0) or 0.0)
    if ovh > 0.0 and gain > 0.0:
        return RankPathCalibration(ovh, gain, jax.default_backend(),
                                   measured=False)
    base = get_calibration()
    if ovh <= 0.0 and gain <= 0.0:
        return base
    return dataclasses.replace(
        base,
        conv_rank_overhead=ovh if ovh > 0.0 else base.conv_rank_overhead,
        fused_compose_gain=gain if gain > 0.0 else base.fused_compose_gain,
    )


def for_dispatch(cfg):
    """The calibration an engine should thread through, or ``None`` when
    the config's dispatch never consults the cost model (non-``auto``
    ``forward_impl``) — materialize / rank_space runs must not trigger
    the micro-benchmarks."""
    if getattr(cfg, "forward_impl", "auto") != "auto":
        return None
    return from_config(cfg)
