"""Client-side procedure (paper Alg. 2).

A client receives (basis, reduced coefficient, tau), runs tau local SGD
iterations over its data directly on the factors, estimates
(L, sigma^2, G^2) and returns updated tensors + estimates.  How each
layer weight is *applied* inside the loss is the ``forward_impl`` knob:
composed first (``materialize`` — the historical bitwise path) or
contracted in rank space without ever building the p-width weight
(``rank_space`` / the FLOPs-driven ``auto`` default); see
``FLModelDef.prepare_weights`` and docs/ENGINE.md "Rank-space client
compute".
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimator
from repro.data.streaming import draw_batch_indices
from repro.fl.models import FLModelDef
from repro.obs.recorder import NOOP

Array = jax.Array


def data_batch(model: FLModelDef, x, y, idx) -> Dict[str, Array]:
    return {model.input_key: jnp.asarray(x[idx]),
            "labels": jnp.asarray(y[idx])}


def _ce(logits: Array, labels: Array) -> Array:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


class ClientStep(NamedTuple):
    """One client step's programs, each a lazy ``jax.jit`` wrapper (it
    compiles on its first call), all over the one ``loss_fn``.  Every
    program also returns the forward's device-side counts
    (``FLModelDef.forward_stats``; an empty dict, so no output at all,
    for a model that counts nothing)."""

    loss: Callable      # (params, batch) -> (loss, stats)
    grad: Callable      # (params, batch) -> (grads, stats)
    sgd: Callable       # (params, batch, lr) -> (params', stats)
    # (params, anchor, batch, lr, mu) -> (params', stats): the FedProx
    # step on f(w) + (mu/2) ||w - anchor||^2
    prox: Callable
    # (params0, params, batches) -> (estimates, stats): the
    # (L, sigma^2, G^2) dict, and the four gradients' counts in call
    # order (three at params0, then one at params)
    estimate: Callable


@functools.lru_cache(maxsize=64)
def client_step(model: FLModelDef, width: int, factorized: bool,
                forward_impl: str = "auto", calibration=None) -> ClientStep:
    """The client step's programs, cached per key.

    Keyed on the model *instance* (FLModelDef hashes by identity): a
    string registry key would drop constructor kwargs that are not part
    of the encoding (e.g. ``in_ch``), silently training the wrong model.
    ``calibration`` (a frozen RankPathCalibration, or None = the
    per-process measurement) joins the key so two configs with
    different cost-model overrides never share impl choices.
    """

    def loss_fn(params, batch):
        w = (model.prepare_weights(params, width, batch, forward_impl,
                                   calibration)
             if factorized else {k: v for k, v in params.items()})
        logits, stats = model.forward_with_stats(w, width, batch)
        return _ce(logits, batch["labels"]), stats

    grad_fn = jax.grad(loss_fn, has_aux=True)
    grad_jit = jax.jit(grad_fn)

    @jax.jit
    def sgd_step(params, batch, lr):
        g, stats = grad_fn(params, batch)
        return (jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g),
                stats)

    @jax.jit
    def prox_step(params, anchor, batch, lr, mu):
        g, stats = grad_fn(params, batch)
        return (jax.tree_util.tree_map(
            lambda p, a, gg: p - lr * (gg + mu * (p - a)), params, anchor, g),
            stats)

    @jax.jit
    def estimate(params0, params, batches):
        stats, done = [], []

        def grad(p, b):
            # one gradient at a time: each waits for the one before, so
            # no two hold their activations at once (left to overlap, the
            # DeepSeek cell's width-4 program needs 15.5 GB of
            # temporaries on a v5e, chained 6.5)
            if done:
                p, b, _ = jax.lax.optimization_barrier((p, b, done[-1]))
            g, s = grad_jit(p, b)
            stats.append(s)
            done.append(g)
            return g

        est = estimator.client_estimates(grad, params0, params, batches)
        return est, stats

    return ClientStep(jax.jit(loss_fn), grad_jit, sgd_step, prox_step,
                      estimate)


def _sum_stats(forward: list, backward: list) -> Dict[str, int]:
    """A client-round's counts from the per-program ``stats`` of its
    forward-only (``forward``) and forward-and-backward (``backward``)
    calls, all read back in one ``device_get``: each key summed over
    every call, and again as ``backward.<key>`` over the second kind
    alone; a vector key (a per-expert load) is summed entrywise and
    reported as its largest entry, ``<key>_max``."""
    fwd, bwd = jax.device_get((forward, backward))
    sums: Dict[str, np.ndarray] = {}
    for part, prefixes in ((fwd, ("",)), (bwd, ("", "backward."))):
        for stats in part:
            for k, v in stats.items():
                for pre in prefixes:
                    sums[pre + k] = sums.get(pre + k, 0) + np.asarray(
                        v, np.int64)
    return {(k + "_max" if np.ndim(v) else k): int(np.max(v))
            for k, v in sums.items()}


@dataclasses.dataclass
class ClientResult:
    params: Any  # updated reduced factors (or dense sub-weights)
    estimates: Dict[str, float]
    loss_before: float
    loss_after: float
    # the forward's device-side counts over the client-round (see
    # ``_sum_stats``); read back only when telemetry is on
    stats: Dict[str, int] = dataclasses.field(default_factory=dict)

    def host_params(self, obs=NOOP) -> Any:
        """Params as a host (numpy) pytree, for consumers that need one.

        The trainers hand ``params`` over device-resident: the trained
        pytree the client step produced (sequential and proximal
        trainers), or a lazy view into a mesh-sharded cohort stack, which
        this materializes.  A device pytree is copied to the host under
        ``trainer.pull`` (see :func:`pull_to_host`); a numpy tree, as a
        checkpoint restores it, passes through.
        """
        mat = getattr(self.params, "materialize", None)
        if mat is not None:
            return mat()
        if any(isinstance(v, jax.Array)
               for v in jax.tree_util.tree_leaves(self.params)):
            return pull_to_host(self.params, obs)
        return self.params


def pull_to_host(params, obs=NOOP):
    """``params`` copied to the host under ``trainer.pull``, adding their
    bytes (array metadata, read before the copy) to ``trainer.d2h_bytes``
    and to the span."""
    nbytes = (sum(int(v.nbytes) for v in jax.tree_util.tree_leaves(params))
              if obs.enabled else 0)
    with obs.wall_span("trainer.pull", d2h_bytes=nbytes):
        host = jax.device_get(params)
    obs.counter_add("trainer.d2h_bytes", nbytes)
    return host


def local_train(
    model: FLModelDef,
    reduced_params: Any,
    width: int,
    tau: int,
    x, y,
    lr: float,
    rng: np.random.Generator,
    batch_size: int = 16,
    factorized: bool = True,
    estimate: bool = True,
    forward_impl: str = "auto",
    calibration=None,
    obs=NOOP,
    prox_mu: Optional[float] = None,
) -> ClientResult:
    """tau local SGD iterations (Alg. 2 lines 4-9).

    ``forward_impl`` selects the factorized compute path (see
    ``FLConfig.forward_impl``): ``"materialize"`` reproduces the
    historical compose-then-apply updates bitwise; ``"auto"`` (default)
    applies factors in rank space wherever the measured cost model says
    it is cheaper (``calibration`` carries an FLConfig override; None =
    the per-process measurement).  Ignored when ``factorized=False``.
    The minibatch indices come from ``rng`` in the order of
    :func:`~repro.data.streaming.draw_batch_indices`.  With ``prox_mu``
    every step is the FedProx step anchored at ``reduced_params``.
    ``obs`` records the ``trainer.sgd``, ``trainer.loss`` and
    ``trainer.estimate`` wall spans (nothing with the default no-op);
    with it on, the forward's counts of every program the client ran
    come back in ``ClientResult.stats``, read after the estimates.  The
    estimates run as one compiled program (``ClientStep.estimate``) and
    come back in one read.
    """
    step = client_step(model, width, factorized, forward_impl, calibration)
    params0 = reduced_params
    params = params0
    n = len(y)
    idx, est_idx = draw_batch_indices(rng, n, max(tau, 1),
                                      min(batch_size, n), estimate)
    first_batch = None
    fwd_stats, bwd_stats = [], []
    with obs.wall_span("trainer.sgd"):
        for i in idx:
            batch = data_batch(model, x, y, i)
            if first_batch is None:
                first_batch = batch
            if prox_mu is None:
                params, stats = step.sgd(params, batch, lr)
            else:
                params, stats = step.prox(params, params0, batch, lr,
                                          prox_mu)
            bwd_stats.append(stats)

    est = {}
    with obs.wall_span("trainer.loss"):
        loss_b, stats_b = step.loss(params0, first_batch)
        loss_a, stats_a = step.loss(params, first_batch)
        fwd_stats += [stats_b, stats_a]
        loss_b, loss_a = (float(v) for v in jax.device_get((loss_b, loss_a)))
    if estimate:
        with obs.wall_span("trainer.estimate", compiled=1):
            batches = [data_batch(model, x, y, i) for i in est_idx]
            est, stats = step.estimate(params0, params, batches)
            bwd_stats += stats
            est = {k: float(v) for k, v in jax.device_get(est).items()}
        obs.counter_add("trainer.estimate_compiled_clients")
    counted = (_sum_stats(fwd_stats, bwd_stats)
               if obs.enabled and model.forward_stats is not None else {})
    return ClientResult(params, est, loss_b, loss_a, counted)
