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
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimator
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


@functools.lru_cache(maxsize=64)
def _jitted_fns(model: FLModelDef, width: int, factorized: bool,
                forward_impl: str = "auto", calibration=None):
    # Keyed on the model *instance* (FLModelDef hashes by identity): the
    # old string registry key dropped constructor kwargs that are not part
    # of the encoding (e.g. ``in_ch``), silently training the wrong model.
    # ``calibration`` (a frozen RankPathCalibration, or None = the
    # per-process measurement) joins the key so two configs with
    # different cost-model overrides never share impl choices.

    def loss_fn(params, batch):
        w = (model.prepare_weights(params, width, batch, forward_impl,
                                   calibration)
             if factorized else {k: v for k, v in params.items()})
        logits = model.forward(w, width, batch)
        return _ce(logits, batch["labels"])

    grad_fn = jax.jit(jax.grad(loss_fn))
    loss_jit = jax.jit(loss_fn)

    @jax.jit
    def sgd_step(params, batch, lr):
        g = jax.grad(loss_fn)(params, batch)
        return jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g)

    return loss_jit, grad_fn, sgd_step


@dataclasses.dataclass
class ClientResult:
    params: Any  # updated reduced factors (or dense sub-weights)
    estimates: Dict[str, float]
    loss_before: float
    loss_after: float

    def host_params(self) -> Any:
        """Params as a host pytree.

        Usually ``params`` itself (the numpy contract); a mesh-sharded
        cohort trainer hands the collective backend lazy device-resident
        slices instead, and this materializes them.
        """
        mat = getattr(self.params, "materialize", None)
        return mat() if mat is not None else self.params


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
) -> ClientResult:
    """tau local SGD iterations (Alg. 2 lines 4-9).

    ``forward_impl`` selects the factorized compute path (see
    ``FLConfig.forward_impl``): ``"materialize"`` reproduces the
    historical compose-then-apply updates bitwise; ``"auto"`` (default)
    applies factors in rank space wherever the measured cost model says
    it is cheaper (``calibration`` carries an FLConfig override; None =
    the per-process measurement).  Ignored when ``factorized=False``.
    ``obs`` records the ``trainer.sgd``, ``trainer.loss`` and
    ``trainer.estimate`` wall spans (nothing with the default no-op).
    """
    loss_jit, grad_fn, sgd_step = _jitted_fns(model, width, factorized,
                                              forward_impl, calibration)
    params0 = reduced_params
    params = params0
    n = len(y)
    first_batch = None
    with obs.wall_span("trainer.sgd"):
        for _ in range(max(tau, 1)):
            idx = rng.integers(0, n, min(batch_size, n))
            batch = data_batch(model, x, y, idx)
            if first_batch is None:
                first_batch = batch
            params = sgd_step(params, batch, lr)

    est = {}
    with obs.wall_span("trainer.loss"):
        loss_b = float(loss_jit(params0, first_batch))
        loss_a = float(loss_jit(params, first_batch))
    if estimate:
        with obs.wall_span("trainer.estimate"):
            batches = [
                data_batch(model, x, y,
                           rng.integers(0, n, min(batch_size, n)))
                for _ in range(3)
            ]
            est = estimator.client_estimates(
                lambda p, b: grad_fn(p, b), params0, params, batches
            )
            est = {k: float(v) for k, v in est.items()}
    return ClientResult(params, est, loss_b, loss_a)
