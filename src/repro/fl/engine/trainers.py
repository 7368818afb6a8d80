"""Local-training backends.

``SequentialTrainer`` reproduces the legacy per-client loop bitwise: one
:func:`repro.fl.client.local_train` call per client, one jit dispatch per
SGD step.

``CohortTrainer`` is the batched backend: clients sharing a cohort
signature ``(width, effective batch size)`` are stacked on a leading
client axis and trained in ONE compiled ``jax.vmap``-over-clients +
``jax.lax.scan``-over-tau step.  Clients with different tau inside a
cohort are padded to the cohort max and masked (a padded step is a
no-op), so the per-client math is identical to the sequential loop up to
float re-association — the dispatch count per round drops from
``sum_n tau_n`` to one call per cohort.

Minibatch indices are drawn on the host through the engine's
:class:`~repro.data.ClientDataLoader` (``eng.data``) under the exact
per-client RNG stream the sequential path uses
(``default_rng((seed, round, n))``, tau draws then 3 estimate draws),
so the two backends see the same data order.  Shards may be lazy
:class:`~repro.data.ShardView`s — only the touched minibatches are
gathered — and the cohort backend prefetches the next group's host
batches on a background thread while the device runs the current one.

``ProximalTrainer`` is the FedProx local solver: the sequential trainer
with the proximal pull ``mu * (w - w_global)`` added to every SGD step,
so FedProx drops in as a scheme bundle without core changes.

Every backend runs the one client step of :func:`repro.fl.client.
client_step` (loss, gradient, SGD/proximal update, estimates), so a
change to the step is made once.

Result-params contract: backends return *host-resident* (numpy) param
trees — the collective aggregation backend (repro.fl.engine.collective)
uploads each client's blocks and scatters them into the dense
zero-padded contributions on the device.  The one exception is the
mesh-sharded cohort path feeding the collective backend: there the
trained stack stays *device-resident* on the cohort axis
(``ClientResult.params`` is a lazy
:class:`~repro.fl.engine.collective.CohortSlice``) and the merge
consumes it without a gather/rescatter; ``ClientResult.host_params()``
recovers the numpy tree everywhere else.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding

from repro.core.calibration import for_dispatch
from repro.data.streaming import stack_client_shards
from repro.fl import client as client_lib
from repro.fl.client import ClientResult
from repro.fl.engine.base import Assignment, LocalTrainer
from repro.fl.engine.collective import CohortSlice, CohortStack
from repro.fl.models import FLModelDef
from repro.sharding import fl as flsh


def _cache_size(fn) -> Optional[int]:
    """Compiled-signature count of a ``jax.jit`` wrapper, when this jax
    exposes it (None otherwise — telemetry then skips recompile
    accounting instead of guessing)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


def _count_recompiles(obs, fn, before: Optional[int], **labels) -> None:
    """Credit ``trainer.jit_recompiles`` with the cache growth of ``fn``
    since ``before`` (a ``_cache_size`` snapshot taken pre-call)."""
    if before is None:
        return
    after = _cache_size(fn)
    if after is not None and after > before:
        obs.counter_add("trainer.jit_recompiles", after - before, **labels)


def _pull(obs, params):
    """Trained params to the host under ``trainer.pull``, adding their
    bytes (array metadata, read before the copy) to
    ``trainer.d2h_bytes`` and to the span."""
    nbytes = (sum(int(v.nbytes) for v in jax.tree_util.tree_leaves(params))
              if obs.enabled else 0)
    with obs.wall_span("trainer.pull", d2h_bytes=nbytes):
        host = jax.device_get(params)
    obs.counter_add("trainer.d2h_bytes", nbytes)
    return host


class SequentialTrainer(LocalTrainer):
    """One ``local_train`` call per client (legacy-equivalent backend).

    Result params are pulled to the host (numpy) — the contract shared
    with :class:`CohortTrainer` — and the collective merge uploads each
    client's blocks and scatters them on the device.
    """

    name = "sequential"  # the ``trainer`` label of trainer.jit_recompiles

    def prox_mu(self) -> Optional[float]:
        """The proximal coefficient of every step; None = plain SGD."""
        return None

    def train_all(self, state, assigns: Dict[int, Assignment],
                  ) -> Dict[int, ClientResult]:
        eng = self.eng
        obs = eng.obs
        cal = for_dispatch(eng.cfg)
        mu = self.prox_mu()
        out = {}
        for n, a in assigns.items():
            with obs.wall_span("trainer.client_params", client=int(n)):
                params = eng.aggregator.client_params(state, n, a)
            before = None
            if obs.enabled:
                # the lru-cached lookup local_train makes
                step = client_lib.client_step(
                    eng.model, a["width"], eng.factorized,
                    eng.cfg.forward_impl, cal)
                program = step.sgd if mu is None else step.prox
                before = _cache_size(program)
            with obs.wall_span("trainer.local_train", client=int(n),
                               width=int(a["width"]),
                               tau=int(a["tau"])) as span:
                x, y = eng.data.shard(n)
                res = client_lib.local_train(
                    eng.model, params, a["width"], a["tau"], x, y,
                    eng.cfg.lr,
                    np.random.default_rng((eng.cfg.seed, state.round, n)),
                    eng.cfg.batch_size, factorized=eng.factorized,
                    estimate=eng.estimate,
                    forward_impl=eng.cfg.forward_impl,
                    calibration=cal, obs=obs, prox_mu=mu,
                )
                if res.stats:
                    # the forward's counts (the expert model's moe.*) ride
                    # on the span and add to the counters of their names
                    span.attrs.update(res.stats)
                    for k, v in res.stats.items():
                        if not k.startswith("backward."):
                            obs.counter_add(k, v)
            if obs.enabled:
                _count_recompiles(obs, program, before, trainer=self.name,
                                  width=int(a["width"]))
            out[n] = ClientResult(_pull(obs, res.params), res.estimates,
                                  res.loss_before, res.loss_after)
        return out


class ProximalTrainer(SequentialTrainer):
    """FedProx local solver: SGD on ``f(w) + (mu/2) ||w - w_global||^2``.

    The sequential trainer with every step anchored at the received
    global view (``ClientStep.prox``): the same spans, RNG contract and
    compiled estimates, and ``mu = 0`` reproduces FedAvg's local updates
    bitwise.  ``mu`` defaults to ``FLConfig.prox_mu``.
    """

    name = "proximal"

    def __init__(self, mu: Optional[float] = None):
        self._mu = mu

    def prox_mu(self) -> float:
        return self.eng.cfg.prox_mu if self._mu is None else self._mu


@functools.lru_cache(maxsize=32)
def _cohort_fns(model: FLModelDef, width: int, factorized: bool, mesh=None,
                forward_impl: str = "auto", calibration=None):
    """Compiled cohort functions, keyed on the model instance identity.

    With ``mesh`` (a 1-D cohort mesh from :func:`repro.sharding.fl.
    cohort_mesh`) the vmap+scan step runs under ``shard_map`` with the
    client axis laid out on ``COHORT_AXIS``: every device trains its
    contiguous client shard independently (local updates need no
    collectives), so per-client math is identical to the single-device
    form and the trained params come back sharded over the same axis the
    collective merge consumes.

    ``forward_impl`` selects the factorized client compute path
    (``FLConfig.forward_impl``): with ``"auto"``/``"rank_space"`` the
    per-client loss applies factors in rank space — under the client
    vmap the rank contractions batch over the cohort axis exactly like
    the dense ops, so the whole stacked cohort shares the cheaper
    path in the ONE compiled call.

    The step is the sequential trainer's (:func:`repro.fl.client.
    client_step`): the same loss, SGD update and estimate program,
    vmapped over the client axis; the forward's counts are dropped."""
    step = client_lib.client_step(model, width, factorized, forward_impl,
                                  calibration)

    def train(stacked, batches, taus, lr):
        """Unrolled tau steps, vmap over the client axis — one compiled call.

        stacked: params pytree with leading client axis C.
        batches: batch pytree with leading (tau_pad, C, B, ...).
        taus:    (C,) — steps beyond a client's tau keep its params.

        ``unroll=True`` emits straight-line code instead of an XLA while
        loop: on CPU, ops inside a while body lose intra-op thread
        parallelism, which measures ~2.5x slower per step.  Also returns
        the first-batch loss before/after so a round needs no extra
        dispatches.
        """

        def body(params, xs):
            t, batch = xs
            new, _ = jax.vmap(lambda p, b: step.sgd(p, b, lr))(params, batch)
            keep = t < taus
            params = jax.tree_util.tree_map(
                lambda nw, old: jnp.where(
                    keep.reshape(keep.shape + (1,) * (nw.ndim - 1)), nw, old),
                new, params)
            return params, None

        tau_pad = jax.tree_util.tree_leaves(batches)[0].shape[0]
        final, _ = jax.lax.scan(body, stacked, (jnp.arange(tau_pad), batches),
                                unroll=True)
        # zero the masked-clone rows (tau == 0): nobody consumes them
        # per-client, and zero rows are exactly the client-axis padding
        # the collective merge expects — so a device-resident stack can
        # feed the merge unchanged.  Real rows pass through bitwise.
        live = taus > 0
        final = jax.tree_util.tree_map(
            lambda v: jnp.where(
                live.reshape(live.shape + (1,) * (v.ndim - 1)), v, 0), final)
        first = jax.tree_util.tree_map(lambda v: v[0], batches)
        loss_b, _ = jax.vmap(step.loss)(stacked, first)
        loss_a, _ = jax.vmap(step.loss)(final, first)
        return final, loss_b, loss_a

    def estimates(params0, params_t, est_batches):
        """(L, sigma^2, G^2) per client; est_batches leading (C, 3, B, ...)."""

        def per_client(p0, pt, eb):
            bs = [jax.tree_util.tree_map(lambda x, i=i: x[i], eb)
                  for i in range(3)]
            return step.estimate(p0, pt, bs)[0]

        return jax.vmap(per_client)(params0, params_t, est_batches)

    if mesh is None:
        return jax.jit(train), jax.jit(estimates)

    # mesh variant: clients sharded P(COHORT_AXIS), lr replicated, the
    # batch pytree sharded on its client axis (position 1: (tau, C, B)).
    # Specs are pytree prefixes, so one spec covers each whole subtree.
    # The bodies hold no collective, so there is no cross-device value
    # whose variance along the axis needs checking; the check would
    # reject scans whose initial carry is a constant (the flash
    # attention accumulator) while the updated carry varies per device.
    cs, rs = flsh.contribution_spec(), flsh.replicated_spec()
    bs = flsh.client_axis_spec(1)
    train_sh = shard_map(train, mesh=mesh, in_specs=(cs, bs, cs, rs),
                         out_specs=(cs, cs, cs), check_vma=False)
    est_sh = shard_map(estimates, mesh=mesh, in_specs=(cs, cs, cs),
                       out_specs=cs, check_vma=False)
    return jax.jit(train_sh), jax.jit(est_sh)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class CohortTrainer(LocalTrainer):
    """Batched cohort backend: vmap over clients, unrolled tau steps.

    Shape bucketing keeps recompilation bounded when assignments vary
    round-to-round (Heroes): the client count is padded to the next power
    of two with masked clones (unless the group is the recurring
    full-cohort shape) and tau is padded to the next power of two when
    clients disagree (padded steps are masked no-ops).

    On a multi-device host the client axis is sharded over the 1-D
    cohort mesh (``FLConfig.trainer_mesh_devices``; the same axis the
    collective merge rides): batches are staged as per-device host
    shards, every device trains its contiguous client slice in the one
    compiled call, and — when the collective aggregation backend is
    active — the trained params stay device-resident
    (:class:`~repro.fl.engine.collective.CohortSlice`) so the merge
    consumes them without a gather/rescatter round-trip.
    """

    def setup(self, eng) -> None:
        super().setup(eng)
        self.mesh = flsh.cohort_mesh(
            getattr(eng.cfg, "trainer_mesh_devices", 0))

    def train_all(self, state, assigns: Dict[int, Assignment],
                  ) -> Dict[int, ClientResult]:
        eng = self.eng
        groups: Dict[tuple, List[int]] = {}
        for n, a in assigns.items():
            b_eff = min(eng.cfg.batch_size, eng.data.num_samples(n))
            groups.setdefault((a["width"], b_eff), []).append(n)
        # host batch prep streams through the loader one group ahead of
        # the device step (numpy-only on the worker thread)
        specs = list(groups.items())
        prepared = eng.data.prefetch(
            specs, lambda s: self._prepare_group(state, s[0][1], s[1], assigns))
        results: Dict[int, ClientResult] = {}
        try:
            for ((width, b_eff), ns), prep in zip(specs, prepared):
                results.update(
                    self._train_group(state, width, ns, assigns, prep))
        finally:
            # a failing device step must not abandon the generator with
            # its prefetch worker blocked on the queue (thread leak) —
            # closing it runs the generator's cleanup deterministically
            prepared.close()
        return {n: results[n] for n in assigns}

    def _prepare_group(self, state, b_eff: int, ns: List[int],
                       assigns: Dict[int, Assignment]):
        """Host-side batch staging for one cohort group (numpy only —
        safe to run on the prefetch thread).

        Returns per-device host shard *lists* (one chunk per mesh
        device; a single chunk without a mesh) so the main thread ships
        each chunk straight to its device — the monolithic stacked
        batch never exists when the cohort is sharded.
        """
        # spans land from the prefetch worker thread; the recorder's
        # lock makes that safe
        with self.eng.obs.wall_span("trainer.host_stage", clients=len(ns),
                                    batch=int(b_eff)):
            return self._prepare_group_inner(state, b_eff, ns, assigns)

    def _prepare_group_inner(self, state, b_eff: int, ns: List[int],
                             assigns: Dict[int, Assignment]):
        eng, cfg = self.eng, self.eng.cfg
        taus = [max(assigns[n]["tau"], 1) for n in ns]
        # bucketed padding (bounded recompiles under varying assignments)
        tau_pad = taus[0] if len(set(taus)) == 1 else _next_pow2(max(taus))
        n_real = len(ns)
        c_pad = n_real if n_real == cfg.clients_per_round \
            else _next_pow2(n_real)
        # reconcile the power-of-two bucket with the mesh: the client
        # axis must split evenly over the devices (extra rows are the
        # same masked clones the bucketing already uses)
        c_pad = flsh.pad_cohort(c_pad, self.mesh)
        chunks = self.mesh.devices.size if self.mesh is not None else 1

        xs_steps, ys_steps, xs_est, ys_est = [], [], [], []
        for n, tau in zip(ns, taus):
            # same draw order as the sequential path: tau training
            # batches, then 3 estimate batches (padding steps reuse the
            # last batch — they are masked no-ops in the scan)
            xs, ys, est = eng.data.draw_round(
                n, seed=cfg.seed, rnd=state.round, tau=tau, batch_size=b_eff,
                estimate=eng.estimate, tau_pad=tau_pad)
            xs_steps.append(xs)
            ys_steps.append(ys)
            if est is not None:
                xs_est.append(est[0])
                ys_est.append(est[1])
        for _ in range(c_pad - n_real):  # masked clone clients
            xs_steps.append(xs_steps[0])
            ys_steps.append(ys_steps[0])
            if eng.estimate:
                xs_est.append(xs_est[0])
                ys_est.append(ys_est[0])
        taus_arr = np.zeros((c_pad,), np.int32)
        taus_arr[:n_real] = taus

        xkey = eng.model.input_key
        batches = {  # per chunk: (C', tau_pad, B, ...) -> (tau_pad, C', B, ...)
            xkey: stack_client_shards(xs_steps, chunks, step_leading=True),
            "labels": stack_client_shards(ys_steps, chunks, step_leading=True),
        }
        est_batches = None
        if eng.estimate:
            est_batches = {xkey: stack_client_shards(xs_est, chunks),
                           "labels": stack_client_shards(ys_est, chunks)}
        return batches, est_batches, taus_arr, c_pad

    def _train_group(self, state, width: int, ns: List[int],
                     assigns: Dict[int, Assignment],
                     prep) -> Dict[int, ClientResult]:
        eng, model, cfg = self.eng, self.eng.model, self.eng.cfg
        mesh = self.mesh
        batches_np, est_np, taus_arr, c_pad = prep

        client_params = [eng.aggregator.client_params(state, n, assigns[n])
                         for n in ns]
        client_params += [client_params[0]] * (c_pad - len(ns))
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *client_params)
        if mesh is None:
            batches = {k: jnp.asarray(v[0]) for k, v in batches_np.items()}
            taus = jnp.asarray(taus_arr)
        else:
            # per-device host shards -> one sharded array per leaf, the
            # client axis on COHORT_AXIS (batch pytree has it at axis 1)
            cs = NamedSharding(mesh, flsh.contribution_spec())
            stacked = jax.device_put(stacked, cs)
            batches = {k: flsh.assemble_from_host_shards(v, mesh, axis=1)
                       for k, v in batches_np.items()}
            taus = jax.device_put(taus_arr, cs)

        train_fn, est_fn = _cohort_fns(
            model, width, eng.factorized, mesh,
            cfg.forward_impl, for_dispatch(cfg))
        obs = eng.obs
        before = _cache_size(train_fn) if obs.enabled else None
        # (tau_pad, C', B, ...) per host chunk — the compiled signature
        lead = batches_np[next(iter(batches_np))][0].shape
        with obs.wall_span("trainer.device_step", clients=c_pad,
                           width=int(width), tau_pad=int(lead[0])):
            final, loss_b, loss_a = train_fn(stacked, batches, taus, cfg.lr)
            if obs.enabled:
                # make the span cover the device work, not just dispatch;
                # only when telemetry is on (no-op path stays untouched)
                jax.block_until_ready(loss_a)
        if obs.enabled:
            _count_recompiles(obs, train_fn, before, trainer="cohort",
                              width=int(width))
            # distinct compiled signatures are keyed by the cohort shape
            obs.counter_add("trainer.cohort_shape", width=int(width),
                            clients=c_pad, tau_pad=int(lead[0]),
                            batch=int(lead[2]))
        ests = None
        if est_np is not None:
            if mesh is None:
                est_batches = {k: jnp.asarray(v[0])
                               for k, v in est_np.items()}
            else:
                est_batches = {k: flsh.assemble_from_host_shards(v, mesh)
                               for k, v in est_np.items()}
            ests = est_fn(stacked, final, est_batches)
            ests = {k: np.asarray(v) for k, v in ests.items()}

        loss_b, loss_a = np.asarray(loss_b), np.asarray(loss_a)
        out = {}
        if mesh is not None and eng.merger is not None:
            # device-resident hand-off: the trained stack stays sharded
            # on the cohort axis; the collective merge consumes it with
            # no gather/rescatter (CohortSlice materializes lazily for
            # every other consumer).
            stack = CohortStack(final, n_real=len(ns))
            for j, n in enumerate(ns):
                est = {k: float(v[j]) for k, v in ests.items()} if ests else {}
                out[n] = ClientResult(CohortSlice(stack, j), est,
                                      float(loss_b[j]), float(loss_a[j]))
            return out
        final = _pull(obs, final)  # one transfer; slice per client below
        for j, n in enumerate(ns):
            params = jax.tree_util.tree_map(lambda v, j=j: v[j], final)
            est = {k: float(v[j]) for k, v in ests.items()} if ests else {}
            out[n] = ClientResult(params, est, float(loss_b[j]), float(loss_a[j]))
        return out
