"""Collective aggregation backend: one compiled merge per round.

The host aggregators merge a cohort with a Python loop of per-client
eager scatters — O(K) dispatches per layer, and the server state can
never leave one device.  This backend makes the paper's block-wise merge
(Eq. 5) the mesh-native ``masked_block_mean`` path end to end:

  1. *prep*: every client result is turned into a dense zero-padded
     contribution + mask — the contract of
     ``repro.core.aggregation.scatter_contributions_host``.  Staleness
     weights (semi-async) are blended on the host, in numpy, exactly as
     the host rule does: ``w * update + (1 - w) * global``.  The scatter
     itself runs on the device: one compiled call per client uploads its
     blocks and ids and writes its ``scatter_contribution`` of every
     tensor into its row of a zeroed, donated stack (Heroes; the dense,
     HeteroFL and Flanc rules stack numpy on the host).  When the
     mesh-sharded cohort trainer hands over *device-resident* stacks
     (:class:`CohortStack` / :class:`CohortSlice`) and no weights are in
     play, prep stays on device instead: rows are gathered from the
     stacks and the dense contributions come from the compiled
     from-device scatter — no host round-trip between train and merge.
  2. *merge* (device, compiled): ONE jit call per round folds the
     stacked contributions with a fixed left-to-right ``ordered_sum``
     and divides by the mask counts.  On a multi-device mesh the client
     axis is laid out on ``sharding.fl.COHORT_AXIS`` via ``shard_map``
     and the partial sums meet in a ``jax.lax.psum``; merged
     coefficient tensors can stay *sharded over their block axis*
     (``shard_blocks``, per tensor where the block count divides the
     mesh) so the server state scales past one device.

Bitwise contract: on a single device the merged state is bitwise-equal
to the host aggregators with ``weights=None`` — the ordered fold adds
the same values in the same order (zero rows are IEEE no-ops), the
basis/dense means lower to the identical ``jnp.mean`` reduce, and all
staleness blends run in numpy float32 (same correctly-rounded ops the
host's eager blend uses).  Across devices the psum re-associates the
fold, so multi-device parity is to float tolerance.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, FrozenSet, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from repro.core import aggregation
from repro.obs.recorder import NOOP
from repro.sharding import fl as flsh


# ---------------------------------------------------------------------------
# device-resident trainer -> merger hand-off
# ---------------------------------------------------------------------------


class CohortStack:
    """Device-resident stacked cohort results (leading client axis).

    The mesh-sharded cohort trainer produces one stack per trained
    group: a params pytree whose leaves carry the padded client axis,
    sharded over ``COHORT_AXIS``.  ``n_real`` counts the leading rows
    holding real clients — everything after is a zeroed masked-clone
    row.  ``host()`` gathers the whole stack to numpy once, lazily, and
    caches it — the fallback cost is the single ``device_get`` the
    trainer used to pay eagerly.
    """

    __slots__ = ("tree", "n_real", "_host")

    def __init__(self, tree: Any, n_real: int):
        self.tree = tree
        self.n_real = n_real
        self._host = None

    def host(self):
        if self._host is None:
            self._host = jax.device_get(self.tree)
        return self._host


class CohortSlice:
    """One client's params view into a :class:`CohortStack` row.

    This is what ``ClientResult.params`` holds when the mesh-sharded
    trainer hands results to the collective backend: the merger consumes
    whole stacks device-side (no gather/rescatter between train and
    aggregate), and anything that needs the plain numpy tree calls
    :meth:`materialize` (or ``ClientResult.host_params()``).
    """

    __slots__ = ("stack", "index")

    def __init__(self, stack: CohortStack, index: int):
        self.stack = stack
        self.index = index

    def materialize(self):
        return jax.tree_util.tree_map(lambda v: v[self.index],
                                      self.stack.host())


def _host_results(results: Dict[int, Any]) -> Dict[int, Any]:
    """Materialize device-resident params back to the numpy contract."""
    out = {}
    for n, r in results.items():
        if isinstance(r.params, CohortSlice):
            r = dataclasses.replace(r, params=r.params.materialize())
        out[n] = r
    return out


def _device_groups(results: Dict[int, Any]):
    """Cohort-stack groups ``(stack, rows, positions, clients)`` in
    first-appearance order, or ``None`` unless *every* result is a
    :class:`CohortSlice` (mixed cohorts fall back to the host prep)."""
    groups: Dict[int, list] = {}
    order: List[int] = []
    for pos, (n, r) in enumerate(results.items()):
        if not isinstance(r.params, CohortSlice):
            return None
        key = id(r.params.stack)
        if key not in groups:
            groups[key] = [r.params.stack, [], [], []]
            order.append(key)
        g = groups[key]
        g[1].append(r.params.index)
        g[2].append(pos)
        g[3].append(n)
    return [groups[k] for k in order]


def _rows_in_results_order(parts: List[Any], positions: List[np.ndarray],
                           k_pad: int):
    """Concatenate per-group row stacks back into results order and
    zero-pad the client axis to ``k_pad`` — all jnp ops, leaf-wise."""
    perm = np.argsort(np.concatenate([np.asarray(p) for p in positions]))

    def leafwise(*leaves):
        cat = leaves[0] if len(leaves) == 1 else jnp.concatenate(leaves, 0)
        if not np.array_equal(perm, np.arange(perm.size)):
            cat = jnp.take(cat, jnp.asarray(perm), 0)
        if k_pad > cat.shape[0]:
            pad = jnp.zeros((k_pad - cat.shape[0],) + cat.shape[1:],
                            cat.dtype)
            cat = jnp.concatenate([cat, pad], 0)
        return cat

    return jax.tree_util.tree_map(leafwise, *parts)


def _np_blend(update, w: float, prev):
    """Numpy mirror of the host blend ``w * update + (1 - w) * prev``.

    Scalars are cast to the update dtype first (matching jax weak-typed
    promotion) and ``1 - w`` is rounded from the python double exactly
    like the host's eager ``(1.0 - w) * prev``.
    """
    update = np.asarray(update)
    dt = update.dtype.type
    return dt(w) * update + dt(1.0 - w) * np.asarray(prev, update.dtype)


def _weight_of(weights: Optional[Dict[int, float]], n: int) -> Optional[float]:
    if weights is None:
        return None
    return float(weights.get(n, 1.0))


def _pad_rows(stack: np.ndarray, k_pad: int) -> np.ndarray:
    """Zero-pad the leading client axis to ``k_pad`` rows."""
    if stack.shape[0] == k_pad:
        return stack
    pad = [(0, k_pad - stack.shape[0])] + [(0, 0)] * (stack.ndim - 1)
    return np.pad(stack, pad)


# ---------------------------------------------------------------------------
# single-device compiled merges (bitwise vs the host loops), jitted once at
# module level so every merger shares one trace cache
# ---------------------------------------------------------------------------


@jax.jit
def _fact_1d(stacked):
    """{name: {bases, dense, mask, prev}} -> {name: {basis, coeff}}."""
    return {
        name: {
            "basis": jnp.mean(t["bases"], 0),
            "coeff": aggregation.masked_block_merge(
                t["dense"], t["mask"], t["prev"]),
        }
        for name, t in stacked.items()
    }


@functools.partial(jax.jit, static_argnames="layout")
def _zero_contributions(layout):
    """Zeroed ``{name: {dense, mask}}`` stacks for ``layout``, a tuple of
    ``(name, (k_pad, num_blocks, *block_shape), dtype)``."""
    return {name: {"dense": jnp.zeros(shape, dtype),
                   "mask": jnp.zeros(shape[:2], jnp.float32)}
            for name, shape, dtype in layout}


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_client(contrib, row, blocks, ids):
    """One client's Eq. 5 contributions, every tensor in one call:
    ``scatter_contribution(blocks[name], ids[name])`` written in place
    into row ``row`` of the donated stacks ``contrib``.  Its shapes
    depend only on the client's width, so a round builds at most one
    program per width."""
    out = {}
    for name, t in contrib.items():
        dense, mask = aggregation.scatter_contribution(
            blocks[name].astype(t["dense"].dtype), ids[name],
            t["dense"].shape[1])
        out[name] = {
            "dense": jax.lax.dynamic_update_index_in_dim(
                t["dense"], dense, row, 0),
            "mask": jax.lax.dynamic_update_index_in_dim(
                t["mask"], mask, row, 0),
        }
    return out


@jax.jit
def _mean_1d(stacked):
    """Plain mean over the client axis, leaf-wise (FedAvg/ADP)."""
    return jax.tree_util.tree_map(lambda x: jnp.mean(x, 0), stacked)


@jax.jit
def _masked_1d(stacked):
    """{name: {padded, cnt, prev}} -> {name: merged} (HeteroFL)."""
    out = {}
    for name, t in stacked.items():
        acc = aggregation.ordered_sum(t["padded"])
        cnt = aggregation.ordered_sum(t["cnt"])
        out[name] = jnp.where(cnt > 0, acc / jnp.maximum(cnt, 1), t["prev"])
    return out


@jax.jit
def _flanc_1d(stacked):
    """Basis mean over all clients + per-width coefficient means."""
    basis = {name: jnp.mean(b, 0) for name, b in stacked["bases"].items()}
    coeffs = {
        p: {name: jnp.mean(c, 0) for name, c in group.items()}
        for p, group in stacked["groups"].items()
    }
    return basis, coeffs


class CollectiveMerger:
    """Owns the compiled merge functions for one engine instance.

    ``mesh=None`` is the single-device fallback (bitwise vs the host
    path); with a mesh, clients ride the ``COHORT_AXIS`` and merges run
    under ``shard_map`` + ``psum``.  ``shard_blocks=True`` keeps merged
    coefficient tensors sharded over their block axis, per tensor,
    wherever the block count divides the mesh.
    """

    def __init__(self, mesh=None, shard_blocks: bool = False):
        self.mesh = mesh
        self.shard_blocks = shard_blocks and mesh is not None
        # mesh merge fns, built lazily per variant; a plain instance dict
        # (not lru_cache-on-method, which would pin the merger + its
        # executables in a class-level cache for the process lifetime)
        self._mesh_fns: Dict[Any, Any] = {}
        # telemetry recorder (rebound by the engine runner); merge
        # *latency* is spanned at the loop level ("aggregate.merge"),
        # the merger spans its two stages inside it ("merge.prep",
        # "merge.compiled") and counts per-rule compiled-merge calls, the
        # host bytes a merge ships ("merge.h2d_bytes") and the clients
        # scattered on the device ("merge.device_scatter_clients")
        self.obs = NOOP

    def _count(self, rule: str) -> None:
        if self.obs.enabled:
            self.obs.counter_add("aggregate.collective_calls", rule=rule)

    def _compiled(self, finish, stacked, *args, uploaded: int = 0):
        """``finish(stacked, *args)`` under ``merge.compiled``, adding the
        bytes the merge sends to the device — the numpy leaves handed to
        ``finish`` (device-resident leaves add 0) plus ``uploaded``, the
        prep's own uploads — to ``merge.h2d_bytes`` and to the span."""
        obs = self.obs
        nbytes = (uploaded + sum(
            v.nbytes for v in jax.tree_util.tree_leaves(stacked)
            if isinstance(v, np.ndarray)) if obs.enabled else 0)
        obs.counter_add("merge.h2d_bytes", nbytes)
        with obs.wall_span("merge.compiled", h2d_bytes=nbytes):
            return finish(stacked, *args)

    # -- finish stage: dispatch the prepped stacks to a compiled merge.
    # Split out so subclasses can reroute the reduction topology (the
    # hierarchical edge-group merger in repro.fl.population.hierarchy)
    # without touching the prep contracts.

    def _finish_fact(self, stacked, k: int, shard_names: FrozenSet[str]):
        if self.mesh is None:
            return _fact_1d(stacked)
        return self._mesh_fact_fn(shard_names)(stacked, jnp.float32(k))

    def _finish_mean(self, stacked, k: int):
        if self.mesh is None:
            return _mean_1d(stacked)
        return self._mesh_mean_fn()(stacked, jnp.float32(k))

    def _finish_masked(self, stacked):
        if self.mesh is None:
            return _masked_1d(stacked)
        return self._mesh_masked_fn()(stacked)

    # -- mesh (shard_map) merge builders -----------------------------------

    def _mesh_fact_fn(self, shard_names: FrozenSet[str]):
        key = ("fact", shard_names)
        if key in self._mesh_fns:
            return self._mesh_fns[key]
        mesh, axis = self.mesh, flsh.COHORT_AXIS
        ndev = mesh.devices.size
        contrib, repl = flsh.contribution_spec(), flsh.replicated_spec()

        def per_device(stacked, k_real):
            out = {}
            for name, t in stacked.items():
                bsum = jax.lax.psum(aggregation.ordered_sum(t["bases"]), axis)
                basis = bsum / k_real.astype(bsum.dtype)
                coeff = aggregation.masked_block_merge(
                    t["dense"], t["mask"], t["prev"], axis_name=axis)
                if name in shard_names:
                    per = coeff.shape[0] // ndev
                    idx = jax.lax.axis_index(axis)
                    coeff = jax.lax.dynamic_slice_in_dim(
                        coeff, idx * per, per, axis=0)
                out[name] = {"basis": basis, "coeff": coeff}
            return out

        per_name_in = {"bases": contrib, "dense": contrib, "mask": contrib,
                       "prev": repl}

        def merge(stacked, k_real):
            f = shard_map(
                per_device, mesh=mesh,
                in_specs=({n: per_name_in for n in stacked}, repl),
                out_specs={n: {"basis": repl,
                               "coeff": flsh.block_spec()
                               if n in shard_names else repl}
                           for n in stacked})
            return f(stacked, k_real)

        fn = jax.jit(merge)
        self._mesh_fns[key] = fn
        return fn

    def _mesh_mean_fn(self):
        if "mean" in self._mesh_fns:
            return self._mesh_fns["mean"]
        mesh, axis = self.mesh, flsh.COHORT_AXIS
        contrib, repl = flsh.contribution_spec(), flsh.replicated_spec()

        def per_device(stacked, k_real):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.psum(aggregation.ordered_sum(x), axis)
                / k_real.astype(x.dtype), stacked)

        def merge(stacked, k_real):
            f = shard_map(
                per_device, mesh=mesh,
                in_specs=(jax.tree_util.tree_map(lambda _: contrib, stacked),
                          repl),
                out_specs=jax.tree_util.tree_map(lambda _: repl, stacked))
            return f(stacked, k_real)

        fn = jax.jit(merge)
        self._mesh_fns["mean"] = fn
        return fn

    def _mesh_masked_fn(self):
        if "masked" in self._mesh_fns:
            return self._mesh_fns["masked"]
        mesh, axis = self.mesh, flsh.COHORT_AXIS
        contrib, repl = flsh.contribution_spec(), flsh.replicated_spec()

        def per_device(stacked):
            out = {}
            for name, t in stacked.items():
                acc = jax.lax.psum(aggregation.ordered_sum(t["padded"]), axis)
                cnt = jax.lax.psum(aggregation.ordered_sum(t["cnt"]), axis)
                out[name] = jnp.where(cnt > 0, acc / jnp.maximum(cnt, 1),
                                      t["prev"])
            return out

        def merge(stacked):
            per_in = {"padded": contrib, "cnt": contrib, "prev": repl}
            f = shard_map(per_device, mesh=mesh,
                          in_specs=({n: per_in for n in stacked},),
                          out_specs={n: repl for n in stacked})
            return f(stacked)

        fn = jax.jit(merge)
        self._mesh_fns["masked"] = fn
        return fn

    def _mesh_flanc_fn(self):
        if "flanc" in self._mesh_fns:
            return self._mesh_fns["flanc"]
        mesh, axis = self.mesh, flsh.COHORT_AXIS
        contrib, repl = flsh.contribution_spec(), flsh.replicated_spec()

        def per_device(stacked, k_real):
            basis = {
                name: jax.lax.psum(aggregation.ordered_sum(b), axis)
                / k_real.astype(b.dtype)
                for name, b in stacked["bases"].items()
            }
            onehot = stacked["onehot"]  # (K_local, P)
            coeffs = {}
            for p, group in stacked["prevs"].items():
                sel = jax.lax.psum(jnp.sum(onehot[:, p - 1]), axis)
                coeffs[p] = {}
                for name, prev in group.items():
                    total = jax.lax.psum(
                        jnp.einsum("k,k...->...", onehot[:, p - 1],
                                   stacked["dense"][name]), axis)
                    nb = prev.shape[0]
                    mean = total[:nb] / jnp.maximum(sel, 1).astype(total.dtype)
                    coeffs[p][name] = jnp.where(sel > 0, mean, prev)
            return basis, coeffs

        def merge(stacked, k_real):
            in_specs = ({
                "bases": {n: contrib for n in stacked["bases"]},
                "onehot": contrib,
                "dense": {n: contrib for n in stacked["dense"]},
                "prevs": {p: {n: repl for n in g}
                          for p, g in stacked["prevs"].items()},
            }, repl)
            out_specs = ({n: repl for n in stacked["bases"]},
                         {p: {n: repl for n in g}
                          for p, g in stacked["prevs"].items()})
            f = shard_map(per_device, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs)
            return f(stacked, k_real)

        fn = jax.jit(merge)
        self._mesh_fns["flanc"] = fn
        return fn

    # -- device-resident prep (mesh-sharded trainer hand-off) --------------

    def _device_stacked(self, groups, k_pad: int):
        """Client rows stacked in results order, zero-padded to ``k_pad``
        — all device-side.  When the trainer's stack already matches
        (one group consuming *every* real row in trained order, same
        padded height — so the rows beyond are zeroed clones) the
        stack's tree passes through untouched: the params trained on
        the cohort axis feed the merge with no data movement at all."""
        if len(groups) == 1:
            stack, rows, _, _ = groups[0]
            nrows = jax.tree_util.tree_leaves(stack.tree)[0].shape[0]
            if rows == list(range(stack.n_real)) and nrows == k_pad:
                return stack.tree
        parts = [jax.tree_util.tree_map(
            lambda v, r=np.asarray(g[1]): jnp.take(v, jnp.asarray(r), 0),
            g[0].tree) for g in groups]
        return _rows_in_results_order(parts, [g[2] for g in groups], k_pad)

    def _stack_factorized_device(self, prev_params, specs, groups,
                                 k_pad: int, assigns):
        """Factorized merge inputs straight from device-resident stacks:
        coefficient rows become dense contributions through the compiled
        from-device scatter (one vmapped call per group/layer), bases
        are row-gathers — the host never sees the trained params.
        Returns ``(stacked, shard_names)`` for ``_finish_fact``."""
        shard_names: FrozenSet[str] = frozenset()
        if self.shard_blocks:
            shard_names = frozenset(
                n for n, t in prev_params.items()
                if flsh.can_shard_blocks(t["coeff"].shape[0], self.mesh))
        stacked: Dict[str, Dict[str, Any]] = {}
        positions = [g[2] for g in groups]
        for name, spec in specs.items():
            ids_key = "hidden_ids" if spec.mode == "square" else "anchored_ids"
            prev_c = prev_params[name]["coeff"]
            bases, dense, mask = [], [], []
            for stack, rows, _, ns in groups:
                sub = stack.tree[name]
                r = jnp.asarray(np.asarray(rows))
                bases.append(jnp.take(sub["basis"], r, 0))
                ids = np.stack([np.asarray(assigns[n][ids_key]) for n in ns])
                d, m = aggregation.scatter_contributions_host(
                    jnp.take(sub["coeff"], r, 0), jnp.asarray(ids),
                    num_blocks=prev_c.shape[0])
                dense.append(d)
                mask.append(m)
            stacked[name] = {
                "bases": _rows_in_results_order(bases, positions, k_pad),
                "dense": _rows_in_results_order(dense, positions, k_pad),
                "mask": _rows_in_results_order(mask, positions, k_pad),
                "prev": prev_c,
            }
        return stacked, shard_names

    # -- prep + dispatch ----------------------------------------------------

    def merge_factorized(self, prev_params, specs, results, assigns,
                         weights=None):
        """Heroes merge: basis mean + Eq. 5 block-wise coefficient merge."""
        self._count("factorized")
        k = len(results)
        k_pad = flsh.pad_cohort(k, self.mesh)
        self.obs.counter_add("merge.device_scatter_clients", k)
        with self.obs.wall_span("merge.prep", clients=k, device_scatter=k):
            stacked, shard_names, uploaded = self._stack_factorized(
                prev_params, specs, results, assigns, weights, k_pad)
        return self._compiled(self._finish_fact, stacked, k, shard_names,
                              uploaded=uploaded)

    def _stack_factorized(self, prev_params, specs, results, assigns,
                          weights, k_pad: int):
        """``merge_factorized``'s inputs: ``(stacked, shard_names,
        uploaded)``, ``uploaded`` the bytes of client blocks and ids the
        prep sends to the device.  Each client's (blended) blocks go to
        ``_scatter_client`` in results order; the bases stay a numpy
        stack."""
        if weights is None:
            groups = _device_groups(results)
            if groups is not None:
                return self._stack_factorized_device(
                    prev_params, specs, groups, k_pad, assigns) + (0,)
        results = _host_results(results)
        prev_np: Dict[str, Any] = {}
        bases: Dict[str, List[np.ndarray]] = {name: [] for name in specs}
        contrib = None
        uploaded = 0
        for j, (n, r) in enumerate(results.items()):
            w = _weight_of(weights, n)
            blocks, ids = {}, {}
            for name, spec in specs.items():
                key = "hidden_ids" if spec.mode == "square" else "anchored_ids"
                i = np.asarray(assigns[n][key], np.int32)
                b = np.asarray(r.params[name]["basis"])
                c = r.params[name]["coeff"]
                if w is not None:
                    if name not in prev_np:
                        prev_np[name] = (
                            np.asarray(prev_params[name]["basis"]),
                            np.asarray(prev_params[name]["coeff"]))
                    b = _np_blend(b, w, prev_np[name][0])
                    c = _np_blend(c, w, prev_np[name][1][i])
                bases[name].append(b)
                blocks[name] = c
                ids[name] = i
            if contrib is None:
                contrib = _zero_contributions(tuple(
                    (name, (k_pad, prev_params[name]["coeff"].shape[0])
                     + tuple(blk.shape[1:]), np.dtype(blk.dtype))
                    for name, blk in blocks.items()))
            contrib = _scatter_client(contrib, np.int32(j), blocks, ids)
            uploaded += sum(v.nbytes for v in (*blocks.values(),
                                               *ids.values())
                            if isinstance(v, np.ndarray))
        if self.mesh is not None:
            contrib = jax.device_put(contrib, jax.sharding.NamedSharding(
                self.mesh, flsh.contribution_spec()))
        stacked = {
            name: {"bases": _pad_rows(np.stack(bases[name]), k_pad),
                   "dense": contrib[name]["dense"],
                   "mask": contrib[name]["mask"],
                   "prev": prev_params[name]["coeff"]}
            for name in specs
        }
        shard_names: FrozenSet[str] = frozenset()
        if self.shard_blocks:
            shard_names = frozenset(
                n for n, t in stacked.items()
                if flsh.can_shard_blocks(t["prev"].shape[0], self.mesh))
        return stacked, shard_names, uploaded

    def merge_dense_mean(self, prev_params, results, weights=None):
        """FedAvg/ADP: plain parameter mean over the cohort."""
        self._count("dense_mean")
        k = len(results)
        k_pad = flsh.pad_cohort(k, self.mesh)
        with self.obs.wall_span("merge.prep", clients=k):
            stacked = self._stack_dense(prev_params, results, weights, k_pad)
        return self._compiled(self._finish_mean, stacked, k)

    def _stack_dense(self, prev_params, results, weights, k_pad: int):
        """``merge_dense_mean``'s stacked client trees."""
        if weights is None:
            groups = _device_groups(results)
            if groups is not None:
                return self._device_stacked(groups, k_pad)
        results = _host_results(results)
        prev_np = None
        trees = []
        for n, r in results.items():
            w = _weight_of(weights, n)
            if w is None:
                trees.append(jax.tree_util.tree_map(np.asarray, r.params))
            else:
                if prev_np is None:
                    prev_np = jax.tree_util.tree_map(np.asarray, prev_params)
                trees.append(jax.tree_util.tree_map(
                    lambda u, g, w=w: _np_blend(u, w, g), r.params, prev_np))
        return jax.tree_util.tree_map(
            lambda *xs: _pad_rows(np.stack(xs), k_pad), *trees)

    def merge_masked_dense(self, prev_params, results, weights=None):
        """HeteroFL: element-wise mean over the covering clients."""
        self._count("masked_dense")
        with self.obs.wall_span("merge.prep", clients=len(results)):
            stacked = self._stack_masked(prev_params, results, weights)
        return self._compiled(self._finish_masked, stacked)

    def _stack_masked(self, prev_params, results, weights):
        """``merge_masked_dense``'s zero-padded regions and counts."""
        results = _host_results(results)
        k_pad = flsh.pad_cohort(len(results), self.mesh)
        stacked = {}
        for name, full in prev_params.items():
            full_np = None
            pads, cnts = [], []
            for n, r in results.items():
                wv = np.asarray(r.params[name])
                w = _weight_of(weights, n)
                if w is not None:
                    if full_np is None:
                        full_np = np.asarray(full)
                    region = full_np[tuple(slice(0, s) for s in wv.shape)]
                    wv = _np_blend(wv, w, region)
                pad = [(0, full.shape[i] - wv.shape[i])
                       for i in range(wv.ndim)]
                pads.append(np.pad(wv, pad))
                cnts.append(np.pad(np.ones_like(wv), pad))
            stacked[name] = {"padded": _pad_rows(np.stack(pads), k_pad),
                             "cnt": _pad_rows(np.stack(cnts), k_pad),
                             "prev": full}
        return stacked

    def merge_flanc(self, basis, coeffs, results, widths, weights=None):
        """Flanc: shared basis mean + per-width coefficient means.

        ``widths`` maps client -> assigned width (which coefficient set
        the client trained).  Returns ``(new_basis, new_coeffs)`` where
        widths nobody trained keep their previous coefficients.
        """
        self._count("flanc")
        k = len(results)
        with self.obs.wall_span("merge.prep", clients=k):
            stacked = self._stack_flanc(basis, coeffs, results, widths,
                                        weights)
        if self.mesh is not None:
            return self._compiled(self._mesh_flanc_fn(), stacked,
                                  jnp.float32(k))
        new_basis, merged = self._compiled(_flanc_1d, stacked)
        new_coeffs = dict(coeffs)
        for p, g in merged.items():
            new_coeffs[p] = g
        return new_basis, new_coeffs

    def _stack_flanc(self, basis, coeffs, results, widths, weights):
        """``merge_flanc``'s inputs: per-width coefficient stacks, or on a
        mesh one width-P zero-padded coefficient and a one-hot width row
        per client."""
        results = _host_results(results)
        k = len(results)
        names = list(basis)
        max_width = max(coeffs)
        bases = {name: [] for name in names}
        for n, r in results.items():
            w = _weight_of(weights, n)
            for name in names:
                b = np.asarray(r.params[name]["basis"])
                if w is not None:
                    b = _np_blend(b, w, np.asarray(basis[name]))
                bases[name].append(b)

        if self.mesh is None:
            by_width: Dict[int, List[int]] = {}
            for n in results:
                by_width.setdefault(widths[n], []).append(n)
            groups = {}
            for p, ns in by_width.items():
                groups[p] = {}
                for name in names:
                    rows = []
                    for n in ns:
                        c = np.asarray(results[n].params[name]["coeff"])
                        w = _weight_of(weights, n)
                        if w is not None:
                            c = _np_blend(c, w, np.asarray(coeffs[p][name]))
                        rows.append(c)
                    groups[p][name] = np.stack(rows)
            return {"bases": {n: np.stack(b) for n, b in bases.items()},
                    "groups": groups}

        # mesh path: every client contributes ONE zero-padded dense coeff
        # (padded to the width-P block count) plus a one-hot width row;
        # per-width means select rows through the one-hot.
        k_pad = flsh.pad_cohort(k, self.mesh)
        onehot = np.zeros((k_pad, max_width), np.float32)
        dense = {name: [] for name in names}
        for j, n in enumerate(results):
            p = widths[n]
            onehot[j, p - 1] = 1.0
            for name in names:
                c = np.asarray(results[n].params[name]["coeff"])
                w = _weight_of(weights, n)
                if w is not None:
                    c = _np_blend(c, w, np.asarray(coeffs[p][name]))
                nb_max = coeffs[max_width][name].shape[0]
                pad = [(0, nb_max - c.shape[0])] + [(0, 0)] * (c.ndim - 1)
                dense[name].append(np.pad(c, pad))
        return {
            "bases": {n: _pad_rows(np.stack(b), k_pad)
                      for n, b in bases.items()},
            "onehot": onehot,
            "dense": {n: _pad_rows(np.stack(rows), k_pad)
                      for n, rows in dense.items()},
            "prevs": {p: {n: coeffs[p][n] for n in names} for p in coeffs},
        }


def build_merger(cfg) -> CollectiveMerger:
    """Merger per the engine config: mesh when >1 device is visible;
    hierarchical edge-group reduction when ``cfg.edge_groups > 1``."""
    mesh = flsh.cohort_mesh(getattr(cfg, "agg_devices", 0))
    shard = getattr(cfg, "shard_server_state", False)
    groups = getattr(cfg, "edge_groups", 0)
    if groups and groups > 1:
        # population layers on the engine; import here to avoid a cycle
        from repro.fl.population.hierarchy import HierarchicalMerger
        return HierarchicalMerger(mesh, shard_blocks=shard,
                                  edge_groups=groups)
    return CollectiveMerger(mesh, shard_blocks=shard)
