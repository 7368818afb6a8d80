"""Expert-parallel MoE under ``shard_map`` with an explicit collective
schedule.

The pjit formulations in :mod:`repro.models.moe` leave collective
placement to GSPMD; this module pins it by hand — the §Perf "future work"
item for the MoE pairs:

  * tokens are sharded over the **data** axis and replicated over the
    **model** axis (the layer's activations already live that way);
  * experts are sharded over the **model** axis (E_loc = E/|model|
    resident per device — weight-stationary: no per-layer FSDP gathers of
    expert weights);
  * each device routes its tokens, runs ONLY its resident experts on the
    (capacity-bounded) subset of tokens that chose them, and a single
    ``psum`` over the model axis combines the per-expert partial outputs.

Communication per layer = one all-reduce of the token activations
(T_loc × d), independent of the expert count and of the expert weights —
vs. the ZeRO formulation's per-layer expert-weight all-gathers.

Validated against a dense per-token reference and the pjit GShard
formulation in ``tests/test_moe_shardmap.py`` on an 8-device host mesh.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

Array = jax.Array
Params = Dict[str, Any]


def _local_expert_pass(x2, gates, ids, gate_w, up_w, down_w,
                       e_base, E_loc: int, cap: int, activation: str):
    """Run the resident experts [e_base, e_base+E_loc) on their tokens.

    x2 (T, d); gates/ids (T, k); expert weights (E_loc, d, f)/(E_loc, f, d).
    Returns the partial output (T, d) covering only resident experts.
    """
    T, d = x2.shape
    k = ids.shape[1]
    flat_e = ids.reshape(-1)
    flat_gate = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    loc = flat_e - e_base
    mine = (loc >= 0) & (loc < E_loc)
    loc = jnp.where(mine, loc, E_loc)  # sink bucket
    # position within local expert by stable order (token-index priority)
    order = jnp.argsort(loc, stable=True)
    sloc, stok, sgate = loc[order], flat_tok[order], flat_gate[order]
    starts = jnp.searchsorted(sloc, jnp.arange(E_loc + 1))
    pos = jnp.arange(T * k) - jnp.take(starts, sloc)
    keep = (sloc < E_loc) & (pos < cap)
    buf = jnp.where(keep, sloc * cap + pos, E_loc * cap)
    xbuf = jnp.zeros((E_loc * cap + 1, d), x2.dtype).at[buf].set(
        jnp.where(keep[:, None], x2[stok], 0))
    xe = xbuf[:-1].reshape(E_loc, cap, d)
    g = jnp.einsum("ecd,edf->ecf", xe, gate_w)
    u = jnp.einsum("ecd,edf->ecf", xe, up_w)
    if activation == "geglu":
        h = jax.nn.gelu(g, approximate=True) * u
    else:
        h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, down_w).reshape(E_loc * cap, d)
    contrib = jnp.where(keep[:, None],
                        ye[jnp.minimum(buf, E_loc * cap - 1)]
                        * sgate[:, None].astype(ye.dtype), 0)
    y = jnp.zeros((T, d), x2.dtype).at[
        jnp.where(keep, stok, 0)].add(
            jnp.where(keep[:, None], contrib.astype(x2.dtype), 0))
    return y


def apply_moe_shardmap(params: Params, cfg, x: Array, mesh,
                       data_axis: str = "data",
                       model_axis: str = "model") -> Array:
    """x: (B, S, d) sharded P(data_axis, None, None) (model-replicated).
    Expert tensors (E, d, f) sharded P(model_axis, None, None).
    Returns y with the same layout as x."""
    m = cfg.moe
    E = m.num_experts
    n_model = mesh.shape[model_axis]
    assert E % n_model == 0, "experts must divide the model axis"
    E_loc = E // n_model

    def body(router_w, gate_w, up_w, down_w, xs):
        B_loc, S, d = xs.shape
        x2 = xs.reshape(B_loc * S, d)
        T = x2.shape[0]
        cap = max(4, -(-math.ceil(T * m.top_k * m.capacity_factor / E) // 4) * 4)
        logits = x2.astype(jnp.float32) @ router_w
        probs = jax.nn.softmax(logits, -1)
        gates, ids = jax.lax.top_k(probs, m.top_k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        e_base = jax.lax.axis_index(model_axis) * E_loc
        y = _local_expert_pass(x2, gates, ids, gate_w, up_w, down_w,
                               e_base, E_loc, cap, cfg.activation)
        y = jax.lax.psum(y, model_axis)  # combine expert partials
        return y.reshape(B_loc, S, d)

    f = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None), P(data_axis, None, None)),
        out_specs=P(data_axis, None, None),
    )
    # an Explicit-axis mesh (``jax.make_mesh``'s default) needs the mesh
    # context for the arrays the body builds, e.g. the searchsorted bins
    with jax.set_mesh(mesh):
        y = f(params["router"]["w"], params["gate"], params["up"],
              params["down"], x)
    if "shared" in params:
        from repro.models import layers
        y = y + layers.apply_mlp(params["shared"], x, cfg.activation)
    return y
