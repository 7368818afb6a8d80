"""Global aggregation (Heroes, Sec. III phase 3).

* Neural basis: plain average over the K participating clients.
* Coefficient: *block-wise* aggregation (Eq. 5) — block ``i`` is averaged
  over exactly the clients that trained it this round; blocks nobody
  trained keep their previous value.

Two implementations:

``aggregate_*``           — host-driven, list-of-client-pytrees (FL runtime).
``masked_block_mean``     — collective form: every client contributes a
                            dense ``(P^2, R, O)`` tensor with zeros at
                            untrained blocks plus a 0/1 mask; aggregation is
                            ``psum(contrib)/psum(mask)``.  This is the
                            mesh-native formulation used by the distributed
                            launcher (identical math, shardable on the data
                            axis).
``masked_block_merge``    — stacked form of the same rule: contributions
                            laid out on a leading client axis, accumulated
                            with a fixed left-to-right ``ordered_sum`` so a
                            single compiled call reproduces the host scatter
                            loop *bitwise*, optionally followed by a
                            ``psum`` when the client axis is sharded over a
                            device mesh (``axis_name``).

Bitwise contract: floating-point addition is not associative, so any
reduction that wants to reproduce the host loop exactly must add client
contributions in the same order the host loop did.  ``ordered_sum`` is
that reduction (a ``lax.scan`` fold — XLA's ``reduce`` is free to
re-associate and measurably does on CPU); zero-padded rows are exact
no-ops under IEEE addition, which is what makes the dense zero-padded
contribution form equivalent to the sparse scatter form.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def _per_block(v: Array, like: Array) -> Array:
    """A per-block vector ``(num_blocks,)`` shaped to broadcast over a
    coefficient ``like`` of any rank (``(P^2, R, O)``, or an expert
    bank's ``(P^2, E, R, O)``)."""
    return v.reshape(v.shape + (1,) * (like.ndim - 1))


def ordered_sum(stacked: Array) -> Array:
    """Sum over the leading axis with fixed left-to-right association.

    Bitwise-identical to the eager loop ``acc = acc + stacked[k]`` (and,
    with zero-padded contributions, to ``acc.at[ids].add(blocks)`` host
    scatters in the same client order) — unlike ``jnp.sum``, whose
    reduce order XLA may re-associate.
    """
    init = jnp.zeros_like(stacked[0])
    return jax.lax.scan(lambda acc, x: (acc + x, None), init, stacked)[0]


def aggregate_basis(
    client_bases: Sequence[Array],
    weights: Optional[Sequence[float]] = None,
    prev: Optional[Array] = None,
) -> Array:
    """v^{h+1} = (1/K) sum_n v̄_n^h.

    With ``weights`` (semi-async staleness discount), each client's basis
    is first blended toward ``prev`` (the current global basis) as
    ``w * v̄_n + (1 - w) * prev`` — all-ones weights reduce to the plain
    mean bitwise.
    """
    if weights is None:
        return jnp.mean(jnp.stack(client_bases, axis=0), axis=0)
    if prev is None:
        raise ValueError("weighted aggregation needs the previous basis")
    blended = [w * b + (1.0 - w) * prev for b, w in zip(client_bases, weights)]
    return jnp.mean(jnp.stack(blended, axis=0), axis=0)


def aggregate_coefficient(
    global_coeff: Array,
    client_blocks: Sequence[Array],
    client_block_ids: Sequence[np.ndarray],
    weights: Optional[Sequence[float]] = None,
) -> Array:
    """Block-wise aggregation, Eq. (5).

    Args:
      global_coeff: previous round's complete coefficient ``(P^2, R, O)``.
      client_blocks: per client, updated reduced coefficient ``(m_n, R, O)``.
      client_block_ids: per client, the block indices (length ``m_n``)
        those rows correspond to.
      weights: optional per-client staleness weights in [0, 1]; a client's
        blocks are blended toward the current global blocks as
        ``w * blocks + (1 - w) * global[ids]`` before the block mean.

    Returns:
      New complete coefficient in ``global_coeff.dtype`` (the per-block
      counters are kept in float32 — exact for any realistic cohort — and
      cast to the coefficient dtype only for the division, so bf16/f16
      coefficients are not silently upcast); untrained blocks unchanged.
    """
    num_blocks = global_coeff.shape[0]
    acc = jnp.zeros_like(global_coeff)
    cnt = jnp.zeros((num_blocks,), dtype=jnp.float32)
    if weights is None:
        weights = [None] * len(client_blocks)
    for blocks, ids, w in zip(client_blocks, client_block_ids, weights):
        ids = jnp.asarray(np.asarray(ids))
        blocks = blocks.astype(acc.dtype)
        if w is not None:
            blocks = w * blocks + (1.0 - w) * global_coeff[ids]
        acc = acc.at[ids].add(blocks)
        cnt = cnt.at[ids].add(1.0)
    trained = _per_block(cnt > 0, acc)
    denom = _per_block(jnp.where(cnt > 0, cnt, 1.0), acc).astype(acc.dtype)
    mean = acc / denom
    return jnp.where(trained, mean, global_coeff)


def aggregate_factorized(
    global_params: Dict[str, Dict[str, Array]],
    client_params: Sequence[Dict[str, Dict[str, Array]]],
    client_block_ids: Sequence[np.ndarray],
) -> Dict[str, Dict[str, Array]]:
    """Aggregate a whole CompositionPlan param tree (basis + coeff per layer)."""
    out: Dict[str, Dict[str, Array]] = {}
    for name, gp in global_params.items():
        out[name] = {
            "basis": aggregate_basis([cp[name]["basis"] for cp in client_params]),
            "coeff": aggregate_coefficient(
                gp["coeff"],
                [cp[name]["coeff"] for cp in client_params],
                client_block_ids,
            ),
        }
    return out


# ---------------------------------------------------------------------------
# Mesh-native (collective) formulation
# ---------------------------------------------------------------------------


def scatter_contribution(
    updated_blocks: Array, block_ids: Array, num_blocks: int
) -> tuple[Array, Array]:
    """Client-side: dense zero-padded contribution + mask for masked psum.

    ``block_ids`` with duplicates contribute additively (matching the
    host path's ``at[ids].add``): the dense row receives the sum of the
    duplicate rows and the mask counts each occurrence.
    """
    dense = jnp.zeros((num_blocks,) + updated_blocks.shape[1:],
                      updated_blocks.dtype).at[block_ids].add(updated_blocks)
    mask = jnp.zeros((num_blocks,), jnp.float32).at[block_ids].add(1.0)
    return dense, mask


@functools.partial(jax.jit, static_argnames="num_blocks")
def _scatter_contributions_device(
    blocks: Array, block_ids: Array, num_blocks: int
) -> Tuple[Array, Array]:
    """Compiled stacked form of :func:`scatter_contribution`: blocks
    ``(K, m, R, O)`` + ids ``(K, m)`` -> dense ``(K, num_blocks, R, O)``
    + mask ``(K, num_blocks)``, vmapped over the client axis."""
    return jax.vmap(
        lambda b, i: scatter_contribution(b, i, num_blocks))(blocks, block_ids)


def scatter_contributions_host(
    client_blocks,
    client_block_ids,
    num_blocks: int,
    dtype=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Stack per-client dense contributions + masks on the host.

    One numpy pass instead of ``2K`` eager device scatters; the result is
    shipped to the device once and merged in a single compiled call.
    Duplicate ids within a client accumulate (``np.add.at``), matching
    the host scatter loop.

    From-device path: when ``client_blocks`` is a stacked ``jax.Array``
    (``(K, m, R, O)``, with ``client_block_ids`` ``(K, m)``) the scatter
    runs as one compiled vmapped call and the dense contributions stay
    device-resident — the path the mesh-sharded cohort trainer uses to
    hand results to the collective merge without a host round-trip.
    ``dtype`` is ignored there (contributions keep the blocks' dtype).
    """
    if isinstance(client_blocks, jax.Array):
        return _scatter_contributions_device(
            client_blocks, jnp.asarray(client_block_ids), num_blocks)
    k = len(client_blocks)
    first = np.asarray(client_blocks[0])
    dense = np.zeros((k, num_blocks) + first.shape[1:],
                     dtype or first.dtype)
    mask = np.zeros((k, num_blocks), np.float32)
    for j, (blocks, ids) in enumerate(zip(client_blocks, client_block_ids)):
        ids = np.asarray(ids)
        np.add.at(dense[j], ids, np.asarray(blocks, dtype=dense.dtype))
        np.add.at(mask[j], ids, 1.0)
    return dense, mask


def masked_block_mean(
    dense_contrib: Array, mask: Array, prev_coeff: Array, axis_name: str
) -> Array:
    """Collective Eq. (5): psum dense contributions / psum masks.

    Runs inside ``shard_map`` with clients laid out on ``axis_name``.
    """
    total = jax.lax.psum(dense_contrib, axis_name)
    count = jax.lax.psum(mask, axis_name)
    trained = _per_block(count > 0, total)
    denom = _per_block(jnp.where(count > 0, count, 1.0), total)
    return jnp.where(trained, total / denom.astype(total.dtype), prev_coeff)


def masked_block_merge(
    dense_stack: Array, mask_stack: Array, prev_coeff: Array,
    axis_name: Optional[str] = None,
) -> Array:
    """Eq. (5) over a stacked client axis: ordered local fold, then psum.

    ``dense_stack``/``mask_stack`` carry the (local shard of the) client
    axis in front.  Without ``axis_name`` this is the single-device form
    and reproduces :func:`aggregate_coefficient` with ``weights=None``
    *bitwise* (same left-to-right addition order; zero-padded rows are
    exact no-ops).  With ``axis_name`` the local partial sums are
    combined with ``psum`` — clients sharded over a mesh axis — which
    re-associates across devices (parity to float tolerance).

    Returns the merged coefficient in ``prev_coeff.dtype``.
    """
    total = ordered_sum(dense_stack)
    count = ordered_sum(mask_stack)
    if axis_name is not None:
        total = jax.lax.psum(total, axis_name)
        count = jax.lax.psum(count, axis_name)
    trained = _per_block(count > 0, total)
    denom = _per_block(jnp.where(count > 0, count, 1.0), total)
    mean = total / denom.astype(total.dtype)
    return jnp.where(trained, mean, prev_coeff)
