"""Operations and bytes of the latent-attention + experts decoder
(``reference/mla_moe.py``'s block) from a configuration's shapes and the
routed pairs the program counted.

As in ``flops.py``: the least work the algorithm needs, each composed
layer at the cheaper of rank-space application and compose-then-dense, a
multiply-add counting 2, a backward pass twice its forward.  The routed
experts are counted at the pairs (token, held expert) the program
reports computing (``moe.routed_pairs``), each pair costing one
expert's gate, up and down projections.  Only ``counted_clients``
reads the program: the counts its traced window carried.
"""

from __future__ import annotations

from typing import Dict, Tuple

import flops


def layers(m: dict) -> Dict[str, Tuple[str, int, int]]:
    """name -> (mode, I, O) of every composed layer applied to every
    token (all but the expert banks)."""
    d, hb = m["d_base"], m["heads_base"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    vd, lat = m["v_head_dim"], m["kv_lora_rank"]
    out = {"embed": ("grow_out", m["vocab"], d)}

    def swiglu(prefix, ff):
        out[f"{prefix}.gate"] = ("square", d, ff)
        out[f"{prefix}.up"] = ("square", d, ff)
        out[f"{prefix}.down"] = ("square", ff, d)

    for i in range(m["n_layers"]):
        out[f"l{i}.wq"] = ("square", d, hb * (nope + rope))
        out[f"l{i}.wkv_a"] = ("grow_in", d, lat + rope)
        out[f"l{i}.wkv_b"] = ("grow_out", lat, hb * (nope + vd))
        out[f"l{i}.wo"] = ("square", hb * vd, d)
        if i < m["first_dense"]:
            swiglu(f"l{i}", m["dense_ff_base"])
        else:
            out[f"l{i}.router"] = ("grow_in", d, m["n_experts"])
            swiglu(f"l{i}.shared", m["shared_ff_base"])
    out["head"] = ("grow_in", d, m["vocab"])
    return out


def moe_layers(m: dict) -> int:
    return m["n_layers"] - m["first_dense"]


def attention_fwd_flops(m: dict, p: int, seq: int, sequences: int) -> int:
    """Scores (query-key dim ``nope + rope``) and weighted values (value
    dim) over the causal pairs of one layer at width ``p``."""
    pairs = seq * (seq + 1) // 2
    heads = p * m["heads_base"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return sequences * 2 * pairs * heads * (qk + m["v_head_dim"])


def dense_fwd_flops(m: dict, p: int, batch: int, seq: int) -> int:
    """One forward pass over ``batch`` sequences, without the routed
    experts."""
    rows = batch * seq
    total = sum(flops.layer_fwd_flops(mode, I, O, m["rank"], p, rows,
                                      lookup=name == "embed")
                for name, (mode, I, O) in layers(m).items())
    return total + m["n_layers"] * attention_fwd_flops(m, p, seq, batch)


def bank_fwd_flops(m: dict, p: int, pairs: float) -> float:
    """One expert bank's forward over ``pairs`` routed pairs: the cheaper
    of rank space (per pair, one expert's p input groups project I -> R
    and its p*p blocks contract R -> O) and composing every held expert
    then one dense matmul per pair."""
    d, f, R, E = m["d_base"], m["expert_ff_base"], m["rank"], m["experts_held"]
    total = 0.0
    for I, O in ((d, f), (d, f), (f, d)):  # gate, up, down
        rank = 2 * pairs * (p * I * R + p * p * R * O)
        dense = E * flops.compose_flops("square", I, O, R, p) + (
            2 * pairs * p * I * p * O)
        total += min(rank, dense)
    return total


def client_round_flops(m: dict, p: int, tau: int, batch: int, seq: int,
                       pairs: float, backward_pairs: float) -> float:
    """One Heroes client's least work in a round: ``tau`` SGD steps, the
    two losses and the four estimate gradients (as
    ``flops.client_round_flops``), the routed experts at the counted
    pairs -- ``pairs`` over every forward, ``backward_pairs`` over those
    that a backward pass followed -- spread evenly over the calls and the
    expert layers."""
    fwd = dense_fwd_flops(m, p, batch, seq)
    total = tau * 3 * fwd + 2 * fwd + 4 * 3 * fwd
    calls = {"forward": 2, "backward": tau + 4}
    fwd_pairs = pairs - backward_pairs
    for kind, n, mult in (("forward", fwd_pairs, 1), ("backward",
                                                      backward_pairs, 3)):
        per = calls[kind] * moe_layers(m)
        total += mult * per * bank_fwd_flops(m, p, n / per)
    return total


def grouped_matmul_cost(m: dict, p: int, calls: int, pairs: float,
                        backward_calls: int, backward_pairs: float,
                        itemsize: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of the grouped expert matmuls that computed
    ``pairs`` routed pairs in ``calls`` forward passes, ``backward_pairs``
    and ``backward_calls`` of them followed by a backward pass: per pair
    and projection a dense ``(pI, pO)`` product; per pass and expert layer
    the held experts' composed weights read, and per pair its rows in and
    out.  A backward pass costs twice its forward in both."""
    d, f, E = p * m["d_base"], p * m["expert_ff_base"], m["experts_held"]
    per_pair = 2 * 3 * d * f
    weights = itemsize * 3 * E * d * f
    rows = itemsize * 3 * (d + f)
    weighted_pairs = pairs + 2 * backward_pairs
    weighted_calls = calls + 2 * backward_calls
    return (per_pair * weighted_pairs,
            weights * moe_layers(m) * weighted_calls + rows * weighted_pairs)


def counted_clients(ctx) -> list:
    """The window's ``trainer.local_train`` span attributes that carry
    the program's routed-pair counts (none from a build that counts
    none, or from an untraced run)."""
    obs = ctx.get("obs")
    if not obs or ctx.get("kind") != "train":
        return []
    return [e["attrs"] for e in obs["spans"]
            if e.get("type") == "span" and e["name"] == "trainer.local_train"
            and "moe.routed_pairs" in e["attrs"]]
