"""Operations and bytes from a configuration's shapes.

Counts are the least work the algorithm needs, independent of the path
the program takes: each composed layer costs the smaller of applying its
factors in rank space and composing the weight once then applying it
densely.  A multiply-add counts 2; a backward pass counts twice its
forward.  Nothing here reads the program.
"""

from __future__ import annotations

from typing import Dict, Tuple


def layers(m: dict) -> Dict[str, Tuple[str, int, int]]:
    """name -> (mode, I, O) of every composed layer of the decoder; the
    ``embed`` layer is a lookup."""
    d, ff, v = m["d_base"], m["ff_mult"] * m["d_base"], m["vocab"]
    out = {"embed": ("grow_out", v, d)}
    for i in range(m["n_layers"]):
        for p in ("wq", "wk", "wv", "wo"):
            out[f"l{i}.{p}"] = ("square", d, d)
        out[f"l{i}.up"] = ("square", d, ff)
        out[f"l{i}.down"] = ("square", ff, d)
    out["head"] = ("grow_in", d, v)
    return out


def blocks(mode: str, p: int) -> int:
    return p * p if mode == "square" else p


def compose_flops(mode: str, I: int, O: int, R: int, p: int) -> int:
    """Basis (I, R) times ``blocks`` coefficient blocks (R, O)."""
    return 2 * I * R * blocks(mode, p) * O


def compose_bytes(mode: str, I: int, O: int, R: int, p: int,
                  itemsize: int = 4) -> int:
    m = blocks(mode, p)
    return itemsize * (I * R + m * R * O + I * m * O)


def layer_fwd_flops(mode: str, I: int, O: int, R: int, p: int,
                    rows: int, lookup: bool = False) -> int:
    """Forward operations of one composed layer over ``rows`` input rows
    (tokens): the cheaper of the rank-space application and
    compose-then-dense.  The embedding is a lookup: its rank path only
    contracts the gathered R-vectors with the blocks, and its dense path
    costs only the compose."""
    m = blocks(mode, p)
    groups = 1 if mode == "grow_out" else p
    if lookup:
        rank = 2 * rows * m * R * O
        dense = compose_flops(mode, I, O, R, p)
    else:
        pi = I * (1 if mode == "grow_out" else p)
        po = O * (1 if mode == "grow_in" else p)
        rank = 2 * rows * (groups * I * R + m * R * O)
        dense = compose_flops(mode, I, O, R, p) + 2 * rows * pi * po
    return min(rank, dense)


def attention_fwd_flops(d_model: int, seq: int, sequences: int) -> int:
    """Scores and weighted values over the causal pairs of one layer."""
    pairs = seq * (seq + 1) // 2
    return sequences * 2 * 2 * pairs * d_model


def forward_flops(m: dict, p: int, batch: int, seq: int) -> int:
    """One forward pass of a width-``p`` client over ``batch`` sequences."""
    rows = batch * seq
    total = sum(layer_fwd_flops(mode, I, O, m["rank"], p, rows,
                                lookup=name == "embed")
                for name, (mode, I, O) in layers(m).items())
    total += m["n_layers"] * attention_fwd_flops(p * m["d_base"], seq, batch)
    return total


def client_round_flops(m: dict, p: int, tau: int, batch: int, seq: int,
                       estimate: bool = True) -> int:
    """One Heroes client's work in a round: ``tau`` SGD steps (forward +
    backward), the loss before and after on the first batch, and -- when
    the scheme ships estimates -- four gradients (three at the received
    factors, one at the trained ones)."""
    fwd = forward_flops(m, p, batch, seq)
    step = 3 * fwd
    return tau * step + 2 * fwd + (4 * step if estimate else 0)
