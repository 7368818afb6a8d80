"""Operation counts of ``flops.py`` against counts made by hand."""

import pytest

import flops

M410 = {"max_width": 4, "d_base": 256, "heads_base": 4, "n_layers": 4,
        "ff_mult": 4, "rank": 128, "vocab": 50304, "seq_ref": 128}
ROWS = 16 * 128


@pytest.mark.parametrize("p", [1, 2, 3, 4])
def test_query_projection_by_hand(p):
    # l0.wq, square, I = O = 256, R = 128, at width p over 2048 rows.
    # rank space: each of p input groups projects 256 -> 128 (2*256*128
    # per row and group), each of p*p blocks contracts 128 -> 256.
    rank = 2 * ROWS * (p * 256 * 128 + p * p * 128 * 256)
    # compose once (2*256*128 per output column of the p*p*256 columns),
    # then a dense (p*256) x (p*256) matmul per row
    dense = 2 * 256 * 128 * p * p * 256 + 2 * ROWS * (p * 256) ** 2
    assert flops.layer_fwd_flops("square", 256, 256, 128, p, ROWS) == min(
        rank, dense)
    # the rank path is the cheaper one at every width: R = d_base / 2
    assert rank < dense


@pytest.mark.parametrize("p", [1, 2, 3, 4])
def test_head_and_embedding_by_hand(p):
    V = 50304
    # head, grow_in: p groups project 256 -> 128, p blocks 128 -> V
    head = 2 * ROWS * (p * 256 * 128 + p * 128 * V)
    assert flops.layer_fwd_flops("grow_in", 256, V, 128, p, ROWS) == head
    # embedding: a row lookup of the basis, then p blocks 128 -> 256
    emb = 2 * ROWS * p * 128 * 256
    assert flops.layer_fwd_flops("grow_out", V, 256, 128, p, ROWS,
                                 lookup=True) == emb


def test_attention_counts_causal_pairs():
    # T = 4: 10 (query, key) pairs; scores and values 2 * d each
    assert flops.attention_fwd_flops(8, 4, 1) == 2 * 2 * 10 * 8


def test_client_round_adds_steps_losses_and_estimates():
    fwd = flops.forward_flops(M410, 4, 16, 128)
    assert flops.client_round_flops(M410, 4, 2, 16, 128) == (
        2 * 3 * fwd + 2 * fwd + 4 * 3 * fwd)
    assert flops.client_round_flops(M410, 4, 1, 16, 128,
                                    estimate=False) == 3 * fwd + 2 * fwd


def test_compose_cost():
    # basis (256, 128) x 16 blocks (128, 256): output 256 x 4096
    assert flops.compose_flops("square", 256, 256, 128, 4) == (
        2 * 256 * 128 * 16 * 256)
    assert flops.compose_bytes("square", 256, 256, 128, 4) == 4 * (
        256 * 128 + 16 * 128 * 256 + 256 * 16 * 256)
