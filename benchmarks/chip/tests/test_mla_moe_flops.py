"""``flops_mla_moe.py`` against counts made by hand, and the two readers
of the latent-attention + experts cell (``moe.expert_roofline``,
``mfu.train.moe``) on synthetic traces and spans."""

import json
from pathlib import Path

import pytest

import flops
import flops_mla_moe as fm
import harness

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
CONFIG = json.loads((CHIP / "configs" / "deepseek-v2-lite.P4.L5.E8.json")
                    .read_text())
M = CONFIG["model"]
TRAFFIC = json.loads((CHIP / "traffic" / "heroes-edge-seq512.json")
                     .read_text())
PEAKS = harness.peaks_for("TPU v5 lite")
ROWS = 4 * 512


def reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py", name)


@pytest.mark.parametrize("p", [1, 2, 3, 4])
def test_one_latent_attention_layer_by_hand(p):
    R, H = 128, 4 * p
    # wq, square 512 -> 768 (4 heads of 128 + 64): rank space, p groups
    # project 512 -> 128 and p*p blocks contract 128 -> 768
    wq = 2 * ROWS * (p * 512 * R + p * p * R * 768)
    # wkv_a, grow_in 512 -> 576: p groups, p blocks, output anchored
    kv_a = 2 * ROWS * (p * 512 * R + p * R * 576)
    # wkv_b, grow_out 512 -> 1024 per block: one group (the latent is
    # anchored), p blocks
    kv_b = 2 * ROWS * (512 * R + p * R * 1024)
    # wo, square 512 -> 512
    wo = 2 * ROWS * (p * 512 * R + p * p * R * 512)
    for name, want in (("wq", wq), ("wkv_a", kv_a), ("wkv_b", kv_b),
                       ("wo", wo)):
        mode, I, O = fm.layers(M)[f"l1.{name}"]
        got = flops.layer_fwd_flops(mode, I, O, R, p, ROWS)
        # the rank path is the cheaper at every width here
        assert got == want, name
    # scores over 192 dims and values over 128, on the 512*513/2 causal
    # pairs of each of 4 sequences and H heads
    att = 4 * 2 * (512 * 513 // 2) * H * (192 + 128)
    assert fm.attention_fwd_flops(M, p, 512, 4) == att


@pytest.mark.parametrize("p", [1, 2, 3, 4])
def test_one_expert_bank_by_hand(p):
    R, pairs = 128, 1536
    # per pair one expert: gate and up (512 -> 352 per block), down
    # (352 -> 512); rank space: p groups I -> R, p*p blocks R -> O
    rank = 2 * pairs * (2 * (p * 512 * R + p * p * R * 352)
                        + (p * 352 * R + p * p * R * 512))
    assert fm.bank_fwd_flops(M, p, pairs) == rank
    # composing the 8 held experts first costs more at every width
    dense = 8 * (2 * 512 * R * p * p * 352 * 2 + 2 * 352 * R * p * p * 512)
    dense += 2 * pairs * 3 * (p * 512) * (p * 352)
    assert rank < dense
    # the grouped matmuls themselves: dense per pair, weights per pass
    fl, by = fm.grouped_matmul_cost(M, p, 1, pairs, 0, 0)
    assert fl == 2 * 3 * (p * 512) * (p * 352) * pairs
    assert by == 4 * (3 * 8 * (p * 512) * (p * 352) * 4
                      + 3 * (p * 512 + p * 352) * pairs)
    # a backward pass costs twice its forward
    fl2, by2 = fm.grouped_matmul_cost(M, p, 1, pairs, 1, pairs)
    assert (fl2, by2) == (3 * fl, 3 * by)


def test_client_round_adds_steps_losses_estimates_and_pairs():
    tau, pairs, bwd = 2, 9000.0, 6000.0
    fwd = fm.dense_fwd_flops(M, 4, 4, 512)
    want = (tau * 3 * fwd + 2 * fwd + 4 * 3 * fwd
            + 2 * 4 * fm.bank_fwd_flops(M, 4, (pairs - bwd) / (2 * 4))
            + 3 * (tau + 4) * 4 * fm.bank_fwd_flops(M, 4, bwd / (6 * 4)))
    assert fm.client_round_flops(M, 4, tau, 4, 512, pairs, bwd) == (
        pytest.approx(want, rel=1e-12))


def _span(width, tau, pairs, bwd):
    return {"type": "span", "name": "trainer.local_train", "t0": 0.0,
            "t1": 1.0, "attrs": {"client": 1, "width": width, "tau": tau,
                                 "moe.routed_pairs": pairs,
                                 "backward.moe.routed_pairs": bwd}}


OPS = {
    "%ragged-dot-none.5 = f32[12288,1408]{1,0} custom-call(%a, %b), "
    'custom_call_target="tpu_custom_call"': {"seconds": 0.300, "count": 4},
    "%ragged-dot-metadata.1 = (s32[9]{0}) custom-call(%gs), "
    'custom_call_target="tpu_custom_call"': {"seconds": 0.020, "count": 4},
    "%fusion.2 = f32[12288,1408]{1,0} fusion(%ragged-dot-none.5)":
        {"seconds": 5.0, "count": 1},
}


def _ctx(spans, ops=OPS):
    return {"kind": "train", "chips": 1, "rounds": 1, "model": M,
            "traffic": TRAFFIC, "peaks": PEAKS,
            "trace": {"window_s": 10.0, "busy_s": 5.0, "ops": ops},
            "obs": {"spans": spans}}


def test_expert_roofline_by_hand():
    spans = [_span(4, 2, 9000, 6000), _span(3, 1, 5000, 3000)]
    want = 0.0
    for p, tau, pairs, bwd in ((4, 2, 9000, 6000), (3, 1, 5000, 3000)):
        fl, by = fm.grouped_matmul_cost(M, p, tau + 6, pairs, tau + 4, bwd)
        want += max(fl / PEAKS["flops_per_s"], by / PEAKS["hbm_bytes_per_s"])
    got = reader("moe.expert_roofline").read(_ctx(spans))
    # the fusion is not a grouped matmul; the metadata op is
    assert got == pytest.approx(100.0 * want / 0.320)
    assert 0.0 < got <= 100.0


def test_mfu_moe_by_hand():
    spans = [_span(4, 2, 9000, 6000)]
    total = fm.client_round_flops(M, 4, 2, 4, 512, 9000, 6000)
    got = reader("mfu.train.moe").read(_ctx(spans))
    assert got == pytest.approx(100.0 * total / 10.0 / PEAKS["flops_per_s"])
    assert 0.0 < got <= 100.0


@pytest.mark.parametrize("name", ["moe.expert_roofline", "mfu.train.moe"])
def test_readers_find_nothing_without_counts(name):
    """A build that counts no routed pairs (the parent of this cell, or
    any model without experts), an untraced run, or a trace without
    grouped matmuls: nothing to read."""
    mod = reader(name)
    bare = _span(4, 2, 0, 0)
    del bare["attrs"]["moe.routed_pairs"]
    assert mod.read(_ctx([bare])) is None
    assert mod.read({**_ctx([_span(4, 2, 9000, 6000)]), "obs": None}) is None
    assert mod.read({**_ctx([_span(4, 2, 9000, 6000)]), "trace": None}) is None
    if name == "moe.expert_roofline":
        assert mod.read(_ctx([_span(4, 2, 9000, 6000)], ops={})) is None
