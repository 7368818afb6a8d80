"""``trainer.estimate_compiled_share`` on hand-made span lists: the
window's ``compiled`` attributes over its ``trainer.estimate`` spans, and
None where no span carries ``compiled``."""

from pathlib import Path

import pytest

import harness

CHIP = Path(__file__).resolve().parents[1]


def reader():
    return harness.load_module(CHIP / "metrics"
                               / "trainer.estimate_compiled_share.py",
                               "trainer_estimate_compiled_share")


def estimate(**attrs):
    return {"type": "span", "name": "trainer.estimate", "clock": "wall",
            "t0": 0.0, "t1": 1.0, "attrs": attrs}


def ctx(spans, rounds=2):
    return {"kind": "train", "rounds": rounds, "obs": {"spans": spans}}


def test_every_client_compiled_reads_one():
    spans = [estimate(compiled=1) for _ in range(20)] + [
        {"type": "span", "name": "trainer.loss", "clock": "wall",
         "t0": 1.0, "t1": 2.0, "attrs": {}}]
    assert reader().read(ctx(spans)) == 1.0


def test_share_counts_every_estimate_span():
    spans = [estimate(compiled=1)] * 3 + [estimate()]
    assert reader().read(ctx(spans)) == pytest.approx(3 / 4)


@pytest.mark.parametrize("c", [
    ctx([estimate(), estimate()]),
    ctx([]),
    {"kind": "train", "rounds": 1, "obs": None},
    {"kind": "serve", "rounds": 1, "obs": {"spans": [estimate(compiled=1)]}},
])
def test_nothing_to_read(c):
    assert reader().read(c) is None
