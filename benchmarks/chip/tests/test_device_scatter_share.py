"""``merge.device_scatter_share`` on hand-made span lists: the window's
``device_scatter`` over its ``clients``, and None where the program's
``merge.prep`` spans carry no ``device_scatter``."""

from pathlib import Path

import pytest

import harness

CHIP = Path(__file__).resolve().parents[1]


def reader():
    return harness.load_module(CHIP / "metrics"
                               / "merge.device_scatter_share.py",
                               "merge_device_scatter_share")


def prep(**attrs):
    return {"type": "span", "name": "merge.prep", "clock": "wall",
            "t0": 0.0, "t1": 1.0, "attrs": attrs}


def ctx(spans, rounds=2):
    return {"kind": "train", "rounds": rounds, "obs": {"spans": spans}}


def test_every_client_scattered_on_the_device_reads_one():
    spans = [prep(clients=10, device_scatter=10),
             prep(clients=10, device_scatter=10),
             {"type": "span", "name": "merge.compiled", "clock": "wall",
              "t0": 1.0, "t1": 2.0, "attrs": {"h2d_bytes": 5}}]
    assert reader().read(ctx(spans)) == 1.0


def test_share_sums_over_the_window():
    spans = [prep(clients=10, device_scatter=10), prep(clients=6)]
    assert reader().read(ctx(spans)) == pytest.approx(10 / 16)


@pytest.mark.parametrize("c", [
    ctx([prep(clients=10), prep(clients=10)]),
    ctx([]),
    {"kind": "train", "rounds": 1, "obs": None},
    {"kind": "serve", "rounds": 1,
     "obs": {"spans": [prep(clients=3, device_scatter=3)]}},
])
def test_nothing_to_read(c):
    assert reader().read(c) is None
