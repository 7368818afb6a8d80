"""``merge.device_input_share`` on hand-made span lists: the window's
``device_input`` over its ``clients``, and None where the program's
``merge.prep`` spans carry no ``device_input``."""

from pathlib import Path

import pytest

import harness

CHIP = Path(__file__).resolve().parents[1]


def reader():
    return harness.load_module(CHIP / "metrics"
                               / "merge.device_input_share.py",
                               "merge_device_input_share")


def prep(**attrs):
    return {"type": "span", "name": "merge.prep", "clock": "wall",
            "t0": 0.0, "t1": 1.0, "attrs": attrs}


def ctx(spans, rounds=2):
    return {"kind": "train", "rounds": rounds, "obs": {"spans": spans}}


def test_every_client_read_on_the_device_reads_one():
    spans = [prep(clients=10, device_scatter=10, device_input=10),
             prep(clients=10, device_scatter=10, device_input=10),
             {"type": "span", "name": "merge.compiled", "clock": "wall",
              "t0": 1.0, "t1": 2.0, "attrs": {"h2d_bytes": 5}}]
    assert reader().read(ctx(spans)) == 1.0


def test_share_sums_over_the_window():
    spans = [prep(clients=10, device_input=10),
             prep(clients=6, device_input=0)]
    assert reader().read(ctx(spans)) == pytest.approx(10 / 16)


@pytest.mark.parametrize("c", [
    ctx([prep(clients=10, device_scatter=10),
         prep(clients=10, device_scatter=10)]),
    ctx([]),
    {"kind": "train", "rounds": 1, "obs": None},
    {"kind": "serve", "rounds": 1,
     "obs": {"spans": [prep(clients=3, device_input=3)]}},
])
def test_nothing_to_read(c):
    assert reader().read(c) is None
