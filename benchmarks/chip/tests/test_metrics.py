"""Per-layer readers on the recorded v5e trace and on hand-made
contexts: shares of a peak stay within (0, 100], a reader with nothing to
read returns None."""

import gzip
import json
from pathlib import Path

import pytest

import harness
import trace_reduce as tr

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
M410 = {"max_width": 4, "d_base": 256, "heads_base": 4, "n_layers": 4,
        "ff_mult": 4, "rank": 128, "vocab": 50304, "seq_ref": 128}
TRAFFIC = json.loads((CHIP / "traffic" / "heroes-edge-sequential.json")
                     .read_text())


def reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py", name)


@pytest.fixture(scope="module")
def trace():
    with gzip.open(HERE / "trace_v5e_small.json.gz", "rt") as f:
        return tr.reduce_planes(json.load(f))


def test_compose_roofline_on_recorded_trace(trace):
    ctx = {"trace": trace, "peaks": harness.peaks_for("TPU v5 lite")}
    v = reader("compose_pallas_roofline").read(ctx)
    assert 0.0 < v <= 100.0


def test_compose_cost_by_hand():
    text = ('%_compose_pallas_3d.51 = f32[1,256,2304]{2,1,0} custom-call('
            'f32[1,256,128]{2,1,0} %a, f32[128,2304]{1,0} %b), '
            'custom_call_target="tpu_custom_call"')
    fl, by = reader("compose_pallas_roofline").kernel_cost(text)
    assert fl == 2 * 256 * 2304 * 128
    assert by == 4 * (256 * 2304 + 256 * 128 + 128 * 2304)


def test_compose_roofline_costs_each_shape():
    """Two programs' kernels share an instruction name but not a shape:
    each is costed at its own shape and count."""
    def text(n):
        return (f"%_compose_pallas_3d.51 = f32[1,{n},2304]{{2,1,0}} "
                f"custom-call(f32[1,{n},128]{{2,1,0}} %a, "
                f"f32[128,2304]{{1,0}} %b), "
                f'custom_call_target="tpu_custom_call"')
    peaks = harness.peaks_for("TPU v5 lite")
    mod = reader("compose_pallas_roofline")
    ops = {text(256): {"seconds": 2e-3, "count": 2},
           text(1024): {"seconds": 3e-3, "count": 1},
           "%fusion.1 = f32[8]{0} fusion(%_compose_pallas_3d.51)":
               {"seconds": 1.0, "count": 1}}

    def least(n):
        fl, by = mod.kernel_cost(text(n))
        return max(fl / peaks["flops_per_s"], by / peaks["hbm_bytes_per_s"])

    want = 100 * (2 * least(256) + least(1024)) / 5e-3
    got = mod.read({"trace": {"ops": ops}, "peaks": peaks})
    assert got == pytest.approx(want)
    assert least(1024) > 3 * least(256)


def test_idle_share_and_mfu(trace):
    ctx = {"kind": "train", "trace": trace, "model": M410,
           "traffic": TRAFFIC, "peaks": harness.peaks_for("TPU v5 lite"),
           "chips": 1, "device": {"count": 4},
           "assigns": [{1: {"width": 4, "tau": 2}}]}
    idle = reader("device.idle_share.train").read(ctx)
    assert 0.0 < idle < 100.0
    assert idle == pytest.approx(
        100 * (1 - trace["busy_s"] / trace["window_s"]))
    mfu = reader("mfu.train").read(ctx)
    assert 0.0 < mfu <= 100.0
    # the peak is the cell's chips', whatever number of devices JAX sees
    assert reader("mfu.train").read({**ctx, "chips": 2}) == \
        pytest.approx(mfu / 2)


def test_nothing_to_read():
    ctx = {"kind": "train", "trace": None, "peaks": None, "spans": {},
           "obs": None, "rounds": 1}
    for name in ("compose_pallas_roofline", "device.idle_share.train",
                 "mfu.train", "policy.assign_ms", "merge.ms",
                 "trainer.local_train_ms"):
        assert reader(name).read(ctx) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
