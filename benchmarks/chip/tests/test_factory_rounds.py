"""The factory driver end to end on the CPU at a tiny size, on a tiny
DeepSeek-shaped model (``tiny-mla-moe.json``, traffic
``traffic-tiny-factory.json``): a sound run is correct, and a run with
the merge or the batches broken underneath is not.

The harness's ``--cpu-rehearsal`` puts ``tiny.json`` (a Pythia-shaped
decoder) in a cell's place; here a ``Cell`` that puts the tiny
DeepSeek-shaped files there instead drives everything else as a run of
the cell does: data, engine, recorded rounds, warm pass, window,
reference, checks against the cell's limits.
"""

import pytest

import harness
import run

CELL = "heroes-edge-seq512.deepseek-v2-lite"
ARGS = ["--workload", CELL, "--seed", "2147483661", "--seconds", "1",
        "--cpu-rehearsal"]


class TinyCell(harness.Cell):
    def __init__(self, workload, rehearsal=False):
        super().__init__(workload, rehearsal)
        if rehearsal:
            tests = harness.HERE / "tests"
            self.config = harness.load_json(tests / "tiny-mla-moe.json")
            self.traffic = harness.load_json(
                tests / "traffic-tiny-factory.json")


@pytest.fixture
def measure(monkeypatch):
    monkeypatch.setattr(harness, "Cell", TinyCell)
    return lambda *extra: run.measure(run.parse(ARGS + list(extra)))


def test_sound_run_is_correct(measure):
    got = measure()
    assert got["result"]["correct"], got["checks"]
    assert got["result"]["metrics"]["round_s"]["value"] > 0
    assert got["readings"]["update1_diff"] < 0.01


def test_blocks_merged_with_the_wrong_sign_are_caught(measure, monkeypatch):
    """The merged coefficient blocks move away from where Eq. 5 puts
    them by as much as they should move towards it."""
    from repro.fl.engine import collective

    orig = collective.CollectiveMerger.merge_factorized

    def flipped(self, prev_params, *a, **kw):
        merged = orig(self, prev_params, *a, **kw)
        return {n: {"basis": m["basis"],
                    "coeff": 2 * prev_params[n]["coeff"] - m["coeff"]}
                for n, m in merged.items()}

    monkeypatch.setattr(collective.CollectiveMerger, "merge_factorized",
                        flipped)
    got = measure()
    assert not got["result"]["correct"]
    assert got["readings"]["update1_diff"] > 1.0


def test_half_of_each_batch_left_out_is_caught(measure, monkeypatch):
    from repro.fl import client

    orig = client.data_batch

    def half(model, x, y, idx):
        return orig(model, x, y, idx[: len(idx) // 2])

    monkeypatch.setattr(client, "data_batch", half)
    got = measure()
    assert not got["result"]["correct"]


def test_the_window_counts_routed_pairs(measure, monkeypatch):
    """With ``--trace 1`` the engine's telemetry is on: every client span
    of the window carries the forward's counts, and both readers of the
    cell find them (no device trace on the CPU, so they read nothing)."""
    import flops_mla_moe

    seen = {}
    read = harness.read_per_layer

    def keep(cell, ctx):
        seen.update(ctx)
        return read(cell, ctx)

    monkeypatch.setattr(harness, "read_per_layer", keep)
    got = measure("--trace", "1")
    assert got["result"]["correct"], got["checks"]
    clients = flops_mla_moe.counted_clients(seen)
    assert clients and all(0 < c["moe.routed_pairs"] < c["moe.pairs_total"]
                           for c in clients)
    assert got["result"]["metrics"] == {}



def test_control_and_fault_fail_the_limits(capsys):
    """As ``test_control.py`` does for the Pythia cells: the reference in
    bfloat16 and the half-batch fault, in the program's place, each fail
    one of the cell's limits; the program passes them."""
    import json

    cell = TinyCell(CELL, rehearsal=True)
    cell.driver().calibrate(cell, [5], controls=1)
    out = [json.loads(l) for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]

    def fails(r):
        return any(r[k] > cell.limits[k] for k in r if k in cell.limits)

    assert len(out) == 1
    assert not fails(out[0]["program"]), out
    assert fails(out[0]["control_bf16"]), out
    assert fails(out[0]["fault_half_batch"]), out


def test_split_calibration_reads_what_calibrate_reads(capsys, tmp_path,
                                                      monkeypatch):
    """``calibrate_split.py``, one phase a process on the chip, here one
    after the other: the program phase saves a seed, the reference phase
    reads it and prints the program's, the control's and the fault's
    readings; the program passes the cell's limits, the control and the
    fault each fail one."""
    import json

    import calibrate_split

    monkeypatch.setattr(harness, "Cell", TinyCell)
    common = ["--workload", CELL, "--store", str(tmp_path), "--cpu-rehearsal"]
    assert calibrate_split.main(common + ["--phase", "program",
                                          "--seeds", "5"]) == 0
    assert (tmp_path / "5.pkl").exists()
    assert calibrate_split.main(common + ["--phase", "reference",
                                          "--seed", "5"]) == 0
    got = {r["variant"]: r["readings"] for r in (
        json.loads(l) for l in capsys.readouterr().out.splitlines()
        if l.startswith("{"))}
    limits = TinyCell(CELL, rehearsal=True).limits

    def fails(r):
        return any(r[k] > limits[k] for k in r if k in limits)

    assert set(got) == {"program", "bf16", "half"}
    assert not fails(got["program"]), got
    assert fails(got["bf16"]) and fails(got["half"]), got
