"""The harness end to end on the CPU at a tiny size: a sound run is
correct, and a run with the timed path broken underneath is not.

Each run skips the harness's look for a chip (``--cpu-rehearsal``) and
drives everything else: data, engine, recorded rounds, warm pass,
window, reference, checks against the cell's limits.
"""

import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import harness
import run

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
CELL = "heroes-edge-sequential.pythia-410m"
ARGS = ["--workload", CELL, "--seed", "2147483661", "--seconds", "1",
        "--cpu-rehearsal"]


def measure(argv=ARGS):
    return run.measure(run.parse(argv))


def test_sound_run_is_correct():
    got = measure()
    assert got["result"]["correct"], got["checks"]
    assert got["result"]["metrics"]["round_s"]["value"] > 0
    assert got["readings"]["update1_diff"] < 0.01


def test_step_returning_its_state_unchanged_is_caught(monkeypatch):
    from repro.fl import client

    orig = client.local_train

    def unchanged(model, reduced_params, *a, **kw):
        res = orig(model, reduced_params, *a, **kw)
        return client.ClientResult(reduced_params, res.estimates,
                                   res.loss_before, res.loss_before)

    monkeypatch.setattr(client, "local_train", unchanged)
    got = measure()
    assert not got["result"]["correct"]
    assert got["checks"]["update1"]["value"] > got["checks"]["update1"]["limit"]


def test_half_of_each_batch_left_out_is_caught(monkeypatch):
    from repro.fl import client

    orig = client.data_batch

    def half(model, x, y, idx):
        return orig(model, x, y, idx[: len(idx) // 2])

    monkeypatch.setattr(client, "data_batch", half)
    got = measure()
    assert not got["result"]["correct"]


def test_altered_loss_is_caught(monkeypatch):
    from repro.fl import client

    orig = client.local_train

    def altered(*a, **kw):
        res = orig(*a, **kw)
        return client.ClientResult(res.params, res.estimates,
                                   res.loss_before * 1.05, res.loss_after)

    monkeypatch.setattr(client, "local_train", altered)
    got = measure()
    assert not got["result"]["correct"]
    assert got["checks"]["loss"]["value"] > got["checks"]["loss"]["limit"]


def test_update_merged_with_its_sign_flipped_is_caught(monkeypatch):
    """Every leaf's change keeps its norm, so ``update1`` passes; the
    norm of the difference reads 2, and the clients of rounds 2 and 3
    receive the wrong factors, which ``loss`` sees."""
    from repro.fl.engine import collective

    orig = collective.CollectiveMerger.merge_factorized

    def flipped(self, prev_params, *a, **kw):
        merged = orig(self, prev_params, *a, **kw)
        return jax.tree_util.tree_map(lambda m, p: 2 * p - m, merged,
                                      prev_params)

    monkeypatch.setattr(collective.CollectiveMerger, "merge_factorized",
                        flipped)
    got = measure()
    checks, read = got["checks"], got["readings"]
    assert not got["result"]["correct"]
    assert checks["update1"]["value"] <= checks["update1"]["limit"]
    assert checks["loss"]["value"] > checks["loss"]["limit"]
    assert read["update1_diff"] == pytest.approx(2.0, rel=1e-3)


def test_fault_in_the_replay_alone_is_caught(monkeypatch):
    """Set-up's rounds are sound and the window's replay of them skips
    local training: the reference agrees with set-up's rounds, and only
    the comparison of the window with them sees the fault."""
    from repro.fl import client

    replaying = []
    load_driver = harness.Cell.driver

    def driver(self):
        mod = load_driver(self)
        snapshot = mod._snapshot
        calls = []

        def marking(state):
            calls.append(state)
            # the second snapshot is the way back before the window
            if len(calls) == 2:
                replaying.append(True)
            return snapshot(state)

        monkeypatch.setattr(mod, "_snapshot", marking)
        return mod

    orig = client.local_train

    def skipped_in_replay(model, reduced_params, *a, **kw):
        res = orig(model, reduced_params, *a, **kw)
        if not replaying:
            return res
        return client.ClientResult(reduced_params, res.estimates,
                                   res.loss_before, res.loss_after)

    monkeypatch.setattr(harness.Cell, "driver", driver)
    monkeypatch.setattr(client, "local_train", skipped_in_replay)
    got = measure()
    checks = got["checks"]
    assert replaying and not got["result"]["correct"]
    assert checks["replay"]["value"] > checks["replay"]["limit"]
    assert all(c["value"] <= c["limit"] for k, c in checks.items()
               if k != "replay")


def _run_cli(cwd, env_extra=None):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_host_without_a_chip():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_refuses_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
