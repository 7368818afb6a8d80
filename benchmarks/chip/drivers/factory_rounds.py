"""Federated rounds of a model that the configuration names: the rounds
driver's set-up, window, replay, comparison and calibration, on the
model ``repro.fl.transformer.<factory>`` builds from the configuration's
``model`` keys and followed by ``reference/<reference>.py``.

Everything but the model and its reference is ``rounds.py``'s: this
module runs a private copy of it (and of ``reference/train_check.py``)
whose ``build`` makes the named model and whose ``train_check`` follows
the named reference.  Nothing shared is altered.

An expert bank's coefficient is ``(blocks, E, R, O)`` in the program and
``(blocks, E*R, O)`` in the reference (whose Eq. 5 merge works on the
leading block axis of a 3-d tensor); the comparison views the program's
with its expert and rank axes merged, which changes no number in it.
"""

from __future__ import annotations

import dataclasses
import importlib
from pathlib import Path

import numpy as np

import data as data_lib
import harness

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent


def _blocks_3d(leaf):
    return leaf.reshape(leaf.shape[0], -1, leaf.shape[-1])


def modules(config: dict):
    """``(rounds, train_check)``: private modules of the two files, the
    first building the configuration's model, the second following its
    reference.

    This leans on names the two files keep to themselves: it replaces
    ``train_check.ref`` (the reference module), ``train_check._changes``
    (the per-leaf changes the readings compare), ``rounds.train_check``
    and ``rounds.build``.  A rename there breaks this driver; folding
    the two drivers (``rounds.build`` taking the factory from the
    configuration, ``train_check`` the reference) would end that."""
    tc = harness.load_module(CHIP / "reference" / "train_check.py",
                             "reference._train_check_" + config["reference"])
    tc.ref = importlib.import_module("reference." + config["reference"])
    changes = tc._changes

    def changes_3d(after, before):
        return {k: _blocks_3d(v) for k, v in changes(after, before).items()}

    tc._changes = changes_3d
    rounds = harness.load_module(HERE / "rounds.py",
                                 "driver_rounds_" + config["factory"])
    rounds.train_check = tc
    rounds.build = build
    return rounds, tc


def build(cell, seed: int, telemetry: bool):
    """``rounds.build`` with the model the configuration names."""
    from repro.data import make_shards
    from repro.fl import FLConfig, build_runner
    from repro.fl import transformer

    m, t = cell.config["model"], cell.traffic
    model = getattr(transformer, cell.config["factory"])(**m)
    x, y, parts, test = data_lib.client_text(t, seed, m["vocab"])
    px, py = make_shards(x, y, parts)
    cfg = FLConfig(**t["engine"], seed=seed,
                   trainer_mesh_devices=cell.chips, agg_devices=cell.chips,
                   telemetry="memory" if telemetry else "off")
    eng = build_runner(t["scheme"], model, px, py, test, cfg=cfg,
                       seed=t["fleet_seed"],
                       tier_weights=tuple(t["tier_weights"]))
    # which clients join each round is part of the traffic, not of the
    # seed: every seed runs the same cohorts over its own data and weights
    eng.state = dataclasses.replace(
        eng.state, rng=np.random.default_rng(t["cohort_seed"]))
    return eng, (x, y, parts)


def run(cell, args, spans, clog, t_start: float) -> dict:
    return modules(cell.config)[0].run(cell, args, spans, clog,
                                               t_start)


def calibrate(cell, seeds, controls: int = 3) -> None:
    modules(cell.config)[0].calibrate(cell, seeds, controls)
