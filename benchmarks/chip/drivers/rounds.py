"""Federated rounds through the engine's public entry points.

Set-up builds one runner (``build_runner``) over the benchmark's client
shards and drives it from the seed: round 1 (which also evaluates), then
rounds 2, 3, ... until the first ``check_rounds`` are recorded for the
reference and the rounds after round 1 are as many as the window will
replay (at the fastest round that ran without a compile).  Then it goes
back to the server state kept after round 1.  The round loops are pure
transitions of that state (the participation generator and tallies are
copied with it), so the window replays rounds 2, 3, ..., every program
they need already built.

The window runs ``EngineRunner.run_round`` until ``--seconds`` have
passed, each round ending on ``block_until_ready`` of the merged server
state.  ``round_s`` is the window over the rounds completed.  With
``--trace 1`` the window is the first ``trace_rounds`` rounds, under the
profiler and with the engine's telemetry on.

After the window, its losses and the server state of its last replayed
round that set-up recorded are compared with set-up's (``replay``: the
window must compute what the first pass computed), the runner is freed
and the reference follows the recorded rounds
(``reference/train_check.py``).
"""

from __future__ import annotations

import copy
import dataclasses
import gc
import math
import time

import numpy as np

import data as data_lib
import harness
from harness import log
from reference import train_check

WINDOW_SPAN = "bench.window"


def _snapshot(state):
    """A copy of the server state that later rounds cannot touch: its
    arrays are immutable, its generator and tallies are copied."""
    sched = state.sched
    if sched is not None:
        sched = dataclasses.replace(sched, counters=sched.counters.copy(),
                                    anchored=sched.anchored.copy())
    return dataclasses.replace(state, rng=copy.deepcopy(state.rng),
                               participation=dict(state.participation),
                               sched=sched)


def build(cell, seed: int, telemetry: bool):
    from repro.data import make_shards
    from repro.fl import FLConfig, build_runner
    from repro.fl.transformer import make_transformer

    m, t = cell.config["model"], cell.traffic
    model = make_transformer(**m)
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


def _instrument(eng, spans: harness.Spans, sync_merge: bool, record: list):
    import jax

    assign = eng.assignment.assign
    train_all = eng.trainer.train_all
    aggregate = eng.aggregator.aggregate

    def recording_train(state, assigns):
        with spans.span("bench.train_all"):
            results = train_all(state, assigns)
        # keep the losses, not the clients' trained factors
        record.append((assigns, {n: (r.loss_before, r.loss_after)
                                 for n, r in results.items()}))
        return results

    eng.assignment.assign = spans.wrap("bench.assign", assign)
    eng.trainer.train_all = recording_train
    eng.aggregator.aggregate = spans.wrap(
        "bench.merge", aggregate,
        after=(lambda st: jax.block_until_ready(st.params))
        if sync_merge else None)


def _round(eng):
    import jax

    eng.run_round()
    jax.block_until_ready(eng.state.params)


def _losses(assigns, results) -> list:
    """A round's client losses (before, after), in the program's order."""
    return [v for n in assigns for v in results[n]]


def _loss_gap(got: list, want: list) -> float:
    """Widest relative gap between two rounds' losses (inf where the
    rounds trained different numbers of clients)."""
    if len(got) != len(want):
        return math.inf
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)
                        / np.abs(want)))


def _describe(assigns) -> str:
    return " ".join(f"{int(n)}:w{a['width']}t{a['tau']}"
                    for n, a in assigns.items())


def run(cell, args, spans: harness.Spans, clog: harness.CompileLog,
        t_start: float) -> dict:
    import jax

    from repro.core.calibration import for_dispatch

    t = cell.traffic
    m = cell.config["model"]
    trace = bool(args.trace)
    eng, (x, y, parts) = build(cell, args.seed, telemetry=trace)
    record: list = []
    _instrument(eng, spans, sync_merge=trace, record=record)
    cal = for_dispatch(eng.cfg)
    for w in range(1, m["max_width"] + 1):
        impls = eng.model.layer_impls(
            w, eng.cfg.batch_size,
            eng.cfg.forward_impl, (eng.cfg.batch_size, t["seq_len"]), cal)
        log(f"width {w} layer impls: {sorted(set(impls.values()))} "
            f"{impls}")

    # round 1 (which also evaluates), then the state to come back to;
    # rounds 2.. are the rest of the recorded rounds and the warm pass
    theta = {0: jax.device_get(eng.state.params)}
    warm_losses, followed = {}, []
    kept = None
    steady, r, t_warm = [], 0, time.perf_counter()
    while True:
        r += 1
        ev0 = clog.events
        t0 = time.perf_counter()
        _round(eng)
        dt = time.perf_counter() - t0
        assigns, results = record[-1]
        log(f"round {r}: {_describe(assigns)} {dt:.3f}s "
            f"compiles {clog.events - ev0}")
        warm_losses[r] = _losses(assigns, results)
        if r <= t["check_rounds"]:
            followed.append({"assigns": assigns})
            theta[r] = jax.device_get(eng.state.params)
        if r == 1:
            kept = _snapshot(eng.state)
            continue
        if clog.events == ev0:
            steady.append(dt)
        if r < t["check_rounds"]:
            continue
        if r - 1 >= t["max_warm_rounds"]:
            break
        if len(steady) >= 2:
            # the window ends on the round that brings it to --seconds:
            # warm as many rounds as that takes at the fastest steady
            # round, with warm_margin of room; a traced window replays
            # trace_rounds rounds, whatever --seconds
            need = (t["trace_rounds"] if trace else math.ceil(
                t["warm_margin"] * args.seconds / min(steady)))
            if r - 1 >= need:
                break
    prog_losses = [v for rr in range(1, t["check_rounds"] + 1)
                   for v in warm_losses[rr]]
    log(f"rounds 1-{r}: {time.perf_counter() - t_warm:.1f}s; the window "
        f"replays them from round 2")
    eng.state = _snapshot(kept)
    record.clear()
    spans.reset()
    gc.collect()

    n_spans0 = len(eng.obs.sinks[0].events) if trace else 0
    if trace:
        jax.profiler.start_trace(str(args.trace_dir))
    ev0, cs0 = clog.mark()
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    rounds = 0
    checked = None
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while True:
            _round(eng)
            rounds += 1
            if rounds + 1 in theta:
                # the server state of the last replayed round that set-up
                # recorded (a reference: no copy inside the window)
                checked = (rounds + 1, eng.state.params)
            elapsed = time.perf_counter() - t0
            if (trace and rounds >= t["trace_rounds"]) or (
                    not trace and elapsed >= args.seconds):
                break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    compiles_window = clog.events - ev0
    log(f"window: {rounds} rounds in {window_s:.3f}s, "
        f"{compiles_window} compiles; set-up {setup_s:.1f}s "
        f"({clog.events} programs built in {cs0:.1f}s, {clog.cache_loads} "
        f"of them from the persistent cache)")
    window_assigns = [a for a, _ in record]
    losses = [v for a, res in record for n in a for v in res[n]]
    failed = sum(1 for a, res in record
                 if not np.isfinite([res[n] for n in a]).all())
    # the window replays rounds 2, 3, ...: its losses and its server state
    # have to be those that set-up recorded for the same rounds
    replay = [_loss_gap(_losses(a, res), warm_losses[i + 2])
              for i, (a, res) in enumerate(record) if i + 2 in warm_losses]
    if checked is not None:
        rn, params = checked
        replay.append(train_check.diff_gap(
            jax.device_get(params), theta[0], theta[rn], theta[0]))
    del checked
    peak = harness.memory_peak_bytes()
    # the program's telemetry of the window (on in the traced run only)
    obs = {"spans": eng.obs.sinks[0].events[n_spans0:]} if trace else None
    eng.close()
    del eng, record
    gc.collect()

    ctx = {"kind": "train", "chips": cell.chips, "rounds": rounds,
           "window_s": window_s,
           "compile_setup_s": cs0,
           "compiles_window": compiles_window, "spans": dict(spans.durations),
           "assigns": window_assigns, "obs": obs, "model": m, "traffic": t}
    end_to_end = {"round_s": window_s / rounds, "setup_s": setup_s}

    # the reference follows the recorded rounds
    t0 = time.perf_counter()
    prog = {"losses": prog_losses, "theta": theta}
    refd = train_check.follow(m, args.seed, t["engine"]["lr"],
                              t["engine"]["batch_size"], x, y, parts,
                              followed)
    read = train_check.readings(prog, refd)
    read["replay"] = max(replay) if replay else math.inf
    log(f"reference: {time.perf_counter() - t0:.1f}s, readings {read}")
    # a reading the cell's limits do not name is printed, not compared
    # (PERF.md gives why for each)
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in read.items() if k in cell.limits}
    checks["window_loss_finite"] = {
        "value": 0.0 if np.isfinite(losses).all() else 1.0, "limit": 0.0}
    return {"end_to_end": end_to_end, "ctx": ctx, "checks": checks,
            "readings": read, "attempted": rounds, "failed": failed,
            "memory_peak_bytes": peak}


def calibrate(cell, seeds, controls: int = 3) -> None:
    """Readings the limits are set from, one JSON line per seed: the
    program against the reference and, on the first ``controls`` seeds,
    the reference computed in bfloat16 (the control) and the reference
    with half of each batch left out (a planted fault), each against the
    float32 reference.  Set-up and the recorded rounds only; no window."""
    import json

    import jax
    import jax.numpy as jnp

    t = cell.traffic
    m = cell.config["model"]
    lr, batch = t["engine"]["lr"], t["engine"]["batch_size"]
    for seed in seeds:
        t0 = time.perf_counter()
        eng, (x, y, parts) = build(cell, seed, telemetry=False)
        record: list = []
        _instrument(eng, harness.Spans(), sync_merge=False, record=record)
        theta = {0: jax.device_get(eng.state.params)}
        losses, followed = [], []
        for r in range(1, t["check_rounds"] + 1):
            _round(eng)
            assigns, results = record[-1]
            followed.append({"assigns": assigns})
            losses += [v for n in assigns for v in results[n]]
            if r == 1 or r == t["check_rounds"]:
                theta[r] = jax.device_get(eng.state.params)
        eng.close()
        del eng, record
        gc.collect()
        t1 = time.perf_counter()
        refd = train_check.follow(m, seed, lr, batch, x, y, parts, followed)
        t2 = time.perf_counter()
        out = {"seed": seed,
               "program": train_check.readings(
                   {"losses": losses, "theta": theta}, refd)}
        lp, lr_ = np.asarray(losses), refd["losses"]
        rel = np.abs(lp - lr_) / np.abs(lr_)
        lc = np.full_like(lp, np.nan)
        variants = (("control_bf16", {"dtype": jnp.bfloat16}),
                    ("fault_half_batch", {"half_batch": True}))
        for name, kw in variants[: 2 * (seeds.index(seed) < controls)]:
            other = train_check.follow(m, seed, lr, batch, x, y, parts,
                                       followed, **kw)
            out[name] = train_check.readings(other, refd)
            if name == "control_bf16":
                lc = other["losses"]
        # the look behind the loss number: the widest gaps at the received
        # factors (even entries) and after local training (odd entries)
        out["worst_losses"] = [
            [*refd["who"][i], float(lp[i]), float(lr_[i]), float(lc[i]),
             float(rel[i])]
            for half in (0, 1)
            for i in half + 2 * np.argsort(-rel[half::2])[:3]]
        out["seconds"] = {"setup_and_rounds": t1 - t0, "reference": t2 - t1}
        print(json.dumps(out), flush=True)
