#!/usr/bin/env python3
"""The readings a cell's limits are set from, one phase a process.

    python3 benchmarks/chip/calibrate_split.py --workload <cell> \\
        --phase program --seeds <a,b,c> --store <dir>
    python3 benchmarks/chip/calibrate_split.py --workload <cell> \\
        --phase reference --seed <a> --store <dir>

``run.py --calibrate`` does both phases in one process.  At the
DeepSeek cell's size that process holds the engine and three float64
follows of 142 M factors at once and meets a one-chip host's 40 GiB.
Here the program phase runs the recorded rounds of each seed, saves
what the reference needs (the assignments, the client losses, the
program's server factors before round 1, after it and after the last)
under ``--store`` and frees the engine before the next seed.  While the
program runs, two threads compile the reference's step programs for
every width and variant, so that the reference phase loads them from the
persistent compile cache.  A reference phase follows one seed with the
float32 reference and prints the program's readings against it, then
follows it with each variant (``bf16``: the reference in bfloat16, the
control; ``half``: half of each batch, a planted fault) and prints its
readings against the float32 reference, one JSON line each, freeing
each follow before the next.

The readings are ``train_check.readings``, as a run of the cell reads
them.  The cell's driver names the two modules (``modules``, as
``drivers/factory_rounds.py`` has it).
"""

from __future__ import annotations

import argparse
import gc
import json
import pickle
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import data as data_lib  # noqa: E402
import harness  # noqa: E402
from harness import log  # noqa: E402


def modules(cell):
    """``(rounds, train_check)`` as the cell's driver runs them."""
    return cell.driver().modules(cell.config)


def _precompile(pool, tc, cell, token_dtype) -> list:
    """Submit to ``pool`` the compiles of the reference's step for widths
    2.. and each variant, in the order the reference phase needs them;
    returns their futures."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref, m, t = tc.ref, cell.config["model"], cell.traffic
    specs = ref.layer_specs(m)
    batch, seq = t["engine"]["batch_size"], t["seq_len"]
    mkey = tuple(sorted(m.items()))

    def compile_step(dtype, rows, width):
        full = jax.eval_shape(lambda: ref.reduce(
            ref.init_params(m, 0), np.arange(width * width),
            np.arange(width), specs))
        params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(dtype)), full)
        tok = jax.ShapeDtypeStruct((rows, seq), token_dtype)
        t0 = time.perf_counter()
        ref._step(width, mkey, dtype).lower(
            params, {"tokens": tok, "labels": tok},
            t["engine"]["lr"]).compile()
        log(f"precompiled reference {dtype} rows {rows} width {width}: "
            f"{time.perf_counter() - t0:.1f}s")

    return [pool.submit(compile_step, dtype, rows, width)
            for dtype, rows in (("float32", batch), ("bfloat16", batch),
                                ("float32", batch // 2))
            for width in range(2, m["max_width"] + 1)]


def _span_ms(events, spans: harness.Spans) -> dict:
    """Milliseconds in each program span (telemetry) and ``bench.*``
    span of one round."""
    out: dict = {}
    for e in events:
        if e.get("type") == "span":
            out[e["name"]] = out.get(e["name"], 0.0) + 1e3 * (e["t1"]
                                                              - e["t0"])
    for name, d in spans.durations.items():
        out[name] = 1e3 * sum(d)
    spans.reset()
    return {k: round(v, 3) for k, v in sorted(out.items())}


def program_phase(cell, seeds, store: Path, telemetry: bool = False
                  ) -> None:
    """The recorded rounds of each seed, saved under ``store``, while the
    reference's programs compile.  With ``telemetry`` the engine's spans
    are on (they change no number the reference reads) and each round's
    milliseconds by span are logged, the merge ended by
    ``block_until_ready``."""
    rounds, tc = modules(cell)
    # one compile keeps about six cores busy: two beside the program
    with ThreadPoolExecutor(max_workers=2) as pool:
        for f in _program_rounds(cell, rounds, tc, seeds, store, pool,
                                 telemetry):
            f.result()


def _program_rounds(cell, rounds, tc, seeds, store, pool, telemetry):
    """Returns the futures of the reference's compiles."""
    import jax
    import jax.numpy as jnp

    t = cell.traffic
    compiles: list = []
    for seed in seeds:
        t0 = time.perf_counter()
        eng, (x, _, _) = rounds.build(cell, seed, telemetry=telemetry)
        if not compiles:
            compiles = _precompile(pool, tc, cell, jnp.asarray(x[:1]).dtype)
        record: list = []
        spans = harness.Spans()
        rounds._instrument(eng, spans, sync_merge=telemetry, record=record)
        theta = {0: jax.device_get(eng.state.params)}
        losses, followed = [], []
        for r in range(1, t["check_rounds"] + 1):
            n0 = len(eng.obs.sinks[0].events) if telemetry else 0
            rounds._round(eng)
            assigns, results = record[-1]
            followed.append({"assigns": assigns})
            losses += rounds._losses(assigns, results)
            if r == 1 or r == t["check_rounds"]:
                theta[r] = jax.device_get(eng.state.params)
            log(f"seed {seed} round {r}: {rounds._describe(assigns)}")
            if telemetry:
                log(f"seed {seed} round {r} span ms: " + json.dumps(
                    _span_ms(eng.obs.sinks[0].events[n0:], spans)))
        eng.close()
        del eng, record
        gc.collect()
        with open(store / f"{seed}.pkl", "wb") as f:
            pickle.dump({"losses": losses, "theta": theta,
                         "followed": followed}, f, protocol=5)
        del theta
        gc.collect()
        log(f"seed {seed}: program saved, "
            f"{time.perf_counter() - t0:.1f}s")
    return compiles


def reference_phase(cell, seed: int, store: Path) -> None:
    import jax.numpy as jnp

    _, tc = modules(cell)
    t, m = cell.traffic, cell.config["model"]
    lr, batch = t["engine"]["lr"], t["engine"]["batch_size"]
    x, y, parts, _ = data_lib.client_text(t, seed, m["vocab"])
    with open(store / f"{seed}.pkl", "rb") as f:
        prog = pickle.load(f)
    followed = prog.pop("followed")
    t0 = time.perf_counter()
    refd = tc.follow(m, seed, lr, batch, x, y, parts, followed)
    print(json.dumps({"seed": seed, "variant": "program",
                      "readings": tc.readings(prog, refd),
                      "seconds": time.perf_counter() - t0}), flush=True)
    del prog
    gc.collect()
    for name, kw in (("bf16", {"dtype": jnp.bfloat16}),
                     ("half", {"half_batch": True})):
        t0 = time.perf_counter()
        other = tc.follow(m, seed, lr, batch, x, y, parts, followed, **kw)
        print(json.dumps({"seed": seed, "variant": name,
                          "readings": tc.readings(other, refd),
                          "seconds": time.perf_counter() - t0}), flush=True)
        del other
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--phase", choices=("program", "reference"),
                    required=True)
    ap.add_argument("--seeds", default="",
                    help="program phase: comma-separated seeds")
    ap.add_argument("--seed", type=int, default=0,
                    help="reference phase: the seed to follow")
    ap.add_argument("--store", required=True,
                    help="directory for what the program phase saves")
    ap.add_argument("--telemetry", action="store_true",
                    help="program phase: the engine's spans on, each "
                         "round's milliseconds by span logged")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="allow a CPU host and put the tiny files of "
                         "tests/ in the cell's place, as run.py does")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, rehearsal=args.cpu_rehearsal)
    harness.device_info(cell.chips, allow_cpu=args.cpu_rehearsal)
    log(f"compile cache: {harness.enable_compile_cache()}")
    clog = harness.CompileLog()
    store = Path(args.store)
    store.mkdir(parents=True, exist_ok=True)
    if args.phase == "program":
        program_phase(cell, [int(s) for s in args.seeds.split(",")], store,
                      args.telemetry)
    else:
        reference_phase(cell, args.seed, store)
    log(f"{args.phase} phase: {clog.events} programs built in "
        f"{clog.compile_s:.1f}s, {clog.cache_loads} from the cache")
    return 0


if __name__ == "__main__":
    sys.exit(main())
