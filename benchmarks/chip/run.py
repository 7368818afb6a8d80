#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One process: set-up (data, weights, the engine, every program the window
uses), a measured window of ``--seconds``, then the comparison with the
plain reference that decides ``correct``.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit.

A host without the chips the cell asks for exits non-zero and prints no
result.  ``--calibrate SEEDS`` (set-up and reference only, no window)
prints the readings that the limits are set from: the program's, the
bfloat16 control's and a planted fault's, per seed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
from harness import log  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--calibrate", default=None,
                    help="comma-separated seeds: print the readings the "
                         "limits are set from, no window, no result line")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="allow a CPU host (interpret-mode kernels) and run "
                         "the tiny configuration and traffic of tests/ in "
                         "the cell's place; never prints a result line")
    args = ap.parse_args(argv)
    # a traced run writes its trace here, inside the checkout
    args.trace_dir = harness.ROOT / ".bench_out" / args.workload / "trace"
    return args


def measure(args) -> dict:
    """Everything of a run but printing: the result, its checks and every
    number read against the reference (compared or not)."""
    cell = harness.Cell(args.workload, rehearsal=args.cpu_rehearsal)
    device = harness.device_info(cell.chips, allow_cpu=args.cpu_rehearsal)
    log(f"compile cache: {harness.enable_compile_cache()}")
    clog = harness.CompileLog()
    spans = harness.Spans()
    driver = cell.driver()
    if args.calibrate is not None:
        driver.calibrate(cell, [int(s) for s in args.calibrate.split(",")])
        return {}
    trace_dir = args.trace_dir
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = driver.run(cell, args, spans, clog, T_START)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    ctx = out["ctx"]
    ctx["device"] = device
    result = {"correct": harness.checks_passed(out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    if args.trace:
        import trace_reduce

        ctx["peaks"] = harness.peaks_for(device["kind"]) \
            if device["platform"] == "tpu" else None
        files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        red = trace_reduce.reduce_planes(trace_reduce.load(str(files[-1]))) \
            if files else None
        ctx["trace"] = red
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = trace_reduce.breakdown(red)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = harness.read_per_layer(cell, ctx)
    else:
        result["metrics"] = {
            mt["name"]: {"value": float(out["end_to_end"][mt["name"]]),
                         "unit": mt["unit"]}
            for mt in cell.end_to_end}
    result["device"] = device
    return {"result": result, "checks": out["checks"],
            "readings": out["readings"]}


def main(argv=None) -> int:
    args = parse(argv)
    got = measure(args)
    if not got:
        return 0
    if args.cpu_rehearsal:
        harness.log("rehearsal: " + json.dumps(got["result"]))
        for name, c in got["checks"].items():
            harness.log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
        return 0
    harness.emit(got["result"], got["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
