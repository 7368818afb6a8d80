#!/usr/bin/env python3
"""The program's own spans (``repro.obs``) as the benchmark reads them.

* ``per_round_seconds`` / ``per_round_attr``: per-round totals over the
  window's span events in ``ctx["obs"]`` (the engine's telemetry, on in
  a traced run).  None where the program recorded no span of that name,
  as a build that lacks the span records none.
* ``load`` / ``reduce_planes``: a profiler trace read as
  ``trace_reduce`` reads it, except that each idle gap is charged to the
  innermost span holding its midpoint among the program's spans as well
  as the benchmark's ``bench.*`` ones, and only on the thread that holds
  ``bench.window`` (a worker thread's span cannot claim the main
  thread's gaps).  Busy time, window and per-op times are
  ``trace_reduce``'s own.

    python3 benchmarks/chip/program_spans.py <trace.xplane.pb>

prints the traced window's idle seconds by span as JSON.
"""

from __future__ import annotations

from typing import Iterable, List, Optional

import trace_reduce as tr


def _spans(ctx, name: str) -> List[dict]:
    obs = ctx.get("obs")
    if not obs or ctx.get("kind") != "train":
        return []
    return [e for e in obs["spans"]
            if e.get("type") == "span" and e["name"] == name]


def per_round_seconds(ctx, name: str) -> Optional[float]:
    """Seconds a round inside the program's ``name`` spans."""
    spans = _spans(ctx, name)
    if not spans:
        return None
    return sum(e["t1"] - e["t0"] for e in spans) / ctx["rounds"]


def per_round_attr(ctx, name: str, key: str) -> Optional[float]:
    """The sum a round of attribute ``key`` over the ``name`` spans."""
    vals = [e["attrs"][key] for e in _spans(ctx, name) if key in e["attrs"]]
    if not vals:
        return None
    return sum(vals) / ctx["rounds"]


def load(path: str, names: Iterable[str]) -> List[dict]:
    """``trace_reduce.load``'s planes, keeping the host events named in
    ``names`` beside the ``bench.*`` spans."""
    from jax.profiler import ProfileData

    names = set(names)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(tr.DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name != tr.OPS_LINE:
                continue
            events = [{"name": ev.name, "start_ns": float(ev.start_ns),
                       "duration_ns": float(ev.duration_ns)}
                      for ev in line.events
                      if device or ev.name.startswith(tr.SPAN_PREFIX)
                      or ev.name in names]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def window_thread(planes: List[dict]) -> List[dict]:
    """The device planes and, of the host's, the one thread line that
    holds ``bench.window`` (no host line when none does)."""
    out = [p for p in planes if p["name"].startswith(tr.DEVICE_PREFIX)]
    for p in planes:
        if p["name"].startswith(tr.DEVICE_PREFIX):
            continue
        for line in p["lines"]:
            if any(e["name"] == tr.WINDOW_SPAN for e in line["events"]):
                return [{"name": p["name"], "lines": [line]}] + out
    return out


def reduce_planes(planes: List[dict]) -> Optional[dict]:
    """``trace_reduce.reduce_planes`` over the window's thread alone."""
    return tr.reduce_planes(window_thread(planes))


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
    from repro.obs.spans import SPANS

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xplane", help="a .xplane.pb of a traced window")
    args = ap.parse_args(argv)
    red = reduce_planes(load(args.xplane, SPANS))
    if red is None:
        print("no traced window with device operations", file=sys.stderr)
        return 1
    idle = sorted(red["gaps"].items(), key=lambda kv: -kv[1])
    print(json.dumps({"window_s": red["window_s"], "busy_s": red["busy_s"],
                      "idle_gaps": [[k, v] for k, v in idle]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
