"""Profiler trace -> device busy time, per-op device time and idle gaps.

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists (planes -> lines -> events), which ``reduce_planes``
turns into the numbers the per-layer readers and the ``breakdown`` use:

* the traced window: the host span ``bench.window`` (the harness wraps
  the traced rounds in it);
* busy seconds per device: the union of the intervals in which an
  operation ran on that device (its ``XLA Ops`` line), clipped to the
  window, averaged over the devices;
* per-op device seconds, by the op event's name: on a TPU that is the
  instruction's whole HLO text (``%fusion.3 = f32[...] fusion(...)``),
  so two instructions that share a name (numbering restarts in every
  program) but differ in shape are kept apart;
* idle gaps: each gap between busy intervals, attributed to the
  innermost benchmark host span (``bench.*``) that holds its midpoint.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(path: str) -> List[dict]:
    """``[{"name", "lines": [{"name", "events": [{"name", "start_ns",
    "duration_ns"}]}]}]`` for the device planes and the host
    plane's benchmark spans (everything else in a trace is dropped)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                events.append({"name": ev.name, "start_ns": float(ev.start_ns),
                               "duration_ns": float(ev.duration_ns)})
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def instruction(name: str) -> str:
    """The HLO instruction name of an op event (``%fusion.3 = f32[...]
    fusion(...)`` -> ``fusion.3``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_group(name: str) -> str:
    """An instruction name without its instance number (``fusion.12`` ->
    ``fusion``), the key the breakdown sums over."""
    return re.sub(r"[.\d]+$", "", name) or name


def reduce_planes(planes: List[dict]) -> Optional[dict]:
    """The trace's numbers, or None when it holds no traced window or no
    device operation in it."""
    host = [e for p in planes if not p["name"].startswith(DEVICE_PREFIX)
            for l in p["lines"] for e in l["events"]]
    win = [e for e in host if e["name"] == WINDOW_SPAN]
    if not win:
        return None
    w0 = min(e["start_ns"] for e in win)
    w1 = max(e["start_ns"] + e["duration_ns"] for e in win)
    spans = [e for e in host if e["name"] != WINDOW_SPAN]
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    busy_s, gaps = [], {}
    ops: Dict[str, dict] = {}
    for p in devices:
        evs = [e for l in p["lines"] for e in l["events"]
               if e["start_ns"] < w1 and e["start_ns"] + e["duration_ns"] > w0]
        for e in evs:
            o = ops.setdefault(e["name"], {"seconds": 0.0, "count": 0})
            o["seconds"] += e["duration_ns"] * 1e-9
            o["count"] += 1
        busy = _clip(_union([(e["start_ns"], e["start_ns"] + e["duration_ns"])
                             for e in evs]), w0, w1)
        busy_s.append(sum(b - a for a, b in busy) * 1e-9)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            holding = [s for s in spans
                       if s["start_ns"] <= mid <= s["start_ns"] + s["duration_ns"]]
            key = (min(holding, key=lambda s: s["duration_ns"])["name"]
                   if holding else "outside bench spans")
            gaps[key] = gaps.get(key, 0.0) + (b - a) * 1e-9 / len(devices)
    if not devices or not ops:
        return None
    window_s = (w1 - w0) * 1e-9
    return {"window_s": window_s,
            "busy_s": sum(busy_s) / len(busy_s),
            "devices": len(devices),
            "ops": ops,
            "gaps": gaps}


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device op groups that took
    most time and the idle seconds by what the host was doing."""
    groups: Dict[str, float] = {}
    for text, o in red["ops"].items():
        g = op_group(instruction(text))
        groups[g] = groups.get(g, 0.0) + o["seconds"] / red["devices"]
    dev = sorted(groups.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in dev],
            "idle_gaps": [[k, v] for k, v in idle]}
