"""Cell lookup, device checks, compile accounting, host spans and the
result line: everything a run shares, whatever its traffic.

Every piece of a cell is found by name from ``BENCHMARK.json``: the
configuration's file, ``traffic/<traffic>.json`` (whose ``driver`` key
names ``drivers/<driver>.py``), ``limits/<workload>.json`` and one
``metrics/<metric>.py`` reader per per-layer metric.  A new cell adds
files and entries; nothing here names a cell.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_BYTES = 4 << 30


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file whose name need not be a Python identifier
    (``metrics/policy.assign_ms.py``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry with its configuration, traffic, limits
    and the metrics ``BENCHMARK.json`` asks of it.

    ``rehearsal`` puts the tiny configuration and traffic of ``tests/``
    in the cell's place (CPU rehearsals and tests; never a result)."""

    def __init__(self, workload: str, rehearsal: bool = False):
        bench = load_json(ROOT / "BENCHMARK.json")
        try:
            self.entry = next(w for w in bench["workloads"]
                              if w["name"] == workload)
        except StopIteration:
            raise SystemExit(f"unknown workload {workload!r}") from None
        self.name = workload
        self.chips = int(self.entry["chips"])
        conf = next(c for c in bench["configs"]
                    if c["name"] == self.entry["config"])
        if rehearsal:
            self.config = load_json(HERE / "tests" / "tiny.json")
            self.traffic = load_json(HERE / "tests" / "traffic-tiny.json")
        else:
            self.config = load_json(ROOT / conf["file"])
            self.traffic = load_json(
                HERE / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = load_json(HERE / "limits" / f"{workload}.json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]

    def driver(self):
        name = self.traffic["driver"]
        return load_module(HERE / "drivers" / f"{name}.py", f"driver_{name}")


# ---------------------------------------------------------------------------
# device and compile cache
# ---------------------------------------------------------------------------


def device_info(chips: int, allow_cpu: bool = False) -> dict:
    """Platform, kind and count as JAX reports them.  Exits non-zero,
    before any result is printed, on a host without the chips the cell
    asks for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"jax {jax.__version__} devices {info}")
    if not allow_cpu and info["platform"] != "tpu":
        log(f"no accelerator: jax.devices()[0].platform is "
            f"{info['platform']!r}")
        sys.exit(3)
    if info["count"] < chips:
        log(f"the cell needs {chips} chips, JAX sees {info['count']}")
        sys.exit(3)
    return info


def enable_compile_cache() -> str:
    """Persistent compilation cache at a fixed place in the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), for every program however
    short its compile: the evaluation's eager ops each compile in well
    under JAX's default one-second floor."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # one cell's TPU executables (about 30 step programs of some MB each,
    # and the reference's) fill about 180 MiB; below room for every
    # cell's, the cache evicts one cell's programs for another's and a run
    # then compiles for minutes
    if 0 <= jax.config.jax_compilation_cache_max_size < CACHE_BYTES:
        jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Counts programs built through ``jax.monitoring``.

    JAX times every program it has to obtain, whether it compiles it or
    loads it from the persistent cache, as one backend-compile event:
    ``events`` counts them, ``compile_s`` sums their seconds, and
    ``cache_loads`` counts the ones the persistent cache served."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    RETRIEVE = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.events = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.BACKEND:
            self.events += 1
            self.compile_s += float(duration)
        elif event == self.RETRIEVE:
            self.cache_loads += 1

    def mark(self) -> tuple:
        return (self.events, self.compile_s)


# ---------------------------------------------------------------------------
# host spans (profiler annotations + host clock)
# ---------------------------------------------------------------------------


class Spans:
    """Host spans around the calls into each layer: a
    ``jax.profiler.TraceAnnotation`` (so they sit on the device trace's
    clock) plus a host-clock duration kept per name."""

    def __init__(self):
        self.durations: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.durations.setdefault(name, []).append(time.perf_counter() - t0)

    def wrap(self, name: str, fn: Callable, after: Optional[Callable] = None
             ) -> Callable:
        def wrapped(*a, **kw):
            with self.span(name):
                out = fn(*a, **kw)
                if after is not None:
                    after(out)
            return out
        return wrapped

    def reset(self) -> None:
        self.durations.clear()


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest local device, where reported."""
    import jax

    peaks = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(kind: str) -> dict:
    """The published peaks of ``kind``; a device missing from the table
    is an error, never a default."""
    table = load_json(HERE / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# per-layer metrics and the result line
# ---------------------------------------------------------------------------


def read_per_layer(cell: Cell, ctx: dict) -> Dict[str, dict]:
    """Each per-layer metric's reader, by name; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(HERE / "metrics" / f"{m['name']}.py",
                          "metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is None:
            log(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def checks_passed(checks: Dict[str, dict]) -> bool:
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())


def emit(result: dict, checks: Dict[str, dict]) -> None:
    """Print the compared numbers beside their limits as the last lines
    of standard error, then the result line (``checks`` last) as the last
    line of standard output."""
    for name, c in checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}")
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
