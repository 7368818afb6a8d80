"""``chip_smoke.py`` on the CPU: its rehearsal mode runs every phase at
tiny sizes with interpret-mode kernels, and without a TPU the script
refuses to run (subprocesses: the script owns its jax initialisation)."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _run(script, *args, cwd=ROOT, timeout=600):
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=ENV, capture_output=True, text=True,
                          timeout=timeout)


def _last_line(stdout: str) -> str:
    lines = stdout.strip().splitlines()
    return lines[-1] if lines else ""


def test_chip_smoke_cpu_rehearsal_runs_every_phase():
    r = _run(SCRIPT, "--cpu-rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    for phase in ("transformer phase", "serving phase", "image phase",
                  "kernel parity"):
        assert f"== {phase}" in r.stdout, phase
    assert "mesh" not in r.stdout  # the 4-device phase runs only on request
    last = json.loads(_last_line(r.stdout))
    assert last == {"rehearsal": "passed",
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    assert '"ok"' not in r.stdout


def test_chip_smoke_refuses_a_host_without_tpu():
    r = _run(SCRIPT, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """Without the repository around it the script has nothing to run."""
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    r = _run(lone, cwd=tmp_path, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_dir(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache
    sits at one fixed, git-ignored path inside the checkout."""
    from repro.compile_cache import DEFAULT_DIR, enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert DEFAULT_DIR.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{DEFAULT_DIR.name}/" in ignored
