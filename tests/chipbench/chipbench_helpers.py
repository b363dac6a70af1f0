"""Run the chip benchmark's command in a child process on the CPU."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BIG_SEED = 2**31 + 12345
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def bench_env(tmp_path: pathlib.Path) -> dict:
    """The CPU backend, the system under test on the path, and a compile
    cache of the test's own (never the checkout's)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env.pop("XLA_FLAGS", None)
    return env


def run_cell(tmp_path, *args: str, code: str | None = None,
             cwd: pathlib.Path = ROOT, timeout: float = 240):
    """Run ``python3 -m chipbench.run <args>`` (or ``code``, a script that
    calls ``chipbench.run.main``); return (exit code, last stdout line
    parsed or None, stdout, stderr)."""
    cmd = [sys.executable, "-m", "chipbench.run", *args] if code is None \
        else [sys.executable, "-c", code, *args]
    p = subprocess.run(cmd, cwd=cwd, env=bench_env(tmp_path),
                       capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except json.JSONDecodeError:
            last = None
    return p.returncode, last, p.stdout, p.stderr


def bench_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())
