"""``chip_smoke.py`` on the CPU: a tiny rehearsal passes, a real run refuses.

The rehearsal drives every one-chip phase in-process at 64K rows with the
Pallas kernels interpreting; without ``--rehearse`` the script must exit
non-zero and print no result, both in the repo and copied out of it alone.
"""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_passes_on_cpu(capsys):
    rc = _chip_smoke().main(["--rehearse", "--rows", "65536"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    last = json.loads(out[-1])
    assert last["ok"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": len(jax.devices())}
    phases = [line.split(":")[0] for line in out if line.startswith("phase ")]
    assert {"phase warmup", "phase rows", "phase where", "phase aggregates",
            "phase refresh", "phase kernel"} <= set(phases)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_without_rehearse_cpu_exits_nonzero(where, tmp_path):
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
