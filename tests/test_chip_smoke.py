"""``chip_smoke.py``'s contract, rehearsed on the CPU backend.

The script itself is the proof that the main path starts on a TPU; these
tests check, without a chip, that it refuses to report a result anywhere
else, and that its phases and checks pass at a rehearsal size.  Each run
is a child process with ``JAX_PLATFORMS=cpu``: the script decides its
platform as a user's process would, and the CPU backend needs no chip.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _run(args, tmp_path, cwd=ROOT, devices=1, script=SCRIPT):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
    )
    env.pop("PYTHONPATH", None)  # the script finds the package itself
    return subprocess.run(
        [sys.executable, str(script), *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600,
    )


def test_refuses_without_a_tpu(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_fails_outside_the_checkout(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone / "chip_smoke.py")
    proc = _run(["--rehearse"], tmp_path, cwd=alone, script=alone / "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("mesh", [None, "2x2"], ids=["one-chip", "2x2"])
def test_rehearsal_passes_every_phase(tmp_path, mesh):
    args = ["--rehearse"] + (["--mesh", mesh] if mesh else [])
    proc = _run(args, tmp_path, devices=4 if mesh else 1)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    phases = [json.loads(line.split("phase ", 1)[1]) for line in lines
              if line.startswith("one chip run, phase ")]
    want = (["2x2/pallas_sparse/expand+fold"] if mesh else
            ["a/pallas", "a/pallas_bf16", "b/1x1/pallas_sparse", "c/serving"])
    assert [p["phase"] for p in phases] == want
    for p in phases:
        assert not any(p["recovery"].values()), p
        assert p["compile_s"] >= 0 and p["setup_s"] >= 0 and p["run_s"] >= 0
    last = json.loads(lines[-1])
    assert last == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4 if mesh else 1},
    }
