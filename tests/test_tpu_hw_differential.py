"""Real-chip differential job wrapper.

The normal suite forces JAX to the CPU platform (conftest.py), so the
hardware job runs in subprocesses with their own env.  Enabled with
RUN_TPU_TESTS=1; kept out of the default run because the chip-side
kernel compile costs minutes per fresh process with a cold cache.
A small smoke variant (RUN_TPU_TESTS unset) still exercises the
orchestration path end-to-end on the CPU platform only, so the job
itself cannot rot.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "tpu_differential.py")


@pytest.mark.skipif(not os.environ.get("RUN_TPU_TESTS"),
                    reason="needs the real TPU (set RUN_TPU_TESTS=1)")
def test_differential_suite_on_real_chip():
    r = subprocess.run(
        [sys.executable, SCRIPT, "orchestrate", "--n", "10000"],
        capture_output=True, text=True, timeout=3600)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    assert r.returncode == 0
    assert "TPU DIFFERENTIAL: PASS" in r.stdout


@pytest.mark.skipif(not os.environ.get("RUN_TPU_TESTS"),
                    reason="needs the real TPU (set RUN_TPU_TESTS=1)")
def test_differential_fast_on_real_chip():
    """Small-bucket chip tier: full strict-check corpus vs the oracle,
    <2 min warm — `RUN_TPU_TESTS=1 pytest -k fast`."""
    r = subprocess.run(
        [sys.executable, SCRIPT, "fast"],
        capture_output=True, text=True, timeout=600)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    assert r.returncode == 0
    assert "FAST DIFFERENTIAL: PASS" in r.stdout


def test_differential_vectors_on_cpu_smoke():
    """The same job, CPU-platform subprocess, small n: proves the
    vectors + runner stay green without the chip."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    out = os.path.join(REPO, "tests", ".tpu-diff-smoke.npz")
    r = subprocess.run(
        [sys.executable, SCRIPT, "run", "--out", out, "--n", "64"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    assert r.returncode == 0
    assert '"mismatches_vs_oracle": 0' in r.stdout
    os.unlink(out)
