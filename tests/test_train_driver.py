"""End-to-end launcher tests: train driver with checkpoint/resume (the
fault-tolerance restart path), QAT mode, and the serve driver."""

import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
# JAX_PLATFORMS=cpu: the entry points run on the CPU here and must not load the
# TPU library, which one process at a time may hold.
ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}


def _run(mod, *args, timeout=560):
    return subprocess.run(
        [sys.executable, "-m", mod, *args],
        capture_output=True, text=True, timeout=timeout, env=ENV,
        cwd=ROOT)


@pytest.mark.slow
def test_train_checkpoint_resume(tmp_path):
    ckpt = str(tmp_path / "run")
    r1 = _run("repro.launch.train", "--arch", "smollm-360m", "--reduced",
              "--steps", "8", "--ckpt-dir", ckpt, "--ckpt-every", "4",
              "--batch", "4", "--seq", "32")
    assert r1.returncode == 0, r1.stdout + r1.stderr
    assert "checkpoint ->" in r1.stdout

    # Simulated restart-after-failure: same command resumes, not restarts.
    r2 = _run("repro.launch.train", "--arch", "smollm-360m", "--reduced",
              "--steps", "12", "--ckpt-dir", ckpt, "--ckpt-every", "4",
              "--batch", "4", "--seq", "32")
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "resumed from step 8" in r2.stdout


@pytest.mark.slow
def test_train_qat_mode(tmp_path):
    r = _run("repro.launch.train", "--arch", "smollm-360m", "--reduced",
             "--steps", "3", "--quant", "qat", "--batch", "2", "--seq", "32")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "quant=qat" in r.stdout and "done" in r.stdout


@pytest.mark.slow
def test_train_compression_and_microbatches(tmp_path):
    r = _run("repro.launch.train", "--arch", "smollm-360m", "--reduced",
             "--steps", "4", "--batch", "4", "--seq", "32",
             "--microbatches", "2", "--compression", "int8")
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.mark.slow
def test_serve_driver():
    r = _run("repro.launch.serve", "--arch", "smollm-360m", "--reduced",
             "--requests", "3", "--capacity", "2", "--max-new", "3")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "3 requests" in r.stdout
