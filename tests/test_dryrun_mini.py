"""Mini dry-run integration test: the real dryrun.py entry point on a small
placeholder mesh (subprocess so XLA device count doesn't leak into other
tests)."""

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
# JAX_PLATFORMS=cpu: the child compiles for placeholder CPU devices and must
# not load the TPU library, which one process at a time may hold.
ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


def _run(args, timeout=560):
    # Artifacts go to a throwaway dir so these mini runs never pollute
    # experiments/dryrun (test_dryrun_artifacts_exist_and_parse validates
    # the real grid set there).
    with tempfile.TemporaryDirectory() as art:
        return subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", *args],
            capture_output=True, text=True, timeout=timeout,
            env={**ENV, "REPRO_DRYRUN_ART_DIR": art}, cwd=ROOT)


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", [
    ("whisper-base", "prefill_32k"),         # enc-dec
    ("whisper-base", "train_4k"),            # enc-dec train
    ("xlstm-350m", "long_500k"),             # ssm long-context decode
])
def test_dryrun_cell_mini_mesh(arch, shape):
    r = _run(["--arch", arch, "--shape", shape, "--mesh-shape", "4,2"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "compiled OK" in r.stdout


@pytest.mark.slow
def test_dryrun_multipod_mini():
    """3-axis (pod, data, model) mesh lowers and compiles."""
    r = _run(["--arch", "smollm-360m", "--shape", "decode_32k",
              "--mesh-shape", "2,2,2"])
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.mark.slow
def test_dryrun_abfp_mode_mini():
    r = _run(["--arch", "smollm-360m", "--shape", "prefill_32k",
              "--mesh-shape", "4,2", "--quant", "abfp"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_dryrun_artifacts_exist_and_parse():
    """The full-mesh grid artifacts (written by the deliverable-e run) are
    valid JSON with the fields the roofline analysis needs."""
    art = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun")
    if not os.path.isdir(art):
        pytest.skip("experiments/dryrun artifacts not generated in this "
                    "checkout (run launch.dryrun --grid to produce them)")
    files = [f for f in os.listdir(art) if f.endswith(".json")]
    if len(files) < 64:  # expected 32 cells x 2 meshes
        pytest.skip(f"partial artifact set ({len(files)} files) — full grid "
                    "not generated (run launch.dryrun --grid)")
    meshes = set()
    for f in files:
        with open(os.path.join(art, f)) as fh:
            d = json.load(fh)
        for k in ("arch", "shape", "mesh", "flops_per_device",
                  "collectives", "live_bytes_per_device"):
            assert k in d, (f, k)
        meshes.add(d["mesh"])
    assert {"16x16", "2x16x16"} <= meshes
