"""The peak table resolves the chip the benchmark runs on and refuses any
other; the command refuses a host without a TPU."""
import os
import pathlib
import subprocess
import sys

import pytest

import harness

RUN = pathlib.Path(__file__).resolve().parent / "run.py"


def test_known_device_kind():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        harness.peaks_for(kind)


def test_command_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(RUN), "--workload", "resnet50.k4-b32-i4",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]
