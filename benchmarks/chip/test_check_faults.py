"""The check refuses what it must, at reduced sizes on the CPU.

* The control: the reference in bfloat16 put in the program's place.
* The faults a training cell can have, planted in the program under a
  whole run (set-up, window, reference, check): a step that returns its
  state unchanged, half of every batch left out with the mean taken over
  the rest, and the exchange between workers left out.  A cell's limits
  must turn each into ``correct`` false.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

import harness
import small

CELLS = ["resnet50.k4-b32-i4", "stablelm2-1.6b-share.k1-b16-s512-i4"]


def _state_unchanged(monkeypatch):
    from repro.core import coda

    monkeypatch.setattr(coda, "apply_grads",
                        lambda ccfg, state, grads, eta: state)


def _half_batch(monkeypatch):
    from repro.core import coda

    whole = coda.grad_step_scores

    def half(mcfg, ccfg, state, batch):
        return whole(mcfg, ccfg, state, jax.tree_util.tree_map(
            lambda l: l[:, :l.shape[1] // 2], batch))

    monkeypatch.setattr(coda, "grad_step_scores", half)


def _no_exchange(monkeypatch):
    from repro.core import bucketing

    monkeypatch.setattr(bucketing, "average_state",
                        lambda state, wa, compress, **kw: state)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "no_exchange": _no_exchange}


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    import calibrate

    cell = small.cell(name)
    batches = calibrate.window_batches(cell, 2 ** 31 + 11)
    want = harness.reference(cell, 2 ** 31 + 11, batches)
    got = harness.reference(cell, 2 ** 31 + 11, batches, control=True)
    checks = harness.check_numbers(cell, got, want, 0)
    assert not harness.passed(checks), checks


@pytest.mark.parametrize("name,fault", [
    (c, f) for c in CELLS for f in FAULTS
    if not (f == "no_exchange" and small.cell(c).K == 1)])
def test_fault_fails(monkeypatch, name, fault):
    monkeypatch.setattr(harness, "peaks_for", small.v5e_peaks)
    FAULTS[fault](monkeypatch)
    result, _ = harness.run(small.cell(name), 2 ** 31 + 3, 0.2, False)
    assert result["correct"] is False, result["checks"]


FOUR_CHIPS = """
import json, sys
sys.path[:0] = sys.argv[1:]
import harness, small
from repro.core import bucketing
harness.peaks_for = small.v5e_peaks
cell = small.cell("resnet50.4chip.k4-b32-i4")
sound, _ = harness.run(cell, 2 ** 31 + 21, 0.2, False)
bucketing.average_state = lambda state, wa, compress, **kw: state
broken, _ = harness.run(cell, 2 ** 31 + 21, 0.2, False)
print(json.dumps({"sound": sound["correct"], "broken": broken["correct"],
                  "checks": broken["checks"], "count": sound["device"]["count"]}))
"""


def test_four_chip_path_and_exchange_fault():
    """The four-chip cell on four virtual CPU devices: a sound run is
    correct, and one without the all-reduce is not."""
    here = pathlib.Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(here),
         str(here.parents[1] / "src")],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["count"] == 4
    assert res["sound"] is True
    assert res["broken"] is False, res["checks"]
