"""`correct` on the CPU at a small size: a sound run of the program passes
each cell's limits, and the control and every fault the cell can have
fail them. On the CPU the program and the reference compute alike in
float32 (in another order of sums), so a sound run reads 1e-3 or
less on every number."""
import json
import os

import jax
import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
# 8 clients of 64 images, budget 3; the cell's epochs, optimizer and
# limits, so the control's and the faults' drift builds up as in the cell
SMALL = {"n_clients": 8, "n_train": 64, "n_val": 16, "n_test": 8}


def small(name: str) -> dict:
    cell = run.load_cell(name)
    cell["config"]["deployment"].update(SMALL)
    cell["config"]["training"].update(budget=3)
    return cell


def result(name: str, plant=None, seed: int = 2 ** 31 + 11) -> dict:
    peak = run.device_peak("TPU v5 lite")
    return run.run_cell(small(name), seed, 0.5, False, peak,
                        jax.devices()[:1], plant)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = result(name)
    assert out["correct"], out["checks"]
    assert all(v <= 1e-3 for v, _ in out["checks"].values()), out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("plant", ["control", "half_batch", "unchanged",
                                   "altered"])
@pytest.mark.parametrize("name", CELLS)
def test_control_and_faults_are_not_correct(name, plant):
    out = result(name, plant)
    assert not out["correct"], out["checks"]
