"""The command measures on a TPU or not at all: each refusal, on the CPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "cnn100.dense"


def command(cwd, env=None):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_no_tpu_exits_2_with_no_result():
    res = command(ROOT)
    assert res.returncode == 2, res.stderr
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and chipbench/ (no program)."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = command(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_require_chips_refuses_cpu():
    import jax

    with pytest.raises(run.Refusal, match="no TPU"):
        run.require_chips(jax, 1)


def test_unknown_device_kind_is_refused():
    with pytest.raises(run.Refusal, match="not in peaks.json"):
        run.device_peak("TPU v9 imaginary")
    assert run.device_peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_kernels_that_are_not_pallas_are_refused(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    with pytest.raises(run.Refusal, match="not 'pallas'"):
        run.require_pallas()
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    run.require_pallas()


def test_unknown_workload_is_refused():
    with pytest.raises(run.Refusal, match="no workload"):
        run.load_cell("no.such.cell")


def test_traffic_the_reference_does_not_cover_is_refused():
    cell = run.load_cell(CELL)
    cell["traffic"] = dict(cell["traffic"], codec={"kind": "topk"})
    with pytest.raises(run.Refusal, match="does not cover"):
        run.dpfl_config(cell, 0)


def test_every_metric_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert callable(run.load_reader(m["name"]))
