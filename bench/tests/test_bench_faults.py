"""A run with its timed path broken underneath reads ``correct`` false,
for each fault a one-chip cell can have; without a chip, nothing runs."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.configs import resnet_tiny
from bench.faults import FAULTS
from bench.tests import tiny

CELL = "cifar10-secagg-l8"


@pytest.mark.parametrize("name", sorted(FAULTS))
@pytest.mark.parametrize("cell, seed", [("cifar10-secagg-l8", 2 ** 32 + 3),
                                        ("mnist-secagg-l2", 2 ** 32 + 7)])
def test_fault_is_caught(cell, seed, name):
    result, run = harness.run_cell(tiny.ROOT, cell, seed, 0.1, False, require_tpu=False,
                                   overrides=tiny.overrides(resnet_tiny, tiny.CELLS[cell]),
                                   fault=FAULTS[name])
    assert result["correct"] is False, run["readings"]


def test_no_chip_no_result(capsys, monkeypatch):
    entry = harness.load_module(tiny.ROOT / "bench" / "run.py")
    # JAX is imported already, so the entry's cache variables change nothing
    # here; they are set back after the test
    for name in ("JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE"):
        monkeypatch.setenv(name, "")
    rc = entry.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_cache_is_the_checkouts_whatever_the_machine_sets(monkeypatch):
    entry = harness.load_module(tiny.ROOT / "bench" / "run.py")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/shared/by/the/machine")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_MAX_SIZE", str(192 * 2 ** 20))
    entry.use_checkout_cache()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tiny.ROOT / ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] == "-1"


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
                           "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
