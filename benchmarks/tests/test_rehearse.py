"""The CPU rehearsal of every cell at a tiny size (four virtual devices
for the trainer cell), and the contract's refusals.  Each case is a
process of its own, as the driver starts it."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def run(*args, cwd=ROOT, env=None, timeout=1500):
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_walks_the_cell_and_prints_no_metric(cell):
    p = run("--workload", cell, "--seed", "7", "--seconds", "2", "--trace", "1",
            "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "REHEARSAL on the CPU" in p.stderr
    assert '"correct": true' in p.stderr
    assert '"metrics"' not in p.stdout  # a CPU number never gets a metric's name


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = run("--workload", BENCH["workloads"][0]["name"], "--seconds", "1", env=env)
    assert p.returncode == 2
    assert p.stdout.strip() == ""


def test_a_bare_benchmark_directory_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = run("--workload", BENCH["workloads"][0]["name"], "--seconds", "1",
            cwd=str(tmp_path), env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
