"""Shared helpers for the benchmark's own checks (run with
``pytest benchmarks/chip/tests``; they are not part of the repo's suite).

Each check that drives ``run.py`` does so in a child process on the CPU,
with the harness's look for a TPU replaced (``drive.py``), against a
``BENCHMARK.json`` of test-size cells written to a temporary checkout."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(ROOT / "src"))

TEST_CELLS = [
    {"name": "mlperf_small.test_open", "config": "mlperf_small", "traffic": "test_open", "chips": 1,
     "why": "test size"},
    {"name": "mlperf_small.test_optimize", "config": "mlperf_small", "traffic": "test_optimize",
     "chips": 1, "why": "test size"},
]


@pytest.fixture(scope="session")
def checkout(tmp_path_factory) -> Path:
    """A checkout whose BENCHMARK.json adds the test-size cells."""
    root = tmp_path_factory.mktemp("checkout")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] += TEST_CELLS
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(CHIP / "configs", root / "benchmarks" / "chip" / "configs")
    shutil.copytree(CHIP / "traffic", root / "benchmarks" / "chip" / "traffic")
    shutil.copytree(CHIP / "graphs", root / "benchmarks" / "chip" / "graphs")
    for f in (HERE / "data").glob("test_*.json"):
        shutil.copy(f, root / "benchmarks" / "chip" / "traffic" / f.name)
    return root


def drive(checkout: Path, workload: str, fault: str = "none", seconds: float = 2.0,
          seed: int = 2**31 + 11) -> dict:
    """Run one cell on the CPU with ``fault`` planted; the parsed last line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(HERE / "drive.py"), fault, str(checkout / "BENCHMARK.json"),
           "--workload", workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"]
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])
