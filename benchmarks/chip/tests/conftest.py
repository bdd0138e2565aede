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
    {"name": "frontier_small.test_frontier", "config": "frontier_small", "traffic": "test_frontier",
     "chips": 4, "why": "test size"},
]
# configurations of the test-size cells only (``data/configs``)
TEST_CONFIGS = [
    {"name": "frontier_small", "source": "test size", "file": "benchmarks/chip/configs/frontier_small.json",
     "reduced": [], "why": "test size"},
]


@pytest.fixture(scope="module")
def x64():
    """float64 for the reference, as ``run.py`` switches it on once the
    program is freed; put back after the module, so that the program the
    later files drive in this process runs as it is served (float32)."""
    import jax

    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", before)


@pytest.fixture(scope="session")
def checkout(tmp_path_factory) -> Path:
    """A checkout whose BENCHMARK.json adds the test-size cells."""
    root = tmp_path_factory.mktemp("checkout")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] += TEST_CELLS
    bench["configs"] += TEST_CONFIGS
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(CHIP / "configs", root / "benchmarks" / "chip" / "configs")
    shutil.copytree(CHIP / "traffic", root / "benchmarks" / "chip" / "traffic")
    shutil.copytree(CHIP / "graphs", root / "benchmarks" / "chip" / "graphs")
    for f in (HERE / "data").glob("test_*.json"):
        shutil.copy(f, root / "benchmarks" / "chip" / "traffic" / f.name)
    for f in (HERE / "data" / "configs").glob("*.json"):
        shutil.copy(f, root / "benchmarks" / "chip" / "configs" / f.name)
    return root


def drive(checkout: Path, workload: str, fault: str = "none", seconds: float = 2.0,
          seed: int = 2**31 + 11, devices: int = 1, with_log: bool = False):
    """Run one cell on the CPU, on ``devices`` virtual devices, with
    ``fault`` planted; the parsed last line (and, ``with_log``, what the run
    wrote to standard error)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable, str(HERE / "drive.py"), fault, str(checkout / "BENCHMARK.json"),
           "--workload", workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"]
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    return (out, p.stderr) if with_log else out
