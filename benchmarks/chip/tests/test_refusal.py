"""Without a TPU the benchmark exits non-zero and prints no result line."""
import os
import subprocess
import sys

from conftest import CHIP, ROOT


def test_cpu_run_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload", "mlperf_small.notebook_sweep",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
