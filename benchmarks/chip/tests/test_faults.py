"""A run with the timed path broken underneath comes out not correct, once
per fault the cells can have: an answer altered where it is produced, half
of a coalesced batch answered with another lane's result, an optimizer
step that leaves its state unchanged, and an optimize reply that states its
start design as the optimized one.  (No cell spans chips, so there is no
exchange between chips to leave out.)"""
import pytest

from conftest import drive


@pytest.mark.parametrize("workload", ["mlperf_small.test_open", "mlperf_small.notebook_sweep",
                                      "mlperf_small.test_optimize"])
def test_sound_run_is_correct(checkout, workload):
    out = drive(checkout, workload)
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("workload,fault", [
    ("mlperf_small.test_open", "answer"),
    ("mlperf_small.notebook_sweep", "answer"),
    ("mlperf_small.test_optimize", "answer"),
    ("mlperf_small.test_open", "half_batch"),
    ("mlperf_small.test_optimize", "state_unchanged"),
    ("mlperf_small.test_optimize", "design_stale"),
])
def test_fault_is_caught(checkout, workload, fault):
    out = drive(checkout, workload, fault)
    assert out["correct"] is False, out["checks"]
