"""A run with the timed path broken underneath comes out not correct, once
per fault the cells can have: an answer altered where it is produced, half
of a coalesced batch answered with another lane's result, an optimizer
step that leaves its state unchanged, and an optimize reply that states its
start design as the optimized one.  Frontier replies, run on four virtual
devices as the four-chip cell shards them, with: a member of the front left
out, one member's Adam step frozen, the budget penalty skipped, the
hypervolume's box shifted, and a shard's members lost (the population's
sharded chunk has no collectives: the exchange between chips is the
gathering of each chip's members)."""
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


def test_sound_frontier_run_is_correct(checkout):
    """Correct, and warm-up leaves the window nothing to compile: the front
    and the hypervolume are built at each front size the window meets."""
    out, log = drive(checkout, "frontier_small.test_frontier", devices=4, with_log=True)
    assert out["correct"] is True, out["checks"]
    assert "programs compiled inside the window: 0 " in log, log[-4000:]
    assert "loaded from the persistent cache inside the window: 0\n" in log, log[-4000:]


@pytest.mark.parametrize("fault", ["front_dropped", "member_frozen", "penalty_skipped",
                                   "hv_box_shifted", "shard_lost"])
def test_frontier_fault_is_caught(checkout, fault):
    out = drive(checkout, "frontier_small.test_frontier", fault, devices=4)
    assert out["correct"] is False, out["checks"]
