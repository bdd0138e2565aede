"""Workload tracer validation: DFG totals vs closed-form model FLOPs,
plus hypothesis properties over the chunked-xent / attention helpers."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # degrades to skip without hypothesis

from pathlib import Path

from repro.configs import SHAPES, get_config, traced_archs
from repro.configs.base import ShapeConfig
from repro.core.trace import model_flops, trace_lm
from repro.workloads import lm_cell


@pytest.mark.parametrize("arch", traced_archs())
def test_train_dfg_flops_vs_6nd(arch):
    """Traced DFG FLOPs should be ~6*N_active*D for train (plus attention,
    which 6ND ignores — so ratio in [0.95, 3.0])."""
    cfg = get_config(arch)
    shape = SHAPES["train_4k"]
    g = trace_lm(cfg, shape)
    traced = float(np.asarray(g.total_flops).sum())
    closed = model_flops(cfg, shape)
    ratio = traced / closed
    assert 0.9 < ratio < 3.0, (arch, ratio)


@pytest.mark.parametrize("arch", traced_archs())
def test_decode_dfg_much_smaller_than_prefill(arch):
    cfg = get_config(arch)
    if not cfg.subquadratic() and arch == "skip":
        pytest.skip()
    dec = float(np.asarray(trace_lm(cfg, SHAPES["decode_32k"]).total_flops).sum())
    pre = float(np.asarray(trace_lm(cfg, SHAPES["prefill_32k"]).total_flops).sum())
    assert dec < pre / 10


def test_moe_dfg_counts_active_experts_only():
    k2 = get_config("kimi-k2-1t-a32b")
    g = trace_lm(k2, SHAPES["train_4k"])
    traced = float(np.asarray(g.total_flops).sum())
    all_experts = 6.0 * k2.param_count() * SHAPES["train_4k"].seq_len * SHAPES["train_4k"].global_batch
    assert traced < all_experts / 5  # active << total


def test_vertex_stats_nonnegative():
    for arch in traced_archs():
        g = lm_cell(arch, "train_4k")
        for f in (g.n_comp, g.n_read, g.n_write, g.n_alloc):
            assert float(jnp.min(f)) >= 0.0


GRAPHS = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "graphs"


@pytest.mark.parametrize("arch,shape,frozen", [
    ("qwen2.5-32b", "prefill_32k", "qwen2.5-32b.prefill_32k.npz"),
    ("kimi-k2-1t-a32b", "decode_32k", "kimi-k2-as-gqa.decode_32k.npz"),
    ("kimi-k2-instruct", "prefill_32k", "kimi-k2-instruct.prefill_32k.npz"),
    ("kimi-k2-instruct", "decode_32k", "kimi-k2-instruct.decode_32k.npz"),
])
def test_lm_cell_gives_the_benchmarks_frozen_graph(arch, shape, frozen):
    """The chip benchmark prices these arrays (``freeze_graphs.py``): the
    shared emitters may not drift from them by a bit."""
    g = lm_cell(arch, shape)
    with np.load(GRAPHS / frozen) as kept:
        assert tuple(str(n) for n in kept["names"]) == g.names
        for f in ("n_comp", "n_read", "n_write", "n_alloc", "dims", "op_kind", "edges"):
            a = np.asarray(getattr(g, f))
            assert kept[f].dtype == a.dtype and np.array_equal(kept[f], a), f


class TestChunkedXentProperty:
    @settings(max_examples=10, deadline=None)
    @given(
        S=st.sampled_from([8, 12, 16]),
        chunk=st.sampled_from([3, 4, 8, 16]),
        seed=st.integers(0, 10),
    )
    def test_equals_full_xent(self, S, chunk, seed):
        from repro.models import build_model
        from repro.models import transformer as T

        cfg = dataclasses.replace(get_config("granite-3-8b").reduced(), dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(seed))
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, S), 0, cfg.vocab_size)
        h, _, _ = m.forward(params, tokens, head=False)
        logits, _, _ = m.forward(params, tokens, head=True)
        full = float(T.xent_loss(logits, tokens))
        chunked = float(T.chunked_xent(cfg, params, h, tokens, chunk=chunk))
        assert chunked == pytest.approx(full, rel=1e-5)


class TestTrainStepEquivalence:
    def test_microbatch_accumulation_matches_full(self, rng):
        from repro.models import build_model
        from repro.optim import AdamWConfig
        from repro.train import TrainConfig, init_train_state, make_train_step

        cfg = dataclasses.replace(get_config("granite-3-8b").reduced(), dtype="float32")
        m = build_model(cfg)
        batch = {
            "tokens": jax.random.randint(rng, (4, 16), 0, cfg.vocab_size),
            "labels": jax.random.randint(rng, (4, 16), 0, cfg.vocab_size),
        }
        ocfg = AdamWConfig(lr=1e-3, schedule=None)
        outs = []
        for mb in (1, 2):
            state = init_train_state(m, jax.random.PRNGKey(3), ocfg)
            step = jax.jit(make_train_step(m, ocfg, TrainConfig(microbatches=mb)))
            state, _ = step(state, batch)
            outs.append(state["params"])
        for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


class TestGNNWorkloads:
    """Paper Table 1 claims GNN support — validate the message-passing DFGs."""

    def test_gcn_simulates(self):
        from repro.core import ArchParams, TechParams, simulate
        from repro.workloads import get_workload

        g = get_workload("gcn")
        p = simulate(TechParams.default(), ArchParams.default(), g)
        assert float(p.runtime) > 0 and np.isfinite(float(p.energy))

    def test_gather_dominates_mainmem_traffic(self):
        """GNNs are gather/aggregation-bound — mainMem reads exceed weight
        traffic by a wide margin (the property that distinguishes them from
        CNNs in the paper's Table 3 analysis)."""
        from repro.workloads import get_workload

        g = get_workload("gcn")
        main_reads = float(np.asarray(g.n_read)[:, 2].sum())
        flops = float(np.asarray(g.n_comp).sum())
        # arithmetic intensity well below a dense CNN's
        assert flops / main_reads < 100.0

    def test_degree_scales_gather(self):
        from repro.workloads import get_workload

        lo = get_workload("graphsage", avg_degree=4)
        hi = get_workload("graphsage", avg_degree=32)
        # mainMem gather traffic scales with degree (weight traffic doesn't)
        assert (float(np.asarray(hi.n_read)[:, 2].sum())
                > 4 * float(np.asarray(lo.n_read)[:, 2].sum()))
