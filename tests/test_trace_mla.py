"""Kimi-K2's latent attention, shared expert and leading dense layer in the
tracer, against the plain reference ``workloads/kimi_reference.py``.

The reference's absorbed decode through the latent cache reproduces its
decompressed full forward (and in bfloat16 it does not); the traced graph's
matmul FLOPs per layer equal the reference's ``dot_general`` FLOPs, and its
decode vertices read exactly the latent cache from mainMem, at a small size
on seeded weights and at the published widths on abstract shapes (nothing
allocated); the published graphs sit in the 2048 bucket and run through
``Session``.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.extend import core as jex
import numpy as np
import pytest

from repro.api import Session, Workload
from repro.configs import SHAPES, MLAConfig, ModelConfig, MoEConfig, ShapeConfig, get_config
from repro.core.graph import MATMUL
from repro.core.params import MEM_IDX
from repro.core.trace import trace_lm
from repro.workloads import kimi_reference as K
from repro.workloads import lm_cell

SMALL = ModelConfig(
    name="kimi-k2-small", family="moe", n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96,
    vocab_size=128, rope_theta=50_000.0, norm_eps=1e-6,
    moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, n_shared=1, first_k_dense=1, routed_scaling=2.827),
    mla=MLAConfig(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8),
)
PUBLISHED = get_config("kimi-k2-instruct")
SIZES = {"small": (SMALL, {"prefill": ShapeConfig("p", 12, 2, "prefill"),
                           "decode": ShapeConfig("d", 12, 2, "decode")}),
         "published": (PUBLISHED, {"prefill": SHAPES["prefill_32k"], "decode": SHAPES["decode_32k"]})}

# Absorbed decode against the decompressed forward, float32 at highest
# precision: the two forms differ only in the order of the score and output
# products ((q W_UK) c against q (c W_UK)), so logits of magnitude ~4 differ
# by a few float32 roundings over these sums (worst seen 3.0e-6 over 4 seeds).
# bfloat16 (8 mantissa bits) moves them by 5e-2 and more (1.5 where two
# experts' scores nearly tie and the rounding flips the routing).
LOGIT_ATOL = 1e-4


def _decode_gap(dtype, seed: int) -> float:
    """Widest |decoded logit - full-forward logit| over positions 5..11."""
    params = K.init(SMALL, jax.random.PRNGKey(seed), dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(100 + seed), (2, 12), 0, SMALL.vocab_size)
    forward = jax.jit(K.forward, static_argnums=2)
    decode_step = jax.jit(K.decode_step, static_argnums=4)
    full, _ = forward(params, tokens, SMALL)
    _, cache = forward(params, tokens[:, :5], SMALL)
    gap = 0.0
    for t in range(5, 12):
        logits, cache = decode_step(params, tokens[:, t], t, cache, SMALL)
        diff = np.asarray(logits, np.float64) - np.asarray(full[:, t], np.float64)
        gap = max(gap, float(np.max(np.abs(diff))))
    return gap


@pytest.mark.parametrize("seed", [0, 1])
def test_absorbed_decode_matches_decompressed_forward(seed):
    assert _decode_gap(jnp.float32, seed) < LOGIT_ATOL


def test_bfloat16_control_fails_the_decode_tolerance():
    assert _decode_gap(jnp.bfloat16, 0) > LOGIT_ATOL


# --------------------------------------------------------------------------- #
# FLOPs and bytes: the traced graph against the reference's own program
# --------------------------------------------------------------------------- #


def _sub_jaxprs(params):
    for v in params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            if isinstance(x, jex.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex.Jaxpr):
                yield x


def _dot_flops(jaxpr) -> float:
    """2 x output elements x contracted length, over every ``dot_general``."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lhs_c, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2.0 * math.prod(eqn.outvars[0].aval.shape) * math.prod(lhs[c] for c in lhs_c)
        for sub in _sub_jaxprs(eqn.params):
            total += _dot_flops(sub)
    return total


def _abstract_layers(cfg: ModelConfig) -> list:
    """Shapes of layer 0 (dense) and layer 1 (experts)."""
    two = dataclasses.replace(cfg, n_layers=2)
    return jax.eval_shape(lambda k: K.init(two, k), jax.random.PRNGKey(0))["layers"]


def _reference_layer(cfg: ModelConfig, shape: ShapeConfig):
    """One reference layer at the tracer's shape, as a function of the
    layer's weights, and its other inputs as abstract shapes: the whole
    sequence decompressed (prefill), or one token per row absorbed over a
    cache that then holds ``seq_len``."""
    m, f32 = cfg.mla, jnp.float32
    B, L = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        cache = (jax.ShapeDtypeStruct((B, L - 1, m.kv_lora_rank), f32),
                 jax.ShapeDtypeStruct((B, L - 1, m.qk_rope_head_dim), f32))
        x = jax.ShapeDtypeStruct((B, 1, cfg.d_model), f32)
        return (lambda lp, x, c: K.layer_decode(lp, x, L - 1, c, cfg)), (x, cache)
    pos = np.arange(L)
    x = jax.ShapeDtypeStruct((B, L, cfg.d_model), f32)
    return (lambda lp, x: K.layer_forward(lp, x, pos, cfg)), (x,)


def _traced(g, layer: int, kind=None) -> list[int]:
    return [i for i, n in enumerate(g.names) if n.startswith(f"L{layer}.")
            and (kind is None or int(g.op_kind[i]) == kind)]


@pytest.mark.parametrize("size", ["small", "published"])
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_traced_matmul_flops_equal_reference_dots(size, phase):
    """Per layer, dense (0) and routed (1): the graph stores its counts in
    float32 (rtol 1e-6).  The one difference is the tracer's causal halving:
    it prices half of prefill's S x S scores and AV, where the reference
    computes them all and masks."""
    cfg, shapes = SIZES[size]
    shape = shapes[phase]
    g = trace_lm(cfg, shape)
    n_comp = np.asarray(g.n_comp, np.float64)
    m, B, S = cfg.mla, shape.global_batch, shape.seq_len
    halved = 0.5 * 2.0 * B * cfg.n_heads * S * S * (m.qk_nope_head_dim + m.qk_rope_head_dim + m.v_head_dim)
    for layer, lp in enumerate(_abstract_layers(cfg)):
        fn, args = _reference_layer(cfg, shape)
        ref = _dot_flops(jax.make_jaxpr(fn)(lp, *args).jaxpr)
        if phase == "prefill":
            ref -= halved
        traced = n_comp[_traced(g, layer, MATMUL)].sum()
        assert traced == pytest.approx(ref, rel=1e-6), (layer, traced, ref)


@pytest.mark.parametrize("size", ["small", "published"])
def test_decode_reads_the_latent_cache_from_main_memory(size):
    """Each layer's attention reads from mainMem exactly the latent cache
    the reference attends over, at the graph's bf16 (2 bytes an element)."""
    cfg, shapes = SIZES[size]
    g = trace_lm(cfg, shapes["decode"])
    main = np.asarray(g.n_read, np.float64)[:, MEM_IDX["mainMem"]]
    lp = _abstract_layers(cfg)[1]
    fn, args = _reference_layer(cfg, shapes["decode"])
    _, (c_kv, k_pe) = jax.eval_shape(fn, lp, *args)
    cache_bytes = 2.0 * (math.prod(c_kv.shape) + math.prod(k_pe.shape))
    for layer in range(cfg.n_layers):
        core = [i for i in _traced(g, layer) if ".mla." in g.names[i]]
        assert main[core].sum() == pytest.approx(cache_bytes, rel=1e-6), layer


# --------------------------------------------------------------------------- #
# the published graphs
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("shape,forms", [
    ("prefill_32k", {"kv_b": 61, "q_absorb": 0, "v_up": 0}),
    ("decode_32k", {"kv_b": 0, "q_absorb": 61, "v_up": 61}),
])
def test_published_graph_in_the_2048_bucket(shape, forms):
    g = lm_cell("kimi-k2-instruct", shape)
    assert 1024 < g.n_vertices <= 2048
    assert Workload(g).bucket == (1, 2048)
    ops = [n.split(".", 1)[1] for n in g.names if n.startswith("L")]
    for op, count in dict(forms, **{"mla.scores": 61, "shared_up": 60, "router": 60,
                                    "mlp_up": 1}).items():
        assert ops.count(op) == count, op
    assert "L0.mlp_up" in g.names and "L0.router" not in g.names


def test_published_decode_runs_through_session():
    w = Workload(lm_cell("kimi-k2-instruct", "decode_32k"), labels=("kimi-k2-instruct:decode_32k",))
    sess = Session("datacenter")
    (rep,) = sess.simulate(w).workloads
    assert np.isfinite(rep.runtime_s) and rep.runtime_s > 0
    assert sess.explain(w).attribution
    opt = sess.optimize(w, steps=4, chunk=2, objective="edp")
    assert opt.epochs == 4 and np.all(np.isfinite(opt.objective_history))
