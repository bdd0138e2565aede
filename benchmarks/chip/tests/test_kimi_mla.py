"""The ``kimi_k2_mla`` graphs against the plain reference
``model_refs/kimi_k2.py`` at the published widths, on abstract shapes
(nothing allocated): each layer's matmul FLOPs equal the reference's
``dot_general`` FLOPs, less the graphs' causal halving of prefill's S x S
scores and AV; each decode layer reads from mainMem exactly the latent cache
the reference attends over (bf16, 2 bytes an element).  And the
``vertex_pad_share.dopt`` reader: 1 - 9/32 on a CPU trace of optimize calls
on dlrm (9 vertices, bucket 32); None with no trace and on a trace of a
program whose spans carry no ``vertices``."""
import gzip
import json
import math
import shutil
import sys

import jax
import jax.numpy as jnp
from jax.extend import core as jex
import numpy as np
import pytest

import check as C
import program_spans as S
import run
from conftest import CHIP, HERE

sys.path.insert(0, str(CHIP / "model_refs"))
import kimi_k2 as R  # noqa: E402

CONFIG = json.loads((CHIP / "configs" / "kimi_k2_mla.json").read_text())
PUB = R.Config.of(CONFIG)
ENTRIES = {e["name"]: e for e in CONFIG["graphs"]}
MATMUL, MAIN = 0, 2  # op kind of a matmul vertex; mainMem's column


@pytest.fixture(scope="module")
def graphs():
    return C.load_graphs(dict(CONFIG, dir=str(CHIP)))


def _dot_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lhs_c, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2.0 * math.prod(eqn.outvars[0].aval.shape) * math.prod(lhs[c] for c in lhs_c)
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(x, jex.ClosedJaxpr):
                    total += _dot_flops(x.jaxpr)
                elif isinstance(x, jex.Jaxpr):
                    total += _dot_flops(x)
    return total


def _layer(priced: dict):
    """The reference layer at the graph's shape, and its abstract inputs
    after the weights."""
    f32, B, L = jnp.float32, priced["batch"], priced["seq_len"]
    if priced["phase"] == "decode":
        cache = (jax.ShapeDtypeStruct((B, L - 1, PUB.kv_lora_rank), f32),
                 jax.ShapeDtypeStruct((B, L - 1, PUB.qk_rope_head_dim), f32))
        x = jax.ShapeDtypeStruct((B, 1, PUB.hidden_size), f32)
        return (lambda lp, x, c: R.layer_decode(lp, x, L - 1, c, PUB)), (x, cache)
    pos = np.arange(L)
    x = jax.ShapeDtypeStruct((B, L, PUB.hidden_size), f32)
    return (lambda lp, x: R.layer_forward(lp, x, pos, PUB)), (x,)


def _rows(g: dict, layer: int) -> list[int]:
    return [i for i, n in enumerate(g["names"]) if str(n).startswith(f"L{layer}.")]


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_layer_matmul_flops_equal_reference_dots(graphs, name):
    g, priced = graphs[name], ENTRIES[name]["priced_as"]
    fn, args = _layer(priced)
    B, L = priced["batch"], priced["seq_len"]
    halved = 0.0
    if priced["phase"] == "prefill":
        heads = PUB.qk_nope_head_dim + PUB.qk_rope_head_dim + PUB.v_head_dim
        halved = 0.5 * 2.0 * B * PUB.num_attention_heads * L * L * heads
    n_comp = np.asarray(g["n_comp"], np.float64)
    for layer in (0, 1):  # the dense layer, then the first of 60 alike
        ref = _dot_flops(jax.make_jaxpr(fn)(R.layer_shapes(PUB, layer), *args).jaxpr) - halved
        rows = [i for i in _rows(g, layer) if g["op_kind"][i] == MATMUL]
        assert n_comp[rows].sum() == pytest.approx(ref, rel=1e-6), (layer, ref)


def test_decode_reads_the_latent_cache(graphs):
    name = "kimi-k2-instruct:decode_32k"
    g, priced = graphs[name], ENTRIES[name]["priced_as"]
    fn, args = _layer(priced)
    _, (c_kv, k_pe) = jax.eval_shape(fn, R.layer_shapes(PUB, 1), *args)
    cache_bytes = 2.0 * (math.prod(c_kv.shape) + math.prod(k_pe.shape))
    main = np.asarray(g["n_read"], np.float64)[:, MAIN]
    for layer in range(CONFIG["num_hidden_layers"]):
        rows = [i for i in _rows(g, layer) if ".mla." in str(g["names"][i])]
        assert main[rows].sum() == pytest.approx(cache_bytes, rel=1e-6), layer


# --------------------------------------------------------------------------- #
# vertex_pad_share.dopt
# --------------------------------------------------------------------------- #


def _read(ctx):
    return run.read_metric({"name": "vertex_pad_share.dopt"}, ctx)


@pytest.fixture(scope="module")
def optimize_trace(tmp_path_factory):
    from repro.api import Session

    sess = Session()
    sess.optimize("dlrm", steps=4, chunk=2)  # compile outside the trace
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        sess.optimize("dlrm", steps=4, chunk=2)
        sess.optimize("dlrm", steps=4, chunk=2)
    saved, S.TRACE_DIR = S.TRACE_DIR, trace_dir
    yield
    S.TRACE_DIR = saved


def test_pad_share_reads_the_spans(optimize_trace):
    assert _read(dict(trace={"window_s": 1.0})) == pytest.approx(100.0 * (1 - 9 / 32), rel=1e-12)


def test_pad_share_none_without_a_trace(optimize_trace):
    assert _read(dict(trace=None)) is None


def test_pad_share_none_on_a_trace_without_vertices(tmp_path):
    with gzip.open(HERE / "data" / "trace.xplane.pb.gz", "rb") as src, \
            open(tmp_path / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    saved, S.TRACE_DIR = S.TRACE_DIR, tmp_path
    try:
        assert _read(dict(trace={"window_s": 1.0})) is None
    finally:
        S.TRACE_DIR = saved
