"""The DSim Pallas kernels and one served program compile for a TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with jaxlib
compiles for a *described* v5e (``topologies.get_topology_desc``), which
refuses what Mosaic cannot lower (scatter, per-lane dynamic slices, ...)
exactly as the chip would, while interpret-mode tests cannot see it.  Each
test asserts the kernel really is in the executable (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and under pytest-xdist every worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import popsim_kernel, runtime, sscan


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer ``interpret=None`` to Mosaic: the process backend is the CPU,
    but the program is compiled for the described chip."""
    monkeypatch.setattr(runtime, "auto_interpret", lambda: False)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x), sharding=sharding),
        tree,
    )


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_popsim_kernel_compiles(one_chip):
    graph = jax.ShapeDtypeStruct((1024, popsim_kernel.GRAPH_COLS), jnp.float32, sharding=one_chip)
    chw = jax.ShapeDtypeStruct((4096, popsim_kernel.CHW_COLS), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda g, c: popsim_kernel.popsim(g, c, block_pop=128, interpret=False), graph, chw
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("v", [32, 1024])
def test_affine_scan_forward_compiles(one_chip, on_tpu, v):
    add = jax.ShapeDtypeStruct((v,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(lambda b: sscan.affine_scan(0.8, b), add)


def test_affine_scan_grad_compiles(one_chip, on_tpu):
    add = jax.ShapeDtypeStruct((1024,), jnp.float32, sharding=one_chip)
    grad = jax.grad(lambda b: jnp.sum(sscan.affine_scan(0.8, b) ** 2))
    assert "tpu_custom_call" in _compiled_text(grad, add)


def test_served_batched_simulate_compiles(one_chip, on_tpu):
    """The program a 1024-bucket ``simulate`` query is served by, at request
    bucket 16, with the mapper's bw-EMA on the Pallas kernel."""
    from repro.api import Session, Workload
    from repro.core.mapper import MapperCfg
    from repro.workloads import lm_cell

    sess = Session("datacenter", mcfg=MapperCfg(scan_impl="pallas"))
    w = Workload(lm_cell("qwen2.5-32b", "prefill_32k"))
    assert w.bucket == (1, 1024)
    ws, archs, nb, stacked = sess._assemble_batch([w], None, request_bucket=16)
    _, build = sess._batched_report_spec(nb, w.bucket, archs[0].spec, sess.mcfg)
    text = build().lower(*_shapes(stacked, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text
