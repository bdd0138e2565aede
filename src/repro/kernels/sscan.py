"""Pallas TPU selective-scan kernel (Mamba1) — falcon-mamba's hot spot.

The jnp chunked scan (models/mamba.selective_scan) materializes the
[B, chunk, C, N] decay/update tensors in HBM every chunk — ~60 s of HBM
time per train step for falcon-mamba-7b (§Roofline). This kernel keeps the
SSM state [block_c, N] resident in VMEM scratch and streams u/dt/B/C
chunk-by-chunk, so HBM traffic drops to the O(S·C) inputs/outputs — the
mamba-style "hardware-aware" scan, TPU edition.

Grid: (batch, channel_blocks, seq_chunks); the seq axis is sequential
("arbitrary") so the state scratch carries across chunks. Inside a chunk a
fori_loop steps time; every op is [block_c, N]-shaped (VPU lanes on N,
sublanes on channels).

Validated against the exact per-step recurrence in tests/test_kernels.py.

This module also hosts :func:`affine_scan` — the first-order affine prefix
``s_i = decay * s_{i-1} + b_i`` the DSim mapper's bandwidth-EMA carry
dispatches through when ``MapperCfg.scan_impl == "pallas"``.  The forward
runs as a Pallas kernel (each chunk one product with a lower-triangular
matrix of decay powers, state resident in VMEM scratch across a sequential
grid over chunks, through the ``runtime.dragon_pallas_call`` seam); the backward
is the closed-form reversed scan (``custom_vjp``), so the mapper stays
fully differentiable.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import runtime


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
                 *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0].astype(jnp.float32)    # [chunk, bc]
    dt = dt_ref[0].astype(jnp.float32)  # [chunk, bc]
    A = a_ref[...].astype(jnp.float32)  # [bc, N]
    Bm = b_ref[0].astype(jnp.float32)   # [chunk, N]
    Cm = c_ref[0].astype(jnp.float32)   # [chunk, N]
    D = d_ref[...].astype(jnp.float32)  # [1, bc]

    def step(t, carry):
        state, ys = carry  # [bc, N], [chunk, bc]
        dt_t = jax.lax.dynamic_slice_in_dim(dt, t, 1, 0)  # [1, bc]
        u_t = jax.lax.dynamic_slice_in_dim(u, t, 1, 0)
        b_t = jax.lax.dynamic_slice_in_dim(Bm, t, 1, 0)  # [1, N]
        c_t = jax.lax.dynamic_slice_in_dim(Cm, t, 1, 0)
        decay = jnp.exp(dt_t.T * A)  # [bc, N]
        state = decay * state + (dt_t * u_t).T * b_t  # [bc, N]
        y_t = jnp.sum(state * c_t, axis=1) + (u_t * D)[0]  # [bc]
        ys = jax.lax.dynamic_update_slice_in_dim(ys, y_t[None], t, 0)
        return state, ys

    state, ys = jax.lax.fori_loop(
        0, chunk, step, (state_ref[...], jnp.zeros_like(u))
    )
    state_ref[...] = state
    y_ref[0] = ys.astype(y_ref.dtype)


def selective_scan_pallas(
    u: jax.Array,   # [B, S, C]
    dt: jax.Array,  # [B, S, C] (post softplus)
    A: jax.Array,   # [C, N] (negative)
    Bm: jax.Array,  # [B, S, N]
    Cm: jax.Array,  # [B, S, N]
    D: jax.Array,   # [C]
    *,
    chunk: int = 64,
    block_c: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    B, S, C = u.shape
    N = A.shape[1]
    chunk = runtime.clamp_block(chunk, S, name="chunk")
    block_c = runtime.clamp_block(block_c, C, name="block_c")
    n_chunks = S // chunk

    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=n_chunks)
    return runtime.dragon_pallas_call(
        kernel,
        grid=(B, C // block_c, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_c), lambda b, c, s: (b, s, c)),  # u
            pl.BlockSpec((1, chunk, block_c), lambda b, c, s: (b, s, c)),  # dt
            pl.BlockSpec((block_c, N), lambda b, c, s: (c, 0)),            # A
            pl.BlockSpec((1, chunk, N), lambda b, c, s: (b, s, 0)),        # B
            pl.BlockSpec((1, chunk, N), lambda b, c, s: (b, s, 0)),        # C
            pl.BlockSpec((1, block_c), lambda b, c, s: (0, c)),            # D
        ],
        out_specs=pl.BlockSpec((1, chunk, block_c), lambda b, c, s: (b, s, c)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), u.dtype),
        scratch_shapes=[runtime.vmem_scratch((block_c, N), jnp.float32)],
        interpret=interpret,
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )(u, dt, A, Bm, Cm, D.reshape(1, C))


# --------------------------------------------------------------------------- #
# first-order affine prefix scan (the mapper's bw-EMA carry)
# --------------------------------------------------------------------------- #


def _affine_scan_kernel(b_ref, s_ref, state_ref, *, chunk: int, decay: float):
    """One chunk of the prefix as a matrix product (no per-lane slicing, which
    Mosaic cannot lower): ``s_i = sum_{j<=i} decay^(i-j) b_j + decay^(i+1) s``
    where ``s`` is the state carried in from the previous chunk."""
    ci = pl.program_id(0)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    b = b_ref[...].astype(jnp.float32)  # [1, chunk]
    src = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)  # j
    dst = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)  # i
    lag = (dst - src).astype(jnp.float32)
    log_decay = math.log(decay)
    powers = jnp.where(lag >= 0, jnp.exp(jnp.maximum(lag, 0.0) * log_decay), 0.0)
    within = jnp.dot(b, powers, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)  # [1, chunk]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1).astype(jnp.float32)
    out = within + state_ref[...] * jnp.exp((pos + 1.0) * log_decay)
    state_ref[...] = out[:, chunk - 1:]
    s_ref[...] = out.astype(s_ref.dtype)


def _affine_scan_pallas(decay: float, add: jax.Array, *, chunk: int = 512,
                        interpret: bool | None = None) -> jax.Array:
    """Inclusive prefix of ``s' = decay*s + b`` (s0 = 0) as a Pallas kernel.

    The running state lives in a [1, 1] VMEM scratch that carries across the
    sequential chunk grid; trailing padding (b = 0) only touches dropped
    outputs, never the prefix of real elements."""
    (v,) = add.shape
    chunk = min(chunk, max(v, 1))
    vp = -(-v // chunk) * chunk
    b = jnp.pad(add, (0, vp - v)).reshape(1, vp)
    kernel = functools.partial(_affine_scan_kernel, chunk=chunk, decay=float(decay))
    out = runtime.dragon_pallas_call(
        kernel,
        grid=(vp // chunk,),
        in_specs=[pl.BlockSpec((1, chunk), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, chunk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, vp), add.dtype),
        scratch_shapes=[runtime.vmem_scratch((1, 1), jnp.float32)],
        interpret=interpret,
        dimension_semantics=("arbitrary",),
    )(b)
    return out[0, :v]


def _affine_prefix(decay: float, add: jax.Array) -> jax.Array:
    """The backward workhorse: core.mapper's associative inclusive prefix.

    Imported lazily (mapper itself lazily imports :func:`affine_scan` for
    its pallas dispatch, so neither module needs the other at import time);
    one definition of the recurrence keeps the VJP in lockstep with the
    forward semantics."""
    from repro.core.mapper import affine_prefix_assoc

    return affine_prefix_assoc(decay, add)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def affine_scan(decay: float, add: jax.Array) -> jax.Array:
    """Differentiable Pallas-backed inclusive prefix of ``s' = decay*s + b``.

    ``s_i = sum_{j<=i} decay^(i-j) b_j``; the VJP is the reversed scan
    ``db_k = sum_{i>=k} decay^(i-k) g_i`` — another affine prefix, so no
    residuals beyond the cotangent are needed.
    """
    return _affine_scan_pallas(decay, add)


def _affine_scan_fwd(decay, add):
    return _affine_scan_pallas(decay, add), None


def _affine_scan_bwd(decay, _res, g):
    return (jnp.flip(_affine_prefix(decay, jnp.flip(g))),)


affine_scan.defvjp(_affine_scan_fwd, _affine_scan_bwd)
