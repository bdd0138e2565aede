"""Plain reference of Kimi-K2's block, for the benchmark's own checks of the
``kimi_k2_mla`` graphs: the layer equations in straightforward
``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``.
It imports nothing of the program under test; its widths are the published
``config.json`` keys that ``configs/kimi_k2_mla.json`` copies.

A layer is multi-head latent attention (DeepSeek-V2 §2.1) and then an MLP:
dense for the first ``first_k_dense_replace`` layers, else routed experts
(sigmoid scores, top ``num_experts_per_tok``, weights normalized over them
and scaled by ``routed_scaling_factor``) plus ``n_shared_experts`` shared
experts.  Attention comes in the two forms the graphs price:
:func:`layer_forward` decompresses (``kv_b`` rebuilds every head's key and
value; causal multi-head attention), :func:`layer_decode` absorbs ``kv_b``
into the query and the output and attends over the latent cache as stored.

Departures from the published model, none of which moves a FLOP or a byte
that the graphs count: RoPE without YaRN's scaling or ``mscale``;
rotate-half RoPE (the checkpoint's interleaved layout is a fixed permutation
of the rotary weight columns); top-k over the sigmoid scores without the
``e_score_correction_bias`` offset.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Config:
    """The published keys the block reads, under their published names."""

    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    first_k_dense_replace: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float

    @classmethod
    def of(cls, published: dict) -> "Config":
        return cls(**{f.name: published[f.name] for f in dataclasses.fields(cls)})


def layer_shapes(c: Config, layer: int) -> dict:
    """Abstract float32 weights of layer ``layer``."""
    d, h = c.hidden_size, c.num_attention_heads
    n, p, v, r = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank

    def w(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def swiglu(width, *lead):
        return dict(gate=w(*lead, d, width), up=w(*lead, d, width), down=w(*lead, width, d))

    lp = dict(attn_norm=w(d), q_a=w(d, c.q_lora_rank), q_norm=w(c.q_lora_rank),
              q_b=w(c.q_lora_rank, h * (n + p)), kv_a=w(d, r + p), kv_norm=w(r),
              kv_b=w(r, h * (n + v)), o=w(h * v, d), mlp_norm=w(d))
    if layer < c.first_k_dense_replace:
        lp["dense"] = swiglu(c.intermediate_size)
    else:
        lp["router"] = w(d, c.n_routed_experts)
        lp["experts"] = swiglu(c.moe_intermediate_size, c.n_routed_experts)
        lp["shared"] = swiglu(c.n_shared_experts * c.moe_intermediate_size)
    return lp


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, pos, theta: float):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    if x.ndim == 4:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def project(lp: dict, x, pos, c: Config):
    n, p, r, h = c.qk_nope_head_dim, c.qk_rope_head_dim, c.kv_lora_rank, c.num_attention_heads
    B, S, _ = x.shape
    q = (rms_norm(x @ lp["q_a"], lp["q_norm"], c.rms_norm_eps) @ lp["q_b"]).reshape(B, S, h, n + p)
    kv = x @ lp["kv_a"]
    c_kv = rms_norm(kv[..., :r], lp["kv_norm"], c.rms_norm_eps)
    k_pe = rope(kv[..., r:], pos, c.rope_theta)
    return q[..., :n], rope(q[..., n:], pos, c.rope_theta), c_kv, k_pe


def _scale(c: Config) -> float:
    return 1.0 / math.sqrt(c.qk_nope_head_dim + c.qk_rope_head_dim)


def attend_decompressed(lp: dict, q_nope, q_pe, c_kv, k_pe, c: Config):
    n, v, h = c.qk_nope_head_dim, c.v_head_dim, c.num_attention_heads
    B, S = c_kv.shape[:2]
    kv = (c_kv @ lp["kv_b"]).reshape(B, S, h, n + v)
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_pe[:, :, None, :], (B, S, h, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    s = jnp.einsum("bqhc,bkhc->bhqk", q, k) * _scale(c)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, jnp.asarray(-jnp.inf, s.dtype))
    out = jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(s, axis=-1), kv[..., n:])
    return out.reshape(B, S, h * v) @ lp["o"]


def attend_absorbed(lp: dict, q_nope, q_pe, c_kv, k_pe, c: Config):
    n, v, r, h = c.qk_nope_head_dim, c.v_head_dim, c.kv_lora_rank, c.num_attention_heads
    B, S = q_nope.shape[:2]
    w_kb = lp["kv_b"].reshape(r, h, n + v)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_kb[..., :n])
    s = (jnp.einsum("bshr,btr->bsht", q_lat, c_kv) + jnp.einsum("bshp,btp->bsht", q_pe, k_pe)) * _scale(c)
    o_lat = jnp.einsum("bsht,btr->bshr", jax.nn.softmax(s, axis=-1), c_kv)
    out = jnp.einsum("bshr,rhv->bshv", o_lat, w_kb[..., n:])
    return out.reshape(B, S, h * v) @ lp["o"]


def _swiglu(w: dict, x):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def mlp(lp: dict, x, c: Config):
    if "dense" in lp:
        return _swiglu(lp["dense"], x)
    lead = x.shape[:-1]
    t = x.reshape(-1, x.shape[-1])
    top, idx = jax.lax.top_k(jax.nn.sigmoid(t @ lp["router"]), c.num_experts_per_tok)
    weight = top / jnp.sum(top, axis=-1, keepdims=True) * c.routed_scaling_factor
    ex = lp["experts"]
    g = jnp.einsum("td,tkdf->tkf", t, ex["gate"][idx])
    u = jnp.einsum("td,tkdf->tkf", t, ex["up"][idx])
    y = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(g) * u, ex["down"][idx])
    routed = jnp.sum(weight[..., None].astype(y.dtype) * y, axis=1)
    return (routed + _swiglu(lp["shared"], t)).reshape(*lead, -1)


def layer_forward(lp: dict, x, pos, c: Config):
    """One layer on ``x`` [B, S, d], attention decompressed; also what the
    layer leaves in the cache."""
    with jax.default_matmul_precision("highest"):
        q_nope, q_pe, c_kv, k_pe = project(lp, rms_norm(x, lp["attn_norm"], c.rms_norm_eps), pos, c)
        x = x + attend_decompressed(lp, q_nope, q_pe, c_kv, k_pe, c)
        x = x + mlp(lp, rms_norm(x, lp["mlp_norm"], c.rms_norm_eps), c)
    return x, (c_kv, k_pe)


def layer_decode(lp: dict, x, pos, cache: tuple, c: Config):
    """One new token per row, ``x`` [B, 1, d] at position ``pos``: its latent
    and rotary key appended to ``cache``, attention absorbed over all."""
    with jax.default_matmul_precision("highest"):
        q_nope, q_pe, c_new, k_new = project(lp, rms_norm(x, lp["attn_norm"], c.rms_norm_eps),
                                             jnp.reshape(pos, (1,)), c)
        c_kv = jnp.concatenate([cache[0], c_new], axis=1)
        k_pe = jnp.concatenate([cache[1], k_new], axis=1)
        x = x + attend_absorbed(lp, q_nope, q_pe, c_kv, k_pe, c)
        x = x + mlp(lp, rms_norm(x, lp["mlp_norm"], c.rms_norm_eps), c)
    return x, (c_kv, k_pe)
