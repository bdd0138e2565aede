"""Plain reference of Kimi-K2's block stack: the forward pass that
``core.trace`` prices for ``kimi-k2-instruct``, in straightforward
``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
with no kernels and no batching beyond the array axes.

A layer is multi-head latent attention (DeepSeek-V2 §2.1) and then an MLP:
dense for the first ``moe.first_k_dense`` layers, else routed experts with
sigmoid scores, top-k, weights normalized over the k and scaled by
``moe.routed_scaling``, plus ``moe.n_shared`` shared experts.  Attention
comes in the two forms the tracer prices:

  * :func:`forward` decompresses (prefill, train): ``kv_b`` rebuilds every
    head's key and value from the latent, then causal multi-head attention
    with query/key head ``n + p`` and value head ``v``;
  * :func:`decode_step` absorbs ``kv_b`` into the query and the output and
    attends over the latent cache (``c_kv`` [B, L, r] and the shared rotary
    key ``k_pe`` [B, L, p]) as it is stored.

Departures from the published model, none of which moves a FLOP or a byte
that the tracer counts: RoPE without YaRN's scaling (and so no YaRN
``mscale`` on the softmax scale); rotate-half RoPE, where the published
checkpoint's interleaved layout is a fixed permutation of the rotary weight
columns; top-k over the sigmoid scores alone, without the routing-bias
term ``e_score_correction_bias`` (a load-balancing offset, zero at
initialization); no multi-token-prediction module (the config has none).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def init(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    """Seeded random weights, scaled 1/sqrt(fan-in); norms at one."""
    m, e, d, h = cfg.mla, cfg.moe, cfg.d_model, cfg.n_heads
    n, p, v, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.kv_lora_rank
    keys = iter(jax.random.split(key, 16 * cfg.n_layers + 4))

    def w(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(shape[-2])).astype(dtype)

    def ones(k):
        return jnp.ones((k,), dtype)

    def swiglu(width, *lead):
        return dict(gate=w(*lead, d, width), up=w(*lead, d, width), down=w(*lead, width, d))

    layers = []
    for i in range(cfg.n_layers):
        lp = dict(attn_norm=ones(d), q_a=w(d, m.q_lora_rank), q_norm=ones(m.q_lora_rank),
                  q_b=w(m.q_lora_rank, h * (n + p)), kv_a=w(d, r + p), kv_norm=ones(r),
                  kv_b=w(r, h * (n + v)), o=w(h * v, d), mlp_norm=ones(d))
        if i < e.first_k_dense:
            lp["dense"] = swiglu(cfg.d_ff)
        else:
            lp["router"] = w(d, e.n_experts)
            lp["experts"] = swiglu(e.d_ff_expert, e.n_experts)
            lp["shared"] = swiglu(e.n_shared * e.d_ff_expert)
        layers.append(lp)
    embed = (jax.random.normal(next(keys), (cfg.vocab_size, d), jnp.float32)).astype(dtype)
    return dict(embed=embed, layers=layers, final_norm=ones(d), head=w(d, cfg.vocab_size))


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, pos, theta: float):
    """Rotate-half RoPE over the last axis of ``x`` [..., S, (H,) p] at
    positions ``pos`` [S]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq  # [S, half]
    if x.ndim == 4:  # [B, S, H, p]
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def project(lp: dict, x, pos, cfg: ModelConfig):
    """The low-rank paths of one layer on ``x`` [B, S, d] (already normed):
    the queries' parts without and with position [B, S, h, n], [B, S, h, p],
    and what the cache holds, the normed latent [B, S, r] and the rotary key
    [B, S, p]."""
    m, h = cfg.mla, cfg.n_heads
    n, p, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.kv_lora_rank
    B, S, _ = x.shape
    q = rms_norm(x @ lp["q_a"], lp["q_norm"], cfg.norm_eps) @ lp["q_b"]
    q = q.reshape(B, S, h, n + p)
    kv = x @ lp["kv_a"]
    c_kv = rms_norm(kv[..., :r], lp["kv_norm"], cfg.norm_eps)
    k_pe = rope(kv[..., r:], pos, cfg.rope_theta)
    return q[..., :n], rope(q[..., n:], pos, cfg.rope_theta), c_kv, k_pe


def _scale(cfg: ModelConfig) -> float:
    return 1.0 / math.sqrt(cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim)


def attend_decompressed(lp: dict, q_nope, q_pe, c_kv, k_pe, cfg: ModelConfig):
    """Causal multi-head attention over keys and values rebuilt from the
    latent by ``kv_b``; the output projection included.  [B, S, d]."""
    m, h = cfg.mla, cfg.n_heads
    n, v = m.qk_nope_head_dim, m.v_head_dim
    B, S = c_kv.shape[:2]
    kv = (c_kv @ lp["kv_b"]).reshape(B, S, h, n + v)
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_pe[:, :, None, :], (B, S, h, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    s = jnp.einsum("bqhc,bkhc->bhqk", q, k) * _scale(cfg)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, jnp.asarray(-jnp.inf, s.dtype))
    out = jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(s, axis=-1), kv[..., n:])
    return out.reshape(B, S, h * v) @ lp["o"]


def attend_absorbed(lp: dict, q_nope, q_pe, c_kv, k_pe, cfg: ModelConfig):
    """Attention of queries [B, S, h, .] over the whole latent cache
    (``c_kv`` [B, L, r], ``k_pe`` [B, L, p]): ``kv_b``'s key half absorbed
    into the queries, its value half applied after the AV; the output
    projection included.  [B, S, d]."""
    m, h = cfg.mla, cfg.n_heads
    n, v, r = m.qk_nope_head_dim, m.v_head_dim, m.kv_lora_rank
    B, S = q_nope.shape[:2]
    w_kb = lp["kv_b"].reshape(r, h, n + v)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_kb[..., :n])
    s = (jnp.einsum("bshr,btr->bsht", q_lat, c_kv) + jnp.einsum("bshp,btp->bsht", q_pe, k_pe)) * _scale(cfg)
    o_lat = jnp.einsum("bsht,btr->bshr", jax.nn.softmax(s, axis=-1), c_kv)
    out = jnp.einsum("bshr,rhv->bshv", o_lat, w_kb[..., n:])
    return out.reshape(B, S, h * v) @ lp["o"]


def _swiglu(w: dict, x):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def moe(lp: dict, x, cfg: ModelConfig):
    """Routed experts (each token's top-k gathered and applied) plus the
    shared expert, on ``x`` [..., d]."""
    e = cfg.moe
    lead = x.shape[:-1]
    t = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(t @ lp["router"])
    top, idx = jax.lax.top_k(scores, e.top_k)  # [T, k]
    weight = top / jnp.sum(top, axis=-1, keepdims=True) * e.routed_scaling
    ex = lp["experts"]
    g = jnp.einsum("td,tkdf->tkf", t, ex["gate"][idx])
    u = jnp.einsum("td,tkdf->tkf", t, ex["up"][idx])
    y = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(g) * u, ex["down"][idx])
    routed = jnp.sum(weight[..., None].astype(y.dtype) * y, axis=1)
    return (routed + _swiglu(lp["shared"], t)).reshape(*lead, -1)


def mlp(lp: dict, x, cfg: ModelConfig):
    """The layer's MLP on ``x`` (already normed): dense or experts."""
    return _swiglu(lp["dense"], x) if "dense" in lp else moe(lp, x, cfg)


def layer_forward(lp: dict, x, pos, cfg: ModelConfig):
    """One layer on ``x`` [B, S, d] at positions ``pos`` [S], attention
    decompressed; also returns what the layer leaves in the cache."""
    with jax.default_matmul_precision("highest"):
        q_nope, q_pe, c_kv, k_pe = project(lp, rms_norm(x, lp["attn_norm"], cfg.norm_eps), pos, cfg)
        x = x + attend_decompressed(lp, q_nope, q_pe, c_kv, k_pe, cfg)
        x = x + mlp(lp, rms_norm(x, lp["mlp_norm"], cfg.norm_eps), cfg)
    return x, (c_kv, k_pe)


def layer_decode(lp: dict, x, pos, cache: tuple, cfg: ModelConfig):
    """One layer on one new token per row, ``x`` [B, 1, d] at position
    ``pos``: its latent and rotary key appended to ``cache`` (``c_kv``
    [B, L, r], ``k_pe`` [B, L, p]), attention absorbed over all L + 1."""
    with jax.default_matmul_precision("highest"):
        q_nope, q_pe, c_new, k_new = project(lp, rms_norm(x, lp["attn_norm"], cfg.norm_eps),
                                             jnp.reshape(pos, (1,)), cfg)
        c_kv = jnp.concatenate([cache[0], c_new], axis=1)
        k_pe = jnp.concatenate([cache[1], k_new], axis=1)
        x = x + attend_absorbed(lp, q_nope, q_pe, c_kv, k_pe, cfg)
        x = x + mlp(lp, rms_norm(x, lp["mlp_norm"], cfg.norm_eps), cfg)
    return x, (c_kv, k_pe)


def _logits(params: dict, x, cfg: ModelConfig):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["head"]


def forward(params: dict, tokens, cfg: ModelConfig):
    """Logits [B, S, V] of the whole stack on ``tokens`` [B, S], attention
    decompressed; and the latent cache each layer leaves."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[1])
    cache = []
    for lp in params["layers"]:
        x, c = layer_forward(lp, x, pos, cfg)
        cache.append(c)
    return _logits(params, x, cfg), cache


def decode_step(params: dict, token, pos, cache: list, cfg: ModelConfig):
    """Logits [B, V] for ``token`` [B] at position ``pos``, attending
    through the latent ``cache`` (one entry per layer), which it extends."""
    x = params["embed"][token][:, None, :]
    out = []
    for lp, c in zip(params["layers"], cache):
        x, c = layer_decode(lp, x, pos, c, cfg)
        out.append(c)
    return _logits(params, x, cfg)[:, 0], out
