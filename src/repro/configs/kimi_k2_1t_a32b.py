"""kimi-k2-1t-a32b — the grouped-query stand-in for Kimi-K2 (384 experts, top-8).

Not the published model: the chip benchmark's ``lm_1024`` configuration
freezes this trace as ``kimi-k2-as-gqa``, so its values stay as they are.
It takes the widths of Kimi-K2-Instruct's config.json
(https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json):
61L d_model=7168 64H, d_ff=2048 (per expert), vocab=163840, MoE 384e top-8;
and prices the attention as GQA (kv=8, head_dim 112 = 7168/64) where the
model has latent attention, with no shared expert and no leading dense
layer.  ``kimi-k2-instruct`` is the model as published.
~1.04T total params, ~31B active.
Requires: expert parallelism over the model axis, FSDP over data, 8-bit
optimizer states (see train/).
"""
from repro.configs.base import ModelConfig, MoEConfig, register

CONFIG = register(
    ModelConfig(
        name="kimi-k2-1t-a32b",
        family="moe",
        n_layers=61,
        d_model=7168,
        n_heads=64,
        n_kv_heads=8,
        d_ff=2048,
        vocab_size=163840,
        moe=MoEConfig(n_experts=384, top_k=8, d_ff_expert=2048),
        fsdp=True,
        param_dtype="bfloat16",  # 1T fp32 weights cannot fit 512 chips
        source="GQA stand-in for https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json",
    )
)
