"""kimi-k2-instruct — Kimi-K2-Instruct at its published widths.

[hf:moonshotai/Kimi-K2-Instruct config.json]  61L d_model=7168 64H, multi-head
latent attention (q_lora_rank 1536, kv_lora_rank 512, qk_nope/qk_rope/v head
dims 128/64/128), layer 0 dense (first_k_dense_replace 1, intermediate_size
18432), then 60 MoE layers: 384 routed experts top-8 of width 2048 with
sigmoid scores, normalized top-k weights x 2.827, and 1 shared expert;
vocab 163840, RMSNorm eps 1e-6, RoPE theta 50000 (YaRN x32 beyond 4k
positions).  ~1.03T parameters, ~33B active per token.

Traced only: ``repro.models`` has no latent attention, so the assigned grid
(``all_archs``) leaves it out; ``traced_archs`` lists it.  Routing is priced
uniform over the experts, as every MoE block of ``core.trace`` prices it.
``workloads/kimi_reference.py`` is its plain reference.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, register

CONFIG = register(
    ModelConfig(
        name="kimi-k2-instruct",
        family="moe",
        n_layers=61,
        d_model=7168,
        n_heads=64,
        n_kv_heads=64,
        d_ff=18432,  # the leading dense layer's MLP
        vocab_size=163840,
        rope_theta=50_000.0,
        norm_eps=1e-6,
        moe=MoEConfig(n_experts=384, top_k=8, d_ff_expert=2048, n_shared=1, first_k_dense=1,
                      routed_scaling=2.827),
        mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        param_dtype="bfloat16",
        source="https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json",
    )
)
