"""deepseek-v3-671b [moe] -- 61L d_model=7168 128H, multi-head latent
attention (MLA: no KV heads; q_lora 1536, kv_lora 512, nope 128, rope 64,
v 128) with YaRN rope, 3 leading dense layers (d_ff 18432), then MoE: 1
shared + 256 routed experts of 2048, top-8, vocab=129280 untied, MTP
[arXiv:2412.19437; hf deepseek-ai/DeepSeek-V3 config.json]

Interpretation of the published numbers:
* ``expert_ff`` 2048 is ``moe_intermediate_size`` (routed and shared
  experts); the 3 leading dense layers use ``intermediate_size`` 18432.
* Router (``topk_method`` noaux_tc, ``scoring_func`` sigmoid): sigmoid
  scores plus a per-expert bias used only to select; 8 groups of 32, the
  top 4 groups by their two best biased scores, the top 8 experts in them;
  weights are the selected unbiased scores renormalized, times 2.5.
* YaRN (``rope_scaling``): factor 40 over 4096 original positions, beta
  32 / 1, mscale and mscale_all_dim 1; rope on adjacent dim pairs, as the
  published inference code applies it.
* ``n_kv_heads`` 128 is only the config's ``num_key_value_heads``; MLA
  caches one 512-wide latent and one 64-wide rope key per position.
* MTP (multi-token prediction, depth 1) is not built: greedy decoding
  without speculation never runs it.
"""
from .base import ArchConfig, MLAConfig, MoEConfig, YaRNConfig, register

CONFIG = register(ArchConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,                # dense-layer FF (first_k_dense layers)
    vocab_size=129280,
    head_dim=192,              # qk_nope (128) + qk_rope (64)
    norm_eps=1e-6,
    first_k_dense=3,
    moe=MoEConfig(n_routed=256, n_shared=1, top_k=8, expert_ff=2048,
                  n_expert_groups=8, topk_group=4, router_scale=2.5,
                  scoring="sigmoid"),
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    rope_theta=10_000.0,
    rope_scaling=YaRNConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0,
                            mscale_all_dim=1.0),
))
