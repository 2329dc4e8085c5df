"""deepseek-v2-lite [moe] — MLA kv_lora=512 without q compression, 2 shared
+ 64 routed experts top-6 under a softmax router, YaRN rope
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json].

27L, d_model=2048, 16 heads, d_ff=10944 (the one leading dense layer),
d_ff_expert=1408, vocab=102400, untied head. The router takes a softmax
over all 64 experts and keeps the greedy top 6 unrenormalized
(``norm_topk_prob`` false, ``routed_scaling_factor`` 1). Rope is YaRN:
factor 40 over 4096 original positions, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 0.707, theta 1e4.

``deepseek-v2-lite-ep4`` is one chip's share of a four-chip expert-parallel
deployment with data-parallel attention: it holds experts 0-15 of the 64
in each MoE layer (the router keeps all 64 outputs), and every chip holds
its own replica of attention, the shared experts, the dense layer, the
embedding and the head. Depth is cut to the dense layer and 8 MoE layers;
the layers left out would lie on further chips, as pipeline stages. MoE
layers are dropless: every pick of a held expert is computed.
"""
import dataclasses

from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig,
                                RopeScaling, register)

_MOE = MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408, num_shared=2,
                 first_dense=1, capacity_factor=None, router="softmax_topk")

DEEPSEEK_V2_LITE = register(ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,             # MLA: per-head latent attention (no GQA)
    head_dim=128,                # nope dim; see MLAConfig for the split
    d_ff=10944,                  # the single dense layer's FFN width
    vocab_size=102400,
    attention="full",
    causal=True,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0,
                  qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
    moe=_MOE,
    ffn_kind="glu",
    norm_kind="rmsnorm",
    position="rope",
    rope_theta=10_000.0,
    rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707),
    max_position=163_840,
    tie_embeddings=False,
    supports_decode=True,
    subquadratic=False,
))

DEEPSEEK_V2_LITE_EP4 = register(DEEPSEEK_V2_LITE.replace(
    name="deepseek-v2-lite-ep4",
    num_layers=9,
    moe=dataclasses.replace(_MOE, held_count=16),
))
