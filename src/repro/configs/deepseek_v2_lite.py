"""deepseek-v2-lite [moe] — MLA without q-LoRA, 1 dense layer, then MoE:
2 shared + 64 routed experts top-6, softmax gates not renormalised; YaRN.

27L d_model=2048 16H kv_lora=512 d_ff=10944 (dense) d_ff_expert=1408
vocab=102400 [arXiv:2405.04434;
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json]

``full`` holds all 64 routed experts (``experts_held``: the whole layer, no
token dropped); a chip's share of an expert-parallel deployment holds a
block of them (``expert_first``, ``experts_held``; see bench/configs).
"""

from repro.configs.base import ModelConfig

YARN = dict(yarn_factor=40.0, yarn_original_max_len=4096, yarn_beta_fast=32.0,
            yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707)


def full() -> ModelConfig:
    return ModelConfig(
        arch_id="deepseek-v2-lite",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=192,  # qk_nope(128) + qk_rope(64)
        d_ff=10944,
        vocab_size=102_400,
        n_experts=64,
        n_shared_experts=2,
        top_k=6,
        d_ff_expert=1408,
        first_k_dense=1,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        experts_held=64,
        use_mla=True,
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        **YARN,
    )


def smoke() -> ModelConfig:
    return full().replace(
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        head_dim=24,
        d_ff=96,
        vocab_size=512,
        n_experts=8,
        n_shared_experts=1,
        top_k=2,
        d_ff_expert=48,
        experts_held=8,
        kv_lora_rank=24,
        qk_nope_dim=16,
        qk_rope_dim=8,
        v_head_dim=16,
    )
