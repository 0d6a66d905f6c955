"""Operations and bytes a decode step of a latent-attention, expert-share
backbone needs (DeepSeek-V2's block), from its shapes; see ``costs`` for
what "needed" means.

Per token and layer, the absorbed latent attention: the query projection,
the latent and rope key of the new position, the query's nope part taken
into the latent through W_UK, scores against the cached latent and rope
key, the value sum in the latent, W_UV and wo.  Then the dense layer's MLP,
or the router, the shared experts and the held experts' part, whose
operations count the token-choices routed to held experts only (``2 *
choices * 3 * d * width``).  A token misses a given expert with probability
1 - top_k / n_experts, so at 512 tokens every held expert is hit with
probability 1 - (58/64)^512, and every held expert's weights count as
read.  Weights are read once, in the
dtype they are stored in; the cache positions in use are read, one
position is written.
"""

from __future__ import annotations

from bench.costs import BF16, F32


def layer_params(b: dict) -> dict:
    """Parameter counts: one layer's attention, its norms, the dense MLP,
    the router, the held experts, the shared experts; the output head."""
    d, h = b["d_model"], b["n_heads"]
    nope, rope, rank, v = b["qk_nope_dim"], b["qk_rope_dim"], b["kv_lora_rank"], b["v_head_dim"]
    attn = (d * h * (nope + rope) + d * (rank + rope) + rank
            + rank * h * (nope + v) + h * v * d)
    expert = 3 * d * b["d_ff_expert"]
    return {"attn": attn, "norms": 2 * d, "dense_mlp": 3 * d * b["d_ff"],
            "router": d * b["n_experts"], "held": b["experts_held"] * expert,
            "shared": b["n_shared_experts"] * expert, "expert": expert,
            "lm_head": d * b["vocab_size"], "final_norm": d}


def held_choices(b: dict, batch: int) -> float:
    """Token-choices a step routes to the held experts of one MoE layer,
    in expectation under uniform routing (what seeded random weights
    give): batch * top_k * held / n_experts."""
    return batch * b["top_k"] * b["experts_held"] / b["n_experts"]


def decode_step(b: dict, batch: int, position: int) -> tuple:
    """(flops, bytes) of one decode step of ``batch`` streams at
    ``position`` (0-based; the cache then holds ``position`` earlier
    positions)."""
    p = layer_params(b)
    d, h = b["d_model"], b["n_heads"]
    nope, rope, rank, v = b["qk_nope_dim"], b["qk_rope_dim"], b["kv_lora_rank"], b["v_head_dim"]
    n_dense = b["first_k_dense"]
    n_moe = b["n_layers"] - n_dense
    ctx = position + 1
    attn = 2 * (d * h * (nope + rope) + d * (rank + rope) + h * nope * rank
                + h * ctx * (rank + rope) + h * ctx * rank + h * rank * v + h * v * d)
    flops = batch * (b["n_layers"] * attn + n_dense * 2 * p["dense_mlp"]
                     + n_moe * 2 * (p["router"] + p["shared"]) + 2 * p["lm_head"])
    flops += n_moe * 2 * held_choices(b, batch) * p["expert"]
    weights = (b["n_layers"] * (p["attn"] + p["norms"]) + n_dense * p["dense_mlp"]
               + n_moe * (p["router"] + p["held"] + p["shared"])
               + p["lm_head"] + p["final_norm"]) * F32
    cache_read = b["n_layers"] * batch * (ctx - 1) * (rank + rope) * BF16
    cache_write = b["n_layers"] * batch * (rank + rope) * BF16
    embed = batch * d * F32
    return flops, weights + cache_read + cache_write + embed
