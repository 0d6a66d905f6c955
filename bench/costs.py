"""Operations and bytes that each piece of work needs, from its shapes.

These are the needed amounts, not what the program happens to move: the
weights read once, the cache positions in use, one position written; the
head's P and beta read and written only for the rows that learn.  A
roofline share is the least time the chip could take (the larger of
operations over peak rate and bytes over peak bandwidth) over the time the
work took.
"""

from __future__ import annotations

F32, BF16 = 4, 2


def backbone_params(b: dict) -> dict:
    """Parameter counts of the dense decoder: per layer, the matmul
    weights; and the output head."""
    d, hd = b["d_model"], b["d_model"] // b["n_heads"]
    attn = d * b["n_heads"] * hd + 2 * d * b["n_kv_heads"] * hd + b["n_heads"] * hd * d
    mlp = 3 * d * b["d_ff"]
    return {"layer": attn + mlp, "norms": 2 * d, "lm_head": d * b["vocab_size"],
            "final_norm": d}


def decode_step(b: dict, batch: int, position: int) -> tuple:
    """(flops, bytes) of one decode step of ``batch`` streams at
    ``position`` (0-based; the cache then holds ``position`` earlier
    tokens, at most the sliding window)."""
    p = backbone_params(b)
    L, d = b["n_layers"], b["d_model"]
    hd = d // b["n_heads"]
    ctx = min(position + 1, b["sliding_window"])
    flops = 2 * batch * (L * p["layer"] + p["lm_head"])
    flops += L * 4 * batch * b["n_heads"] * ctx * hd  # q.k and p.v
    weights = (L * (p["layer"] + p["norms"]) + p["lm_head"] + p["final_norm"]) * F32
    kv_read = L * batch * (ctx - 1) * b["n_kv_heads"] * hd * 2 * BF16
    kv_write = L * batch * b["n_kv_heads"] * hd * 2 * BF16
    embed = batch * d * F32
    return flops, weights + kv_read + kv_write + embed


def head_plan(rows: int, n_in: int, n_hidden: int, n_out: int) -> tuple:
    """(flops, bytes) of one plan over ``rows`` heads: x read, x alpha,
    beta read, the readout."""
    flops = 2 * rows * n_in * n_hidden + 2 * rows * n_hidden * n_out
    return flops, rows * (n_in + n_hidden * n_out) * F32


def head_learn(rows: int, n_hidden: int, n_out: int) -> tuple:
    """(flops, bytes) of one rank-1 RLS update of ``rows`` heads: P h, the
    outer product, the beta update; P and beta read and written."""
    n, m = n_hidden, n_out
    flops = rows * (4 * n * n + 4 * n * m)
    return flops, rows * 2 * (n * n + n * m) * F32


def min_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
