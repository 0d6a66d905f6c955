"""Attention variants: GQA (full / sliding-window), MLA, cross-attention.

All functions are shape-polymorphic over (B, S) and share one KV-cache
convention for decode: a stack of the layers' caches, of which a decode
step of layer l writes each row's one new position (``write_rows``):

  GQA cache:  {"k": (L, B, S_max, KV * hd), "v": (L, B, S_max, KV * hd)}
  MLA cache:  {"ckv": (L, B, S_max, kv_lora), "k_rope": (L, B, S_max, rope_dim)}

MLA caches the *compressed* latent (DeepSeek-V2's serving advantage: 576
floats/token vs 2 * KV * hd) — the property that makes deepseek-v2 the
cheapest decode_32k cell in the roofline table.

Grouped einsums keep K/V un-repeated for GQA (no head-replication traffic).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed import sharding
from repro.models import layers
from repro.models.layers import Leaf, apply_rope, cast, rmsnorm

NEG_INF = -1e9  # bf16-safe large negative


# ---------------------------------------------------------------------------
# Schemas
# ---------------------------------------------------------------------------


def gqa_schema(cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    s = {
        "wq": Leaf((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": Leaf((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": Leaf((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": Leaf((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = Leaf((hd,), ("head_dim",), init="zeros")
        s["k_norm"] = Leaf((hd,), ("head_dim",), init="zeros")
    return s


def mla_schema(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_dim
    if cfg.q_lora_rank is None:
        q = {"wq": Leaf((d, h, qk + cfg.qk_rope_dim), ("embed", "heads", "head_dim"))}
    else:
        q = {
            "wq_a": Leaf((d, cfg.q_lora_rank), ("embed", None)),
            "q_a_norm": Leaf((cfg.q_lora_rank,), (None,), init="zeros"),
            "wq_b": Leaf(
                (cfg.q_lora_rank, h, qk + cfg.qk_rope_dim), (None, "heads", "head_dim")
            ),
        }
    return {
        **q,
        "wkv_a": Leaf((d, cfg.kv_lora_rank + cfg.qk_rope_dim), ("embed", None)),
        "kv_a_norm": Leaf((cfg.kv_lora_rank,), (None,), init="zeros"),
        "wkv_b": Leaf(
            (cfg.kv_lora_rank, h, qk + cfg.v_head_dim), (None, "heads", "head_dim")
        ),
        "wo": Leaf((h, cfg.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def cross_schema(cfg: ModelConfig) -> dict:
    d, hd, h = cfg.d_model, cfg.resolved_head_dim, cfg.n_heads
    return {
        "wq": Leaf((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": Leaf((d, h, hd), ("embed", "heads", "head_dim")),
        "wv": Leaf((d, h, hd), ("embed", "heads", "head_dim")),
        "wo": Leaf((h, hd, d), ("heads", "head_dim", "embed")),
    }


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------


def _mask_bias(
    q_pos: jnp.ndarray,  # (B, Sq)
    k_pos: jnp.ndarray,  # (B, Sk)
    causal: bool,
    window: Optional[int],
    k_valid: Optional[jnp.ndarray] = None,  # (B, Sk) bool
) -> jnp.ndarray:
    """(B, 1, 1, Sq, Sk) additive bias."""
    dq = q_pos[:, :, None]
    dk = k_pos[:, None, :]
    ok = jnp.ones_like(dq + dk, dtype=bool)
    if causal:
        ok &= dk <= dq
    if window is not None:
        ok &= dq - dk < window
    if k_valid is not None:
        ok &= k_valid[:, None, :]
    return jnp.where(ok, 0.0, NEG_INF)[:, None, None, :, :].astype(jnp.float32)


def _grouped_attention(q, k, v, bias):
    """q: (B,Sq,KV,G,hd); k/v: (B,Sk,KV,hd); bias: (B,1,1,Sq,Sk) -> (B,Sq,KV,G,hd)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(hd) + jnp.transpose(bias, (0, 2, 1, 3, 4))
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs, v, preferred_element_type=jnp.float32)


def _chunked_grouped_attention(
    q, k, v, q_pos, k_pos, causal: bool, window: Optional[int], chunk: int
):
    """Flash-style online-softmax attention, scanned over KV chunks.

    Never materializes the (Sq, Sk) score matrix: per chunk the working set
    is (B, KV, G, Sq, chunk) — O(S * chunk) instead of O(S^2).  Numerics:
    running max/denominator in f32 (the FlashAttention recurrence).
    q: (B,Sq,KV,G,hd); k/v: (B,Sk,KV,hd); q_pos/k_pos: (B, Sq)/(B, Sk).
    """
    b, sq, kv, g, hd = q.shape
    sk = k.shape[1]
    chunk = min(chunk, sk)
    assert sk % chunk == 0, f"kv len {sk} not divisible by chunk {chunk}"
    nc = sk // chunk
    scale = np.float32(1.0 / np.sqrt(hd))

    kc = k.reshape(b, nc, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    kpc = k_pos.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        m, l, acc = carry  # (B,KV,G,Sq) f32, same, (B,Sq,KV,G,hd) f32
        k_i, v_i, kp_i = xs
        s = jnp.einsum("bqkgh,bskh->bkgqs", q, k_i, preferred_element_type=jnp.float32)
        s = s * scale
        ok = jnp.ones((b, sq, chunk), bool)
        dq = q_pos[:, :, None]
        dk = kp_i[:, None, :]
        if causal:
            ok &= dk <= dq
        if window is not None:
            ok &= dq - dk < window
        ok &= dk >= 0  # ring slots never written stay masked
        s = s + jnp.where(ok, 0.0, NEG_INF)[:, None, None, :, :]

        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)  # rescale old accumulator
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        pv = jnp.einsum(
            "bkgqs,bskh->bqkgh", p.astype(v_i.dtype), v_i,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, kpc))
    denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return acc / denom


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def _qkv(x, p, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    g = cfg.n_heads // kv
    q = jnp.einsum("bsd,dhe->bshe", x, cast(p["wq"]))
    k = jnp.einsum("bsd,dhe->bshe", x, cast(p["wk"]))
    v = jnp.einsum("bsd,dhe->bshe", x, cast(p["wv"]))
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = sharding.constrain(q, "batch", "seq", "heads", "head_dim")
    k = sharding.constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = sharding.constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return q.reshape(b, s, kv, g, hd), k, v


def gqa_attention(
    x: jnp.ndarray,  # (B, S, d) compute dtype
    p: dict,
    cfg: ModelConfig,
    positions: jnp.ndarray,  # (B, S)
    causal: bool = True,
    window: Optional[int] = None,
    return_kv: bool = False,
):
    b, s, _ = x.shape
    q, k, v = _qkv(x, p, cfg, positions)
    if window is None:
        window = cfg.sliding_window
    if cfg.attention_impl == "chunked":
        o = _chunked_grouped_attention(
            q, k, v, positions, positions, causal, window, cfg.attention_chunk
        )
    else:
        bias = _mask_bias(positions, positions, causal, window)
        o = _grouped_attention(q, k, v, bias)
    o = o.reshape(b, s, cfg.n_heads, cfg.resolved_head_dim).astype(x.dtype)
    out = jnp.einsum("bshe,hed->bsd", o, cast(p["wo"]))
    if return_kv:
        return out, (k, v)
    return out


def gqa_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=layers.COMPUTE_DTYPE):
    width = cfg.n_kv_heads * cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, width), dtype),
        "v": jnp.zeros((batch, max_len, width), dtype),
    }


def gqa_decode(
    x: jnp.ndarray,  # (B, 1, d)
    p: dict,
    cfg: ModelConfig,
    cache: dict,
    layer,
    pos: jnp.ndarray,  # (B,) ABSOLUTE position (tokens already cached)
    window: Optional[int] = None,
    ring: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """One decode step of layer ``layer`` over the stacked cache ({"k", "v"}:
    (L, B, S_max, KV * hd)): each row's new K/V is written into its slot of
    the stack (``write_rows``) and attention reads the layer back with it.
    A position's heads lie in one row of KV*hd values: a (..., KV, hd)
    stack with hd 80 is laid out on the TPU with the sequence dimension
    minor, and a row write there makes the compiler relay the whole stack
    out around the layer loop; rows of KV*hd keep it row-major.  ``ring=True`` treats
    the cache as a circular buffer of the last ``cache_len`` tokens
    (sliding-window archs): K/V are written at slot ``pos % cache_len`` but
    RoPE always uses absolute positions, and each slot's absolute position
    is reconstructed for masking.  Returns the output and the updated
    stack."""
    b = x.shape[0]
    cache_len = cache["k"].shape[2]
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    q, k_new, v_new = _qkv(x, p, cfg, pos[:, None])  # RoPE at absolute pos
    slot = pos % cache_len if ring else pos
    cache = {"k": write_rows(cache["k"], k_new.reshape(b, 1, kv * hd), layer, slot),
             "v": write_rows(cache["v"], v_new.reshape(b, 1, kv * hd), layer, slot)}
    k, v = (jax.lax.dynamic_index_in_dim(cache[n], layer, keepdims=False)
            .reshape(b, cache_len, kv, hd) for n in ("k", "v"))

    j = jnp.arange(cache_len)[None]  # (1, S_max)
    if ring:
        # Absolute position last written to slot j (negative -> never written).
        k_pos = pos[:, None] - jnp.mod(pos[:, None] - j, cache_len)
        valid = k_pos >= 0
    else:
        k_pos = jnp.broadcast_to(j, (b, cache_len))
        valid = k_pos <= pos[:, None]
    bias = _mask_bias(
        pos[:, None], k_pos, causal=False, window=window or cfg.sliding_window,
        k_valid=valid,
    )
    o = _grouped_attention(q, k.astype(x.dtype), v.astype(x.dtype), bias)
    o = o.reshape(b, 1, cfg.n_heads, cfg.resolved_head_dim).astype(x.dtype)
    out = jnp.einsum("bshe,hed->bsd", o, cast(p["wo"]))
    return out, cache


def write_rows(stack, new, layer, slot):
    """``stack`` (L, B, S_max, ...) with each row b's new entry ``new[b, 0]``
    written at (layer, b, slot[b]): one scatter of B rows, in place where
    the stack is donated or carried through a layer scan, for rows at equal
    or different positions alike."""
    rows = jnp.arange(stack.shape[1])
    return stack.at[layer, rows, slot].set(new[:, 0].astype(stack.dtype),
                                           indices_are_sorted=True, unique_indices=True)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_rope(cfg: ModelConfig) -> tuple:
    """(inverse frequencies or None, cos/sin scale, softmax scale) of the
    latent attention: plain rope and 1/sqrt(q head dim), or with YaRN
    (``yarn_factor``) DeepSeek-V2's frequencies, cos and sin times
    mscale(mscale) / mscale(mscale_all_dim), and the softmax scale times
    mscale(mscale_all_dim)^2."""
    softmax = 1.0 / np.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if not cfg.yarn_factor:
        return None, 1.0, softmax
    f = cfg.yarn_factor
    freqs = layers.yarn_frequencies(cfg.qk_rope_dim, cfg.rope_theta, f,
                                    cfg.yarn_original_max_len, cfg.yarn_beta_fast,
                                    cfg.yarn_beta_slow)
    scale = layers.yarn_mscale(f, cfg.yarn_mscale) / layers.yarn_mscale(
        f, cfg.yarn_mscale_all_dim)
    if cfg.yarn_mscale_all_dim:
        softmax *= layers.yarn_mscale(f, cfg.yarn_mscale_all_dim) ** 2
    return freqs, scale, softmax


def _mla_rope(x, positions, cfg):
    freqs, scale, _ = mla_rope(cfg)
    return apply_rope(x, positions, cfg.rope_theta, freqs=freqs, scale=scale)


def _mla_q(x, p, cfg, positions):
    if "wq" in p:
        q = jnp.einsum("bsd,dhe->bshe", x, cast(p["wq"]))
    else:
        q_a = rmsnorm(x @ cast(p["wq_a"]), p["q_a_norm"])
        q = jnp.einsum("bsr,rhe->bshe", q_a, cast(p["wq_b"]))
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    return q_nope, _mla_rope(q_rope, positions, cfg)


def _mla_kv_latent(x, p, cfg, positions):
    """The compressed latent (B,S,kv_lora), normed, and the shared roped
    key (B,S,rope_dim) of x: what the cache holds."""
    kv_a = x @ cast(p["wkv_a"])
    ckv, k_rope = jnp.split(kv_a, [cfg.kv_lora_rank], axis=-1)
    ckv = rmsnorm(ckv, p["kv_a_norm"])
    k_rope = _mla_rope(k_rope[:, :, None, :], positions, cfg)[:, :, 0]
    return ckv, k_rope


def _mla_kv(ckv, p, cfg):
    """Expand compressed latent (B,S,kv_lora) -> per-head k_nope, v."""
    kv = jnp.einsum("bsr,rhe->bshe", ckv, cast(p["wkv_b"]))
    return jnp.split(kv, [cfg.qk_nope_dim], axis=-1)  # k_nope, v


def mla_attention(x, p, cfg: ModelConfig, positions, causal: bool = True,
                  return_kv: bool = False):
    """The published (expanded) form: the latent is taken up to per-head
    keys and values.  Prefill and training run it; decode runs the absorbed
    form over the cache (``mla_decode``)."""
    q_nope, q_rope = _mla_q(x, p, cfg, positions)
    ckv, k_rope = _mla_kv_latent(x, p, cfg, positions)
    k_nope, v = _mla_kv(ckv, p, cfg)

    bias = _mask_bias(positions, positions, causal, None)
    scale = mla_rope(cfg)[2]
    scores = (
        jnp.einsum("bqhe,bkhe->bhqk", q_nope, k_nope, preferred_element_type=jnp.float32)
        + jnp.einsum("bqhe,bke->bhqk", q_rope, k_rope, preferred_element_type=jnp.float32)
    ) * scale + bias[:, 0]
    probs = jax.nn.softmax(scores, -1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhe->bqhe", probs, v, preferred_element_type=jnp.float32)
    o = o.astype(x.dtype)
    out = jnp.einsum("bshe,hed->bsd", o, cast(p["wo"]))
    if return_kv:
        return out, (ckv, k_rope)
    return out


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=layers.COMPUTE_DTYPE):
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


def mla_decode(x, p, cfg: ModelConfig, cache, layer, pos):
    """One decode step of layer ``layer`` over the stacked latent cache
    ({"ckv", "k_rope"}: (L, B, S_max, ·)) with the up-projections absorbed:
    each row's new latent and roped key are written into their slots of the
    stack (``write_rows``), the query's nope part is taken through W_UK
    into the latent, scores are read against the layer's cached latent and
    roped key directly, the values are summed in the latent and taken out
    through W_UV and wo.  The cache is read once and never expanded to
    per-head keys or values.  Returns the output and the updated stack."""
    max_len = cache["ckv"].shape[2]
    q_nope, q_rope = _mla_q(x, p, cfg, pos[:, None])
    ckv_new, k_rope_new = _mla_kv_latent(x, p, cfg, pos[:, None])
    cache = {"ckv": write_rows(cache["ckv"], ckv_new, layer, pos),
             "k_rope": write_rows(cache["k_rope"], k_rope_new, layer, pos)}
    ckv, k_rope = (jax.lax.dynamic_index_in_dim(cache[n], layer, keepdims=False)
                   for n in ("ckv", "k_rope"))

    w_uk, w_uv = jnp.split(cast(p["wkv_b"]), [cfg.qk_nope_dim], axis=-1)
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bqhe,rhe->bqhr", q_nope, w_uk).astype(x.dtype)
    with jax.named_scope("mla.latent_attn"):
        valid = jnp.arange(max_len)[None] <= pos[:, None]  # (B, S_max)
        scores = (
            jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv.astype(x.dtype),
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhe,bke->bhqk", q_rope, k_rope.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        ) * mla_rope(cfg)[2]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, -1).astype(x.dtype)
        o_lat = jnp.einsum("bhqk,bkr->bqhr", probs, ckv.astype(x.dtype))
    with jax.named_scope("mla.absorb"):
        o = jnp.einsum("bqhr,rhe->bqhe", o_lat.astype(x.dtype), w_uv)
    out = jnp.einsum("bshe,hed->bsd", o.astype(x.dtype), cast(p["wo"]))
    return out, cache


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder)
# ---------------------------------------------------------------------------


def cross_attention(x, p, ctx, src_valid=None):
    """x: (B, Sq, d) queries; ctx: (B, Sk, d) encoder output."""
    b, sq, _ = x.shape
    q = jnp.einsum("bsd,dhe->bshe", x, cast(p["wq"]))
    k = jnp.einsum("bsd,dhe->bshe", ctx, cast(p["wk"]))
    v = jnp.einsum("bsd,dhe->bshe", ctx, cast(p["wv"]))
    hd = q.shape[-1]
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(hd)
    if src_valid is not None:
        scores += jnp.where(src_valid, 0.0, NEG_INF)[:, None, None, :]
    probs = jax.nn.softmax(scores, -1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhe->bqhe", probs, v)
    return jnp.einsum("bshe,hed->bsd", o.astype(x.dtype), cast(p["wo"]))
