"""Whisper-style encoder-decoder backbone (audio family, frontend stubbed).

Per the assignment, the conv frontend is a STUB: ``input_specs`` supplies
precomputed frame embeddings (B, T_frames, d_model).  A learned adapter
linear stands in for the conv stack's output projection; sinusoidal
positions on the encoder, learned positions on the decoder (as in Whisper).

Decode caches: per-decoder-layer self-attention K/V ring + cross-attention
K/V computed ONCE at prefill from the encoder output (cross K/V are
position-independent, Whisper's serving trick).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding
from repro.models import attention
from repro.models.layers import (
    Leaf,
    cast,
    gelu_mlp,
    layernorm,
    sinusoidal_embedding,
    stack_schema,
)
from repro.models.transformer import decode_stack, scan_or_loop


def _ln(d):
    return {"w": Leaf((d,), ("embed",), init="ones"), "b": Leaf((d,), ("embed",), init="zeros")}


def _enc_layer_schema(cfg: ModelConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "ln1": _ln(d),
        "attn": attention.gqa_schema(cfg),
        "ln2": _ln(d),
        "mlp": {"wi": Leaf((d, ff), ("embed", "mlp")), "wo": Leaf((ff, d), ("mlp", "embed"))},
    }


def _dec_layer_schema(cfg: ModelConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "ln1": _ln(d),
        "self": attention.gqa_schema(cfg),
        "ln2": _ln(d),
        "cross": attention.cross_schema(cfg),
        "ln3": _ln(d),
        "mlp": {"wi": Leaf((d, ff), ("embed", "mlp")), "wo": Leaf((ff, d), ("mlp", "embed"))},
    }


def encdec_schema(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    return {
        "adapter": Leaf((d, d), ("embed", None)),  # stands for the conv stem out-proj
        "enc_layers": stack_schema(_enc_layer_schema(cfg), cfg.n_enc_layers),
        "enc_norm": _ln(d),
        "embed": Leaf((cfg.vocab_size, d), ("vocab", "embed"), init="embed"),
        "dec_pos": Leaf((cfg.max_source_len, d), (None, "embed"), init="embed", scale=0.02),
        "dec_layers": stack_schema(_dec_layer_schema(cfg), cfg.n_layers),
        "dec_norm": _ln(d),
    }


def encode(params: dict, frames: jnp.ndarray, cfg: ModelConfig, remat: bool = True):
    """frames: (B, T, d) stubbed frame embeddings -> encoder states."""
    t = frames.shape[1]
    h = frames.astype(jnp.bfloat16) @ cast(params["adapter"])
    h = h + sinusoidal_embedding(t, cfg.d_model)[None].astype(h.dtype)
    h = sharding.constrain(h, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], frames.shape[:2])

    def layer(hh, lp):
        hn = layernorm(hh, lp["ln1"]["w"], lp["ln1"]["b"])
        hh = hh + attention.gqa_attention(hn, lp["attn"], cfg, positions, causal=False)
        hn = layernorm(hh, lp["ln2"]["w"], lp["ln2"]["b"])
        return hh + gelu_mlp(hn, lp["mlp"]["wi"], lp["mlp"]["wo"]), 0.0

    fn = jax.checkpoint(layer, policy=jax.checkpoint_policies.nothing_saveable) if remat else layer

    def body(carry, lp):
        hh, _ = fn(carry, lp)
        return hh, None

    h, _ = scan_or_loop(body, h, params["enc_layers"], cfg.unroll_layers)
    return layernorm(h, params["enc_norm"]["w"], params["enc_norm"]["b"])


def decode_train(params: dict, tokens: jnp.ndarray, enc: jnp.ndarray, cfg: ModelConfig,
                 remat: bool = True):
    """Teacher-forced decoder -> final hidden (B, S, d)."""
    s = tokens.shape[1]
    # Pin the table replicated: sharding propagation otherwise re-shards the
    # gather operand's feature dim, which XLA's gather partitioner rejects
    # for non-mesh-divisible vocabs (51865).
    emb = sharding.constrain(cast(params["embed"]), None, None)
    h = emb[tokens] + cast(params["dec_pos"])[None, :s]
    h = sharding.constrain(h, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], tokens.shape)

    def layer(hh, lp):
        hn = layernorm(hh, lp["ln1"]["w"], lp["ln1"]["b"])
        hh = hh + attention.gqa_attention(hn, lp["self"], cfg, positions, causal=True)
        hn = layernorm(hh, lp["ln2"]["w"], lp["ln2"]["b"])
        hh = hh + attention.cross_attention(hn, lp["cross"], enc)
        hn = layernorm(hh, lp["ln3"]["w"], lp["ln3"]["b"])
        return hh + gelu_mlp(hn, lp["mlp"]["wi"], lp["mlp"]["wo"]), 0.0

    fn = jax.checkpoint(layer, policy=jax.checkpoint_policies.nothing_saveable) if remat else layer

    def body(carry, lp):
        hh, _ = fn(carry, lp)
        return hh, None

    h, _ = scan_or_loop(body, h, params["dec_layers"], cfg.unroll_layers)
    h = layernorm(h, params["dec_norm"]["w"], params["dec_norm"]["b"])
    return h


def logits(params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
    out = jnp.einsum("bsd,vd->bsv", hidden, cast(params["embed"]))  # tied head
    return sharding.constrain(out, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------


def init_caches(params: dict, enc: jnp.ndarray, cfg: ModelConfig, max_len: int):
    """Self-attn ring caches + cross K/V precomputed from encoder states."""
    b = enc.shape[0]

    def one_layer(lp):
        ck = jnp.einsum("bsd,dhe->bshe", enc, cast(lp["cross"]["wk"]))
        cv = jnp.einsum("bsd,dhe->bshe", enc, cast(lp["cross"]["wv"]))
        return ck, cv

    ck, cv = jax.vmap(one_layer)(params["dec_layers"])  # (L, B, T, H, hd)
    self_cache = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
        attention.gqa_init_cache(cfg, b, max_len),
    )
    return {"self": self_cache, "cross_k": ck, "cross_v": cv}


def decode_step(params: dict, token: jnp.ndarray, caches: dict, pos: jnp.ndarray,
                cfg: ModelConfig):
    """One decoder token; cross-attn reads precomputed K/V."""
    h = cast(params["embed"])[token] + cast(params["dec_pos"])[pos][:, None]

    def layer(hh, lp, sc, i, cross):
        hn = layernorm(hh, lp["ln1"]["w"], lp["ln1"]["b"])
        a, sc = attention.gqa_decode(hn, lp["self"], cfg, sc, i, pos)
        hh = hh + a
        hn = layernorm(hh, lp["ln2"]["w"], lp["ln2"]["b"])
        hh = hh + _cross_from_cache(hn, lp["cross"], *cross)
        hn = layernorm(hh, lp["ln3"]["w"], lp["ln3"]["b"])
        return hh + gelu_mlp(hn, lp["mlp"]["wi"], lp["mlp"]["wo"]), sc, None

    h, new_self, _ = decode_stack(
        h, params["dec_layers"], caches["self"], (caches["cross_k"], caches["cross_v"]),
        layer, cfg.unroll_layers,
    )
    h = layernorm(h, params["dec_norm"]["w"], params["dec_norm"]["b"])
    return h, {"self": new_self, "cross_k": caches["cross_k"], "cross_v": caches["cross_v"]}


def _cross_from_cache(x, p, ck, cv):
    import numpy as np

    q = jnp.einsum("bsd,dhe->bshe", x, cast(p["wq"]))
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, ck.astype(q.dtype), preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(scores / np.sqrt(q.shape[-1]), -1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhe->bqhe", probs, cv.astype(x.dtype))
    return jnp.einsum("bshe,hed->bsd", o, cast(p["wo"]))
