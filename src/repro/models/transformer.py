"""Decoder-only LM assembly: scan-over-layers, remat, heterogeneous stacks.

One code path serves all eight decoder-only assigned archs:
  dense  — GQA attention (+ optional SWA / qk_norm) + SwiGLU       (h2o-danube,
           deepseek-coder, mistral-nemo, qwen3, chameleon)
  moe    — GQA or MLA attention + fine-grained MoE                  (deepseek-moe,
           deepseek-v2)
  ssm    — Mamba-2 SSD mixer only                                   (mamba2)
  hybrid — Griffin pattern (rec, rec, attn) with per-block MLPs     (recurrentgemma)

Layers are stacked (leading L dim on every leaf) and executed with
``jax.lax.scan`` + ``jax.checkpoint`` so the unrolled HLO stays one layer
deep — this is what keeps 60-layer/160-expert configs compilable and remat
memory bounded.  Hybrid stacks scan over pattern *groups* plus an explicit
tail stack when n_layers % len(pattern) != 0.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding
from repro.models import attention, moe, rglru, ssm
from repro.models.layers import (
    TOKEN_CHUNK,
    Leaf,
    cast,
    rmsnorm,
    stack_schema,
    swiglu,
    token_chunked,
)

# ---------------------------------------------------------------------------
# Schemas
# ---------------------------------------------------------------------------


def _mixer_schema(cfg: ModelConfig, dense: bool = False) -> dict:
    """One layer; ``dense`` gives a MoE config's leading dense layer."""
    if cfg.family == "ssm":
        return {"norm": Leaf((cfg.d_model,), ("embed",), init="zeros"),
                "ssd": ssm.ssd_schema(cfg)}
    s: dict = {"ln1": Leaf((cfg.d_model,), ("embed",), init="zeros")}
    s["attn"] = attention.mla_schema(cfg) if cfg.use_mla else attention.gqa_schema(cfg)
    s["ln2"] = Leaf((cfg.d_model,), ("embed",), init="zeros")
    if cfg.n_experts and not dense:
        s["moe"] = moe.moe_schema(cfg)
    else:
        d, ff = cfg.d_model, cfg.d_ff
        s["mlp"] = {
            "wg": Leaf((d, ff), ("embed", "mlp")),
            "wu": Leaf((d, ff), ("embed", "mlp")),
            "wd": Leaf((ff, d), ("mlp", "embed")),
        }
    return s


def _hybrid_sub_schema(cfg: ModelConfig, kind: str) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    mlp = {
        "wg": Leaf((d, ff), ("embed", "mlp")),
        "wu": Leaf((d, ff), ("embed", "mlp")),
        "wd": Leaf((ff, d), ("mlp", "embed")),
    }
    if kind == "rec":
        return {
            "ln1": Leaf((d,), ("embed",), init="zeros"),
            "rec": rglru.rglru_schema(cfg),
            "ln2": Leaf((d,), ("embed",), init="zeros"),
            "mlp": mlp,
        }
    return {
        "ln1": Leaf((d,), ("embed",), init="zeros"),
        "attn": attention.gqa_schema(cfg),
        "ln2": Leaf((d,), ("embed",), init="zeros"),
        "mlp": mlp,
    }


def lm_schema(cfg: ModelConfig) -> dict:
    s: dict = {
        "embed": Leaf((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="embed"),
        "final_norm": Leaf((cfg.d_model,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = Leaf((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))

    if cfg.hybrid_pattern:
        pat = cfg.hybrid_pattern
        n_groups, tail = divmod(cfg.n_layers, len(pat))
        group = {f"b{i}_{k}": _hybrid_sub_schema(cfg, k) for i, k in enumerate(pat)}
        s["groups"] = stack_schema(group, n_groups)
        if tail:
            tail_group = {
                f"b{i}_{k}": _hybrid_sub_schema(cfg, k)
                for i, k in enumerate(pat[:tail])
            }
            s["tail"] = stack_schema(tail_group, 1)
    else:
        if cfg.first_k_dense:
            s["dense_layers"] = stack_schema(_mixer_schema(cfg, dense=True),
                                             cfg.first_k_dense)
        s["layers"] = stack_schema(_mixer_schema(cfg), cfg.n_layers - cfg.first_k_dense)
    return s


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def _dense_layer(h, lp, cfg: ModelConfig, positions):
    hn = rmsnorm(h, lp["ln1"])
    if cfg.use_mla:
        h = h + attention.mla_attention(hn, lp["attn"], cfg, positions)
    else:
        h = h + attention.gqa_attention(hn, lp["attn"], cfg, positions)
    hn = rmsnorm(h, lp["ln2"])
    if "moe" in lp:
        y, aux = moe.moe_block(hn, lp["moe"], cfg)
        return h + y, aux
    return h + swiglu(hn, lp["mlp"]["wg"], lp["mlp"]["wu"], lp["mlp"]["wd"]), 0.0


def _ssm_layer(h, lp, cfg: ModelConfig, positions):
    del positions
    return h + ssm.ssd_block(rmsnorm(h, lp["norm"]), lp["ssd"], cfg), 0.0


def _hybrid_sub(h, sp, kind: str, cfg: ModelConfig, positions):
    hn = rmsnorm(h, sp["ln1"])
    if kind == "rec":
        h = h + rglru.rglru_block(hn, sp["rec"], cfg)
    else:
        h = h + attention.gqa_attention(
            hn, sp["attn"], cfg, positions, window=cfg.sliding_window
        )
    hn = rmsnorm(h, sp["ln2"])
    return h + swiglu(hn, sp["mlp"]["wg"], sp["mlp"]["wu"], sp["mlp"]["wd"])


def _stack_len(stacked) -> int:
    return jax.tree.leaves(stacked)[0].shape[0]


def scan_or_loop(body, init, xs, unroll: bool):
    """lax.scan drop-in that can unroll to a Python loop (cost extrapolation).

    Supports pytree ys (stacked along axis 0) like lax.scan.
    """
    if not unroll:
        return jax.lax.scan(body, init, xs)
    carry = init
    ys = []
    for i in range(_stack_len(xs)):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        stacked_ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked_ys = None
    return carry, stacked_ys


def _scan_stack(h, stacked, layer_fn, remat: bool, unroll: bool = False):
    fn = layer_fn
    if remat:
        fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable
        )

    if unroll:  # dry-run cost extrapolation path (see ModelConfig)
        aux = 0.0
        for i in range(_stack_len(stacked)):
            h, a = fn(h, jax.tree.map(lambda x: x[i], stacked))
            aux = aux + a
        return h, aux

    def body(carry, lp):
        hh, aux = carry
        hh, a = fn(hh, lp)
        return (hh, aux + a), None

    (h, aux), _ = jax.lax.scan(body, (h, 0.0), stacked)
    return h, aux


def lm_hidden(
    params: dict,
    tokens: jnp.ndarray,  # (B, S) int32
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray] = None,
    remat: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Token ids -> final hidden states (B, S, d) [compute dtype], aux loss."""
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None], tokens.shape
        )
    h = cast(params["embed"])[tokens]
    h = sharding.constrain(h, "batch", "seq", "embed")
    unroll = cfg.unroll_layers

    if cfg.hybrid_pattern:
        pat = cfg.hybrid_pattern

        def group_fn(hh, gp):
            for i, kind in enumerate(pat):
                key = f"b{i}_{kind}"
                if key in gp:
                    hh = _hybrid_sub(hh, gp[key], kind, cfg, positions)
            return hh, 0.0

        h, aux = _scan_stack(h, params["groups"], group_fn, remat, unroll)
        if "tail" in params:
            def tail_fn(hh, gp):
                for i, kind in enumerate(pat):
                    key = f"b{i}_{kind}"
                    if key in gp:
                        hh = _hybrid_sub(hh, gp[key], kind, cfg, positions)
                return hh, 0.0

            h, _ = _scan_stack(h, params["tail"], tail_fn, remat, unroll)
    elif cfg.family == "ssm":
        h, aux = _scan_stack(
            h, params["layers"],
            functools.partial(_ssm_layer, cfg=cfg, positions=positions), remat, unroll,
        )
    else:
        layer_fn = functools.partial(_dense_layer, cfg=cfg, positions=positions)
        if "dense_layers" in params:
            h, _ = _scan_stack(h, params["dense_layers"], layer_fn, remat, unroll)
        h, aux = _scan_stack(h, params["layers"], layer_fn, remat, unroll)

    h = rmsnorm(h, params["final_norm"])
    h = sharding.constrain(h, "batch", "seq", "embed")
    return h, aux


def lm_logits(params: dict, hidden: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", hidden, cast(params["embed"]))
    else:
        logits = hidden @ cast(params["lm_head"])
    return sharding.constrain(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Prefill: single forward pass that also builds decode caches
# ---------------------------------------------------------------------------


def _pad_full_cache(k, v, max_len):
    """Full-attention cache: (B,S,KV,hd) K/V as rows of KV*hd, padded to
    max_len slots."""
    b, s, kv, hd = k.shape
    k, v = k.reshape(b, s, kv * hd), v.reshape(b, s, kv * hd)
    if s == max_len:
        return {"k": k, "v": v}
    kp = jnp.zeros((b, max_len, kv * hd), k.dtype).at[:, :s].set(k)
    vp = jnp.zeros((b, max_len, kv * hd), v.dtype).at[:, :s].set(v)
    return {"k": kp, "v": vp}


def _ring_cache(k, v, win, s_total):
    """Sliding-window ring: last win tokens at slots (abs_pos % win), as
    rows of KV*hd."""
    b, s, kv, hd = k.shape
    take = min(s, win)
    idx = (jnp.arange(s_total - take, s_total)) % win
    kr = jnp.zeros((b, win, kv * hd), k.dtype).at[:, idx].set(k[:, -take:].reshape(b, take, -1))
    vr = jnp.zeros((b, win, kv * hd), v.dtype).at[:, idx].set(v[:, -take:].reshape(b, take, -1))
    return {"k": kr, "v": vr}


def lm_prefill(params: dict, tokens: jnp.ndarray, cfg: ModelConfig, max_len=None):
    """Forward the prompt once, collecting per-layer decode caches as scan ys.

    Returns (final_hidden (B,S,d), caches, pos (B,)) with cache structure
    identical to ``init_caches``.
    """
    b, s = tokens.shape
    max_len = max_len or s
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], tokens.shape)
    h = cast(params["embed"])[tokens]
    h = sharding.constrain(h, "batch", "seq", "embed")

    if cfg.hybrid_pattern:
        pat = cfg.hybrid_pattern

        def group_fn(hh, gp):
            new_c = {}
            for i, kind in enumerate(pat):
                key = f"b{i}_{kind}"
                if key not in gp:
                    continue
                sp = gp[key]
                hn = rmsnorm(hh, sp["ln1"])
                if kind == "rec":
                    y, new_c[key] = rglru.rglru_block(hn, sp["rec"], cfg, return_cache=True)
                else:
                    y, (k, v) = attention.gqa_attention(
                        hn, sp["attn"], cfg, positions, window=cfg.sliding_window,
                        return_kv=True,
                    )
                    new_c[key] = _ring_cache(k, v, min(max_len, cfg.sliding_window or max_len), s)
                hh = hh + y
                hn = rmsnorm(hh, sp["ln2"])
                hh = hh + swiglu(hn, sp["mlp"]["wg"], sp["mlp"]["wu"], sp["mlp"]["wd"])
            return hh, new_c

        h, groups_c = scan_or_loop(group_fn, h, params["groups"], cfg.unroll_layers)
        caches = {"groups": groups_c}
        if "tail" in params:
            h, tail_c = scan_or_loop(group_fn, h, params["tail"], cfg.unroll_layers)
            caches["tail"] = tail_c
    elif cfg.family == "ssm":

        def ssm_fn(hh, lp):
            y, c = ssm.ssd_block(rmsnorm(hh, lp["norm"]), lp["ssd"], cfg, return_cache=True)
            return hh + y, c

        h, layer_c = scan_or_loop(ssm_fn, h, params["layers"], cfg.unroll_layers)
        caches = {"layers": layer_c}
    else:

        def dense_fn(hh, lp):
            hn = rmsnorm(hh, lp["ln1"])
            # A long prefill runs its sequences in chunks of TOKEN_CHUNK
            # tokens through the wide parts of a layer, so their activations
            # (the latent attention's expanded keys, values and scores, a
            # dense MLP's hidden units) stay a chunk's size.
            if cfg.use_mla:
                y, (ckv, k_rope) = token_chunked(
                    lambda xc, pc: attention.mla_attention(xc, lp["attn"], cfg, pc,
                                                           return_kv=True),
                    hn, positions, chunk=max(1, TOKEN_CHUNK // s))
                if s == max_len:
                    c = {"ckv": ckv, "k_rope": k_rope}
                else:
                    c = {
                        "ckv": jnp.zeros((b, max_len, ckv.shape[-1]), ckv.dtype).at[:, :s].set(ckv),
                        "k_rope": jnp.zeros((b, max_len, k_rope.shape[-1]), k_rope.dtype).at[:, :s].set(k_rope),
                    }
            else:
                y, (k, v) = attention.gqa_attention(
                    hn, lp["attn"], cfg, positions, return_kv=True
                )
                if cfg.sliding_window is not None:
                    c = _ring_cache(k, v, min(max_len, cfg.sliding_window), s)
                else:
                    c = _pad_full_cache(k, v, max_len)
            hh = hh + y
            hn = rmsnorm(hh, lp["ln2"])
            if "moe" not in lp:
                m = lp["mlp"]
                hh = hh + token_chunked(lambda t: swiglu(t, m["wg"], m["wu"], m["wd"]),
                                        hn, chunk=max(1, TOKEN_CHUNK // s))
            elif cfg.experts_held:
                y2, idx, _ = moe.share_block(hn, lp["moe"], cfg)
                hh = hh + y2
                c.update(_expert_stats(cfg), route=idx.reshape(b, s, -1)[:, -1])
            else:
                y2, _ = moe.moe_block(hn, lp["moe"], cfg)
                hh = hh + y2
            return hh, c

        caches = {}
        if "dense_layers" in params:
            h, caches["dense_layers"] = scan_or_loop(dense_fn, h, params["dense_layers"],
                                                     cfg.unroll_layers)
        h, caches["layers"] = scan_or_loop(dense_fn, h, params["layers"], cfg.unroll_layers)

    h = rmsnorm(h, params["final_norm"])
    pos = jnp.full((b,), s, jnp.int32)
    return h, caches, pos


# ---------------------------------------------------------------------------
# Decode (one token, stacked caches)
# ---------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked per-layer caches (leading dim = n stacked layers/groups)."""

    def rep(tree, n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), tree)

    if cfg.hybrid_pattern:
        pat = cfg.hybrid_pattern
        n_groups, tail = divmod(cfg.n_layers, len(pat))
        group = {}
        for i, kind in enumerate(pat):
            if kind == "rec":
                group[f"b{i}_{kind}"] = rglru.rglru_init_cache(cfg, batch)
            else:
                group[f"b{i}_{kind}"] = attention.gqa_init_cache(
                    cfg, batch, min(max_len, cfg.sliding_window or max_len)
                )
        caches = {"groups": rep(group, n_groups)}
        if tail:
            tail_group = {
                f"b{i}_{k}": (
                    rglru.rglru_init_cache(cfg, batch)
                    if k == "rec"
                    else attention.gqa_init_cache(cfg, batch, min(max_len, cfg.sliding_window or max_len))
                )
                for i, k in enumerate(pat[:tail])
            }
            caches["tail"] = rep(tail_group, 1)
        return caches
    if cfg.family == "ssm":
        return {"layers": rep(ssm.ssd_init_cache(cfg, batch), cfg.n_layers)}
    if cfg.use_mla:
        layer = attention.mla_init_cache(cfg, batch, max_len)
    else:
        eff = max_len if cfg.sliding_window is None else min(max_len, cfg.sliding_window)
        layer = attention.gqa_init_cache(cfg, batch, eff)
    if not cfg.first_k_dense and not cfg.experts_held:
        return {"layers": rep(layer, cfg.n_layers)}
    caches = {}
    if cfg.first_k_dense:
        caches["dense_layers"] = rep(layer, cfg.first_k_dense)
    if cfg.experts_held:
        layer = {**layer, **_expert_stats(cfg),
                 "route": jnp.zeros((batch, cfg.top_k), jnp.int32)}
    caches["layers"] = rep(layer, cfg.n_layers - cfg.first_k_dense)
    return caches


def _expert_stats(cfg: ModelConfig) -> dict:
    """The expert share's per-layer counters, kept in the decode state:
    token-choices routed to each held expert and rows each held expert
    computed (padding included), summed over decode steps (prefill starts
    them at 0)."""
    z = jnp.zeros((cfg.experts_held,), jnp.int32)
    return {"expert_tokens": z, "expert_rows": z}


def _decode_dense_layer(h, lp, cc, i, lc, cfg: ModelConfig, pos):
    """Layer ``i`` of a dense or MoE stack over the carried caches ``cc``
    (``lc`` is None): the attention writes its rows into the stack, the
    expert share's counters and route are updated at ``i``."""
    hn = rmsnorm(h, lp["ln1"])
    if cfg.use_mla:
        a, kv = attention.mla_decode(hn, lp["attn"], cfg, cc, i, pos)
    else:
        ring = cfg.sliding_window is not None
        a, kv = attention.gqa_decode(hn, lp["attn"], cfg, cc, i, pos, ring=ring)
    cc = {**cc, **kv}
    h = h + a
    hn = rmsnorm(h, lp["ln2"])
    if "moe" not in lp:
        h = h + swiglu(hn, lp["mlp"]["wg"], lp["mlp"]["wu"], lp["mlp"]["wd"])
    elif cfg.experts_held:
        y, idx, counts = moe.share_block(hn, lp["moe"], cfg)
        h = h + y
        cc.update(route=cc["route"].at[i].set(idx),
                  expert_tokens=cc["expert_tokens"].at[i].add(counts),
                  expert_rows=cc["expert_rows"].at[i].add(idx.shape[0]))
    else:
        y, _ = moe.moe_block(hn, lp["moe"], cfg)
        h = h + y
    return h, cc, None


def _decode_ssm_layer(h, lp, cc, i, lc, cfg: ModelConfig, pos):
    """Layer ``i`` of an SSM stack: its state ``lc`` is updated whole."""
    del i, pos
    y, lc = ssm.ssd_decode(rmsnorm(h, lp["norm"]), lp["ssd"], cfg, lc)
    return h + y, cc, lc


def _decode_hybrid_group(h, gp, cc, i, rec, cfg: ModelConfig, pos):
    """Pattern group ``i`` of a hybrid stack: local attention writes its
    rows into the carried ring caches ``cc``, the recurrent blocks update
    their states ``rec`` whole."""
    cc, rec = dict(cc), dict(rec)
    for j, kind in enumerate(cfg.hybrid_pattern):
        key = f"b{j}_{kind}"
        if key not in gp:
            continue
        sp = gp[key]
        hn = rmsnorm(h, sp["ln1"])
        if kind == "rec":
            y, rec[key] = rglru.rglru_decode(hn, sp["rec"], cfg, rec[key])
        else:
            # Local attention over a ring buffer of the last `window` tokens.
            y, cc[key] = attention.gqa_decode(hn, sp["attn"], cfg, cc[key], i, pos, ring=True)
        h = h + y
        hn = rmsnorm(h, sp["ln2"])
        h = h + swiglu(hn, sp["mlp"]["wg"], sp["mlp"]["wu"], sp["mlp"]["wd"])
    return h, cc, rec


def decode_stack(h, stacked, carried, per_layer, layer, unroll: bool):
    """A decode step over one stacked layer group, ``layer(h, lp, carried,
    i, lc) -> (h, carried, lc')`` at each layer ``i``.  ``carried`` (the
    attention caches and their counters) rides in the scan's carry, so each
    layer writes its rows into the donated stack where it lies; a scan that
    emitted the caches as outputs would build a second stack of them.
    ``per_layer`` (recurrent states, whole-state updates; read-only inputs)
    goes in as the scan's inputs, and the ``lc'`` come out as its outputs
    (None for none).  Returns (h, carried, outputs)."""

    def body(carry, xs):
        hh, cc = carry
        lp, lc, i = xs
        hh, cc, lc = layer(hh, lp, cc, i, lc)
        return (hh, cc), lc

    xs = (stacked, per_layer, jnp.arange(_stack_len(stacked)))
    (h, carried), ys = scan_or_loop(body, (h, carried), xs, unroll)
    return h, carried, ys


def lm_decode_hidden(
    params: dict,
    token: jnp.ndarray,  # (B, 1) int32
    caches: dict,
    pos: jnp.ndarray,  # (B,) int32 number of tokens already in cache
    cfg: ModelConfig,
) -> tuple[jnp.ndarray, dict]:
    """One decode step -> final hidden (B, 1, d) + updated caches."""
    h = cast(params["embed"])[token]
    new_caches = {}
    if cfg.hybrid_pattern:
        rec_keys = {f"b{j}_rec" for j, kind in enumerate(cfg.hybrid_pattern) if kind == "rec"}
        layer = functools.partial(_decode_hybrid_group, cfg=cfg, pos=pos)
        for name in ("groups", "tail"):
            if name not in params:
                continue
            c = caches[name]
            h, attn, rec = decode_stack(
                h, params[name], {k: v for k, v in c.items() if k not in rec_keys},
                {k: v for k, v in c.items() if k in rec_keys}, layer, cfg.unroll_layers)
            new_caches[name] = {**attn, **rec}
    elif cfg.family == "ssm":
        layer = functools.partial(_decode_ssm_layer, cfg=cfg, pos=pos)
        h, _, new_caches["layers"] = decode_stack(h, params["layers"], {}, caches["layers"],
                                                  layer, cfg.unroll_layers)
    else:
        layer = functools.partial(_decode_dense_layer, cfg=cfg, pos=pos)
        for name in ("dense_layers", "layers"):
            if name in params:
                h, new_caches[name], _ = decode_stack(h, params[name], caches[name], None,
                                                      layer, cfg.unroll_layers)

    h = rmsnorm(h, params["final_norm"])
    return h, new_caches
