"""Plain reference of DeepSeek-V2-Lite's forward pass at one chip's expert
share (arXiv:2405.04434; the model's config.json).

A decoder of ``first_k_dense`` dense layers and then MoE layers, each:
RMSNorm; multi-head latent attention in its published, expanded form
(queries straight from d_model, no q-LoRA; keys and values taken up from
the normed 512-wide latent, a 64-wide rope key shared by every head;
scores over nope and rope parts, scaled by mscale^2 / sqrt(192)); RMSNorm;
then a SwiGLU MLP (dense layers) or the expert layer: softmax router over
all ``n_experts``, greedy top-k, gates the raw probabilities unless
``norm_topk_prob``, times ``routed_scaling_factor``, the held experts' part
of the routed sum (``expert_first`` to ``expert_first + experts_held``,
every routed token, no capacity) plus the shared experts; a final RMSNorm.
Rope is YaRN as DeepSeek computes it (``yarn_find_correction_range``, a
linear ramp, cos and sin times mscale(mscale) / mscale(mscale_all_dim)).
Everything in float32 with every matmul at "highest" precision, over the
whole sequence at once (no cache).  It imports nothing of the program.

Departures from the published model, none of which changes the result
under seeded random weights:

* rope uses the rotate-half layout.  DeepSeek's checkpoints store the rope
  columns of ``wq`` and ``wkv_a`` interleaved and de-interleave them before
  rotating: a fixed permutation of those columns.
* norm weights are stored as offsets from 1 (scale = 1 + w), as the
  benchmark's parameter tree stores them.
* what the experts this chip does not hold add to a token is another
  chip's part, left out here as in the program (the expert share).

``control=True`` computes the same forward with every weight and every
activation that enters a matmul rounded to float8 (e4m3) under an absmax
scale, per tensor for weights and per token for activations: the
precision step below the bfloat16 the configuration computes in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _f8(a, axis):
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` inverse frequencies."""

    def correction_dim(rot):
        return (dim * math.log(original / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def rope_params(b: dict):
    """(inverse frequencies, cos/sin scale, softmax scale) of the backbone."""
    dim = b["qk_rope_dim"]
    softmax = (b["qk_nope_dim"] + dim) ** -0.5
    f = b.get("yarn_factor") or 0.0
    if not f:
        return 1.0 / b["rope_theta"] ** (np.arange(0, dim, 2, dtype=np.float32) / dim), 1.0, softmax
    inv = yarn_inv_freq(dim, b["rope_theta"], f, b["yarn_original_max_len"],
                        b["yarn_beta_fast"], b["yarn_beta_slow"])
    scale = yarn_mscale(f, b["yarn_mscale"]) / yarn_mscale(f, b["yarn_mscale_all_dim"])
    if b["yarn_mscale_all_dim"]:
        softmax *= yarn_mscale(f, b["yarn_mscale_all_dim"]) ** 2
    return inv, scale, softmax


def _rope(x, pos, inv, scale):
    """x (B, L, H, dim), rotate-half layout."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)[None, :]  # (L, dim/2)
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("arch", "control"))
def _forward(params, tokens, arch, control):
    (nope, rank, top_k, first, norm_topk, scaling, eps, inv, scale, softmax) = arch
    inv = np.asarray(inv, np.float32)
    act = (lambda a: _f8(a, -1)) if control else (lambda a: a)
    wgt = (lambda w: _f8(w, None)) if control else (lambda w: w)
    b, length = tokens.shape
    pos = jnp.arange(length)
    causal = pos[None, :] <= pos[:, None]
    x = params["embed"][tokens].astype(jnp.float32)

    def attention(x, a):
        xn = act(_rms(x, a["ln1"], eps))
        q = jnp.einsum("bld,dhe->blhe", xn, wgt(a["attn"]["wq"]))
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        kv_a = xn @ wgt(a["attn"]["wkv_a"])
        ckv = act(_rms(kv_a[..., :rank], a["attn"]["kv_a_norm"], eps))
        k_pe = kv_a[..., rank:][:, :, None, :]
        kv = jnp.einsum("blr,rhe->blhe", ckv, wgt(a["attn"]["wkv_b"]))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_pe, k_pe = _rope(q_pe, pos, inv, scale), _rope(k_pe, pos, inv, scale)
        k_pe = jnp.broadcast_to(k_pe, q_pe.shape)
        qk = act(jnp.concatenate([q_nope, q_pe], -1))
        kk = act(jnp.concatenate([k_nope, k_pe], -1))
        s = jnp.einsum("blhe,bshe->bhls", qk, kk) * softmax
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhls,bshe->blhe", act(p), act(v))
        return x + jnp.einsum("blhe,hed->bld", act(o), wgt(a["attn"]["wo"]))

    def swiglu(xn, m):
        u = jax.nn.silu(xn @ wgt(m["wg"])) * (xn @ wgt(m["wu"]))
        return act(u) @ wgt(m["wd"])

    def dense_layer(x, lp):
        x = attention(x, lp)
        return x + swiglu(act(_rms(x, lp["ln2"], eps)), lp["mlp"]), None

    def moe_layer(x, lp):
        x = attention(x, lp)
        m = lp["moe"]
        xn = act(_rms(x, lp["ln2"], eps))
        probs = jax.nn.softmax(xn @ wgt(m["router"]), axis=-1)
        gate, idx = jax.lax.top_k(probs, top_k)
        if norm_topk:
            gate = gate / gate.sum(-1, keepdims=True)
        gate = gate * scaling
        y = swiglu(xn, m["shared"])
        for e in range(m["wg"].shape[0]):
            g = jnp.sum(jnp.where(idx == first + e, gate, 0.0), -1)[..., None]
            u = jax.nn.silu(xn @ wgt(m["wg"][e])) * (xn @ wgt(m["wu"][e]))
            y = y + g * (act(u) @ wgt(m["wd"][e]))
        return x + y, idx

    if "dense_layers" in params:
        x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
    x, routes = jax.lax.scan(moe_layer, x, params["layers"])
    return _rms(x, params["final_norm"], eps), routes


def _arch(b: dict):
    inv, scale, softmax = rope_params(b)
    return (b["qk_nope_dim"], b["kv_lora_rank"], b["top_k"], b["expert_first"],
            bool(b["norm_topk_prob"]), float(b["routed_scaling_factor"]),
            float(b["norm_eps"]), tuple(float(v) for v in inv), float(scale), float(softmax))


def forward(params, tokens, backbone: dict, control: bool = False):
    """(final hidden states (B, L, d_model) float32, each MoE layer's top-k
    expert ids (layers, B, L, top_k)) of ``tokens`` (B, L)."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, _arch(backbone), control)


def features(params, tokens, backbone: dict, control: bool = False):
    """Final hidden states (B, L, d_model), float32, of ``tokens`` (B, L)."""
    return forward(params, tokens, backbone, control)[0]


@functools.partial(jax.jit, static_argnames=("control",))
def _logits(feats, lm_head, control):
    if control:
        return _f8(feats, -1) @ _f8(lm_head, None)
    return feats @ lm_head


def logits(params, feats, control: bool = False):
    """Logits (..., vocab), float32, of final hidden states ``feats`` (from
    ``features`` or ``forward``)."""
    with jax.default_matmul_precision("highest"):
        return _logits(feats, params["lm_head"], control)
