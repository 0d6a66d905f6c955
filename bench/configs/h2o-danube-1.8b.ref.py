"""Plain reference of h2o-danube-1.8b's forward pass (arXiv:2401.16818).

A Llama/Mistral decoder: RMSNorm, rotary embeddings (rotate-half form),
grouped-query attention with a causal sliding window, SwiGLU, a final
RMSNorm.  Everything in float32 with every matmul at "highest" precision,
over the whole sequence at once (no cache): the final hidden state at each
position is the feature vector a decode step hands to the heads.

It reads weights by name from the benchmark's own parameter tree; the
norm weights are stored as offsets from 1 (scale = 1 + w).  It imports
nothing of the program.

``control=True`` computes the same forward with every weight and every
activation that enters a matmul rounded to float8 (e4m3) under an absmax
scale, per tensor for weights and per token for activations: the
precision step below the bfloat16 the configuration computes in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _f8(a, axis):
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]  # (L, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("arch", "control"))
def _forward(params, tokens, arch, control):
    (n_heads, n_kv, window, theta, eps) = arch
    act = (lambda a: _f8(a, -1)) if control else (lambda a: a)
    wgt = (lambda w: _f8(w, None)) if control else (lambda w: w)
    b, length = tokens.shape
    pos = jnp.arange(length)
    allowed = (pos[None, :] <= pos[:, None]) & (pos[:, None] - pos[None, :] < window)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, lp):
        a = lp["attn"]
        xn = act(_rms(x, lp["ln1"], eps))
        q = jnp.einsum("bld,dhe->blhe", xn, wgt(a["wq"]))
        k = jnp.einsum("bld,dhe->blhe", xn, wgt(a["wk"]))
        v = jnp.einsum("bld,dhe->blhe", xn, wgt(a["wv"]))
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        hd = q.shape[-1]
        g = n_heads // n_kv
        q = act(q).reshape(b, length, n_kv, g, hd)
        s = jnp.einsum("blkge,bske->bkgls", q, act(k)) / np.sqrt(hd)
        s = jnp.where(allowed[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgls,bske->blkge", act(p), act(v)).reshape(b, length, n_heads, hd)
        x = x + jnp.einsum("blhe,hed->bld", act(o), wgt(a["wo"]))
        m = lp["mlp"]
        xn = act(_rms(x, lp["ln2"], eps))
        u = jax.nn.silu(xn @ wgt(m["wg"])) * (xn @ wgt(m["wu"]))
        return x + act(u) @ wgt(m["wd"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"], eps)


def features(params, tokens, backbone: dict, control: bool = False):
    """Final hidden states (B, L, d_model), float32, of ``tokens`` (B, L)."""
    arch = (backbone["n_heads"], backbone["n_kv_heads"], backbone["sliding_window"],
            float(backbone["rope_theta"]), float(backbone["norm_eps"]))
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, arch, control)
