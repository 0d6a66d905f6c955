"""The decode step's cache write: each row's new position is written into
the carried layer stack where it lies (``attention.write_rows``).  Against a
copy, kept here, of the one-hot formulation it replaced (each layer's cache
rebuilt as ``buf * (1 - one_hot) + one_hot * new``, the stack emitted by the
layer scan), one decode step with rows at different positions gives the same
caches, logits and features, bit for bit, in float32.  The one-hot write is
exact for finite values, and the row write stores the same ``new``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention, layers, moe, transformer
from repro.models import model as model_lib
from repro.models.layers import cast, rmsnorm, swiglu

B, MAX_LEN = 4, 16

CASES = {
    # dense GQA, no window: slot = position
    "gqa": (lambda: configs.get_config("h2o-danube-1.8b", "smoke").replace(
        sliding_window=None, attention_kind="full"), [3, 11, 0, 7]),
    # GQA over a ring of 8 slots, positions inside the first lap
    "gqa_ring": (lambda: configs.get_config("h2o-danube-1.8b", "smoke").replace(
        sliding_window=8), [3, 5, 1, 7]),
    # the same ring, positions past its length: slot = position % 8
    "gqa_ring_wrapped": (lambda: configs.get_config("h2o-danube-1.8b", "smoke").replace(
        sliding_window=8), [9, 15, 20, 30]),
    # latent attention without q-LoRA, a dense layer then the expert share
    "mla": (lambda: configs.get_config("deepseek-v2-lite", "smoke"), [3, 11, 0, 7]),
    # latent attention with q-LoRA
    "mla_q_lora": (lambda: configs.get_config("deepseek-v2-lite", "smoke").replace(
        q_lora_rank=32), [14, 2, 9, 5]),
}


def _blend(buf, new, oh):
    """The one-hot write: buf (B,S,...), new (B,1,...), oh (B,S)."""
    oh = oh.reshape(oh.shape + (1,) * (buf.ndim - 2)).astype(buf.dtype)
    return buf * (1.0 - oh) + oh * new.astype(buf.dtype)


def _onehot_gqa(x, p, cfg, cache, pos, ring):
    b, cache_len = x.shape[0], cache["k"].shape[1]
    q, k_new, v_new = attention._qkv(x, p, cfg, pos[:, None])
    slot = pos % cache_len if ring else pos
    oh = jax.nn.one_hot(slot, cache_len, dtype=cache["k"].dtype)
    k = _blend(cache["k"], k_new.reshape(b, 1, -1), oh)
    v = _blend(cache["v"], v_new.reshape(b, 1, -1), oh)
    kv_shape = (b, cache_len, cfg.n_kv_heads, cfg.resolved_head_dim)
    j = jnp.arange(cache_len)[None]
    if ring:
        k_pos = pos[:, None] - jnp.mod(pos[:, None] - j, cache_len)
        valid = k_pos >= 0
    else:
        k_pos = jnp.broadcast_to(j, (b, cache_len))
        valid = k_pos <= pos[:, None]
    bias = attention._mask_bias(pos[:, None], k_pos, causal=False,
                                window=cfg.sliding_window, k_valid=valid)
    o = attention._grouped_attention(q, k.reshape(kv_shape).astype(x.dtype),
                                     v.reshape(kv_shape).astype(x.dtype), bias)
    o = o.reshape(b, 1, cfg.n_heads, cfg.resolved_head_dim).astype(x.dtype)
    return jnp.einsum("bshe,hed->bsd", o, cast(p["wo"])), {"k": k, "v": v}


def _onehot_mla(x, p, cfg, cache, pos):
    max_len = cache["ckv"].shape[1]
    q_nope, q_rope = attention._mla_q(x, p, cfg, pos[:, None])
    ckv_new, k_rope_new = attention._mla_kv_latent(x, p, cfg, pos[:, None])
    oh = jax.nn.one_hot(pos, max_len, dtype=cache["ckv"].dtype)
    ckv, k_rope = _blend(cache["ckv"], ckv_new, oh), _blend(cache["k_rope"], k_rope_new, oh)
    w_uk, w_uv = jnp.split(cast(p["wkv_b"]), [cfg.qk_nope_dim], axis=-1)
    q_lat = jnp.einsum("bqhe,rhe->bqhr", q_nope, w_uk).astype(x.dtype)
    valid = jnp.arange(max_len)[None] <= pos[:, None]
    scores = (
        jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv.astype(x.dtype),
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bqhe,bke->bhqk", q_rope, k_rope.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    ) * attention.mla_rope(cfg)[2]
    scores = jnp.where(valid[:, None, None, :], scores, attention.NEG_INF)
    probs = jax.nn.softmax(scores, -1).astype(x.dtype)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", probs, ckv.astype(x.dtype))
    o = jnp.einsum("bqhr,rhe->bqhe", o_lat.astype(x.dtype), w_uv)
    out = jnp.einsum("bshe,hed->bsd", o.astype(x.dtype), cast(p["wo"]))
    return out, {"ckv": ckv, "k_rope": k_rope}


def _onehot_layer(h, xs, cfg, pos):
    lp, lc = xs
    hn = rmsnorm(h, lp["ln1"])
    if cfg.use_mla:
        a, nc = _onehot_mla(hn, lp["attn"], cfg, lc, pos)
    else:
        a, nc = _onehot_gqa(hn, lp["attn"], cfg, lc, pos, cfg.sliding_window is not None)
    h = h + a
    hn = rmsnorm(h, lp["ln2"])
    if "moe" in lp:
        y, idx, counts = moe.share_block(hn, lp["moe"], cfg)
        nc.update(route=idx, expert_tokens=lc["expert_tokens"] + counts,
                  expert_rows=lc["expert_rows"] + idx.shape[0])
        return h + y, nc
    return h + swiglu(hn, lp["mlp"]["wg"], lp["mlp"]["wu"], lp["mlp"]["wd"]), nc


def _onehot_decode(params, token, caches, pos, cfg):
    """One decode step as the layer scan ran it with the one-hot write:
    (logits, features, caches)."""
    h = cast(params["embed"])[token]
    new = {}
    for name in ("dense_layers", "layers"):
        if name in params:
            h, new[name] = jax.lax.scan(
                lambda hh, xs: _onehot_layer(hh, xs, cfg, pos), h, (params[name], caches[name]))
    h = rmsnorm(h, params["final_norm"])
    return transformer.lm_logits(params, h, cfg)[:, 0], h[:, 0].astype(jnp.float32), new


@pytest.mark.parametrize("case", list(CASES))
def test_row_write_equals_the_one_hot_write(monkeypatch, case):
    make_cfg, positions = CASES[case]
    cfg = make_cfg()
    monkeypatch.setattr(layers, "COMPUTE_DTYPE", jnp.float32)
    params = layers.init_params(model_lib.build_schema(cfg), jax.random.PRNGKey(3))
    shapes = jax.eval_shape(lambda: transformer.init_caches(cfg, B, MAX_LEN))
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    # Caches as if filled by earlier steps: every slot holds a finite value.
    caches = treedef.unflatten([
        jax.random.normal(k, s.shape, jnp.float32)
        if jnp.issubdtype(s.dtype, jnp.floating) else jnp.zeros(s.shape, s.dtype)
        for k, s in zip(keys, leaves)])
    pos = jnp.asarray(positions, jnp.int32)
    token = jax.random.randint(jax.random.PRNGKey(5), (B, 1), 0, cfg.vocab_size)
    state = model_lib.ServeState(caches=caches, pos=pos, odl=None)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t, c, q: _onehot_decode(p, t, c, q, cfg))(
            params, token, caches, pos)
        got = jax.jit(lambda p, s, t: model_lib.decode_step(p, s, t, cfg))(
            params, state, token)

    want_logits, want_feats, want_caches = want
    got_logits, got_feats, got_state = got
    np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))
    np.testing.assert_array_equal(np.asarray(got_feats), np.asarray(want_feats))
    assert jax.tree.structure(got_state.caches) == jax.tree.structure(want_caches)
    for g, w in zip(jax.tree.leaves(got_state.caches), jax.tree.leaves(want_caches)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # Each row's new entry sits at its own slot: the written slots changed.
    slot = pos % cfg.sliding_window if cfg.sliding_window else pos
    name = "ckv" if cfg.use_mla else "k"
    written = np.asarray(got_state.caches["layers"][name])[:, np.arange(B), np.asarray(slot)]
    before = np.asarray(caches["layers"][name])[:, np.arange(B), np.asarray(slot)]
    assert (written != before).all(axis=tuple(range(2, written.ndim))).all()
