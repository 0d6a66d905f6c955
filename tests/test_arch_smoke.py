"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
asserting output shapes + no NaNs (assignment requirement), plus a decode
step against the prefill cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import TrainConfig
from repro.models import model as model_lib

B, S = 2, 32


def _batch(cfg, key=0):
    k = jax.random.PRNGKey(key)
    batch = {
        "tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
        "odl_labels": jax.random.randint(k, (B,), 0, cfg.odl.n_out),
    }
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(k, (B, S, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = configs.get_config(arch, "smoke")
    state = model_lib.init_train_state(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    state2, metrics = jax.jit(
        lambda st, b: model_lib.train_step(st, b, cfg, TrainConfig(microbatches=1))
    )(state, batch)

    loss = float(metrics["loss"])
    assert np.isfinite(loss), f"{arch}: loss is not finite"
    assert loss > 0
    # Params must have moved and stayed finite.
    moved = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(a - b_))), state.params, state2.params)
    assert max(jax.tree.leaves(moved)) > 0
    for leaf in jax.tree.leaves(state2.params):
        assert bool(jnp.all(jnp.isfinite(leaf))), f"{arch}: non-finite params"
    # The ODL head trained (paper's technique is in the step).
    assert int(state2.odl.elm.count) > 0


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_train_step_with_microbatches(arch):
    cfg = configs.get_config(arch, "smoke")
    state = model_lib.init_train_state(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    _, m1 = jax.jit(
        lambda st, b: model_lib.train_step(st, b, cfg, TrainConfig(microbatches=2))
    )(state, batch)
    assert np.isfinite(float(m1["loss"]))


@pytest.mark.parametrize(
    "arch",
    [a for a in configs.ARCH_IDS if a != "whisper-small"],
)
def test_prefill_then_decode_smoke(arch):
    """Prefill a prompt, decode one token; logits finite and shaped (B, V)."""
    cfg = configs.get_config(arch, "smoke")
    params = model_lib.layers.init_params(model_lib.build_schema(cfg), jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)

    hidden, serve_state = jax.jit(
        lambda p, t: model_lib.prefill(p, t, cfg, max_len=S + 8)
    )(params, tokens)
    assert hidden.shape == (B, S, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(hidden.astype(jnp.float32))))

    nxt = jnp.full((B, 1), 3, jnp.int32)
    logits, serve_state2, odl_out = jax.jit(
        lambda p, st, t: model_lib.serve_step(p, st, t, cfg)
    )(params, serve_state, nxt)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert odl_out.queried.shape == (B,)
    assert int(serve_state2.pos[0]) == S + 1


def test_whisper_prefill_decode():
    cfg = configs.get_config("whisper-small", "smoke")
    params = model_lib.layers.init_params(model_lib.build_schema(cfg), jax.random.PRNGKey(1))
    frames = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model))
    enc, caches = jax.jit(lambda p, f: model_lib.encdec_prefill(p, f, cfg, max_len=16))(
        params, frames
    )
    assert enc.shape == (B, S, cfg.d_model)
    from repro.models import encdec

    tok = jnp.full((B, 1), 5, jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    h, caches2 = jax.jit(lambda p, t, c, q: encdec.decode_step(p, t, c, q, cfg))(
        params, tok, caches, pos
    )
    logits = encdec.logits(params, h)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_decode_matches_prefill_dense():
    """Decode of token t must equal the prefill hidden at position t (GQA)."""
    cfg = configs.get_config("qwen3-4b", "smoke")
    params = model_lib.layers.init_params(model_lib.build_schema(cfg), jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)

    # Full forward over S tokens.
    from repro.models import transformer

    hidden_all, _ = transformer.lm_hidden(params, tokens, cfg, remat=False)

    # Prefill S-1 tokens, then decode token S-1.
    _, st = model_lib.prefill(params, tokens[:, : S - 1], cfg, max_len=S)
    logits, st2, _ = model_lib.serve_step(params, st, tokens[:, S - 1 :], cfg)
    full_logits = transformer.lm_logits(params, hidden_all, cfg)[:, -1]
    np.testing.assert_allclose(
        np.asarray(logits, np.float32),
        np.asarray(full_logits, np.float32),
        atol=0.15,  # bf16 accumulation over different contraction orders
        rtol=0.05,
    )


def test_long_500k_skip_policy():
    """DESIGN.md §4: exactly h2o-danube, mamba2, recurrentgemma run long_500k."""
    runnable = {
        a: [c for c in configs.cells(a) if c[0].name == "long_500k"][0][1]
        for a in configs.ARCH_IDS
    }
    assert runnable == {
        "deepseek-moe-16b": False,
        "deepseek-v2-236b": False,
        "deepseek-v2-lite": False,
        "h2o-danube-1.8b": True,
        "deepseek-coder-33b": False,
        "mistral-nemo-12b": False,
        "qwen3-4b": False,
        "mamba2-780m": True,
        "recurrentgemma-9b": True,
        "chameleon-34b": False,
        "whisper-small": False,
    }
