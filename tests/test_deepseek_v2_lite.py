"""DeepSeek-V2-Lite's block against its plain reference on the CPU, at smoke
size on seeded random weights: prefill then the absorbed latent decode,
YaRN, the expert share, MLA without q-LoRA and the dense-then-MoE stack.

The reference is the benchmark's (``bench/configs/deepseek-v2-lite.ref.py``),
which imports nothing of the program.  Where a comparison must be tight,
the program computes in float32 at highest precision (``f32_program``):
its bfloat16 rounding, not the mathematics, is then all that could differ.
"""

import dataclasses
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention, layers, moe, transformer
from repro.models import model as model_lib
from repro.runtime import telemetry

REF_PATH = (pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"
            / "deepseek-v2-lite.ref.py")


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("deepseek_v2_lite_ref", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def f32_program(monkeypatch):
    monkeypatch.setattr(layers, "COMPUTE_DTYPE", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


def _cfg(**kw):
    return configs.get_config("deepseek-v2-lite", "smoke").replace(**kw)


def _backbone(cfg):
    return dict(dataclasses.asdict(cfg), norm_eps=1e-6)


def _params(cfg, seed=1):
    return layers.init_params(model_lib.build_schema(cfg), jax.random.PRNGKey(seed))


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def _prefill_decode(params, tokens, cfg, n_prompt):
    """Features of every position (prefill, then one decode step a token)
    and the logits of the decoded positions."""
    hidden, st = model_lib.prefill(params, tokens[:, :n_prompt], cfg,
                                   max_len=tokens.shape[1])
    step = jax.jit(lambda p, s, t: model_lib.decode_step(p, s, t, cfg))
    feats, logits = [np.asarray(hidden, np.float32)], []
    for t in range(n_prompt, tokens.shape[1]):
        lg, f, st = step(params, st, tokens[:, t: t + 1])
        feats.append(np.asarray(f)[:, None])
        logits.append(np.asarray(lg, np.float32))
    return np.concatenate(feats, 1), np.stack(logits, 1), st


@pytest.mark.parametrize("share", [(8, 0), (4, 2)], ids=["all_experts", "held_2_to_5"])
def test_prefill_then_absorbed_decode_matches_reference(ref, f32_program, share):
    """Prefill 16 tokens, decode 8 through the latent cache with the
    absorbed projections: features and logits equal the reference's full
    forward pass to float32 rounding."""
    cfg = _cfg(experts_held=share[0], expert_first=share[1])
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, cfg.vocab_size)
    feats, logits, st = _prefill_decode(params, tokens, cfg, 16)
    want = np.asarray(ref.features(params, tokens, _backbone(cfg)))
    want_logits = np.asarray(ref.logits(params, want[:, 16:]))
    assert _gap(feats, want).max() < 1e-4
    assert _gap(logits, want_logits).max() < 1e-4
    load = model_lib.expert_load(st)
    assert load["tokens"].shape == (cfg.n_layers - cfg.first_k_dense, share[0])
    assert (load["rows"] == 8 * 2).all()  # 8 steps of 2 tokens, every held expert


def test_bf16_decode_stays_near_reference_and_the_control_does_not(ref):
    """At the configuration's bfloat16 compute, the program's median gap to
    the reference is a bfloat16 rounding's, and the float8 control's is
    several times wider."""
    cfg = _cfg(experts_held=4, expert_first=4)
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, cfg.vocab_size)
    feats, _, _ = _prefill_decode(params, tokens, cfg, 16)
    want = np.asarray(ref.features(params, tokens, _backbone(cfg)))
    ctrl = np.asarray(ref.features(params, tokens, _backbone(cfg), control=True))
    assert np.median(_gap(feats, want)) < 0.03
    assert np.median(_gap(ctrl, want)) > 3 * np.median(_gap(feats, want))


def _deepseek_yarn(dim, base, factor, original, beta_fast, beta_slow, mscale, mscale_all_dim):
    """DeepSeek-V2's modeling code, transcribed to NumPy: the YaRN inverse
    frequencies, the cos/sin factor and the softmax scale's factor."""

    def find_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    hi = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (hi - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    cos_sin = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    softmax = get_mscale(factor, mscale_all_dim) ** 2 if mscale_all_dim else 1.0
    return inv_freq, cos_sin, softmax


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "deepseek-v2-236b"])
def test_yarn_frequencies_and_mscale_match_deepseeks_formulas(arch):
    cfg = configs.get_config(arch, "full")
    freqs, scale, softmax = attention.mla_rope(cfg)
    want, want_scale, want_softmax = _deepseek_yarn(
        64, 10000.0, 40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    assert scale == pytest.approx(want_scale) == 1.0
    assert softmax == pytest.approx(want_softmax / math.sqrt(192))
    assert softmax * math.sqrt(192) == pytest.approx((0.1 * 0.707 * math.log(40) + 1) ** 2)
    # The ramp is really there: the fastest dims keep the plain frequency,
    # the slowest are divided by the factor.
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    assert freqs[0] == pytest.approx(plain[0]) and freqs[-1] == pytest.approx(plain[-1] / 40)


def test_expert_shares_add_up_to_the_uncut_layer(f32_program):
    """Eight chips' shares of one expert layer, one expert each, plus the
    shared experts counted once, add up to the layer that holds them all."""
    cfg = _cfg()
    lp = jax.tree.map(lambda a: a[0], _params(cfg)["layers"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, cfg.d_model), jnp.float32)
    whole, _, _ = moe.share_block(x, lp, cfg)
    routed_only = {k: v for k, v in lp.items() if k != "shared"}
    parts = []
    for e in range(cfg.n_experts):
        share = cfg.replace(experts_held=1, expert_first=e)
        p = dict(routed_only, **{k: lp[k][e: e + 1] for k in ("wg", "wu", "wd")})
        parts.append(moe.share_block(x, p, share)[0])
    hs = moe._shared_experts(x.reshape(-1, cfg.d_model), lp).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(sum(parts) + hs), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_a_routing_to_one_held_expert_drops_no_token(f32_program):
    """Every token routed to held expert 0 (a router that prefers it by
    far): each token gets that expert's full output times its gate, and
    the counter sees every token-choice."""
    cfg = _cfg(experts_held=4, expert_first=0, top_k=1, n_shared_experts=0)
    lp = jax.tree.map(lambda a: a[0], _params(cfg)["layers"])["moe"]
    lp = dict(lp, router=lp["router"].at[:, 0].add(1e3))
    lp.pop("shared", None)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (4, 64, cfg.d_model))) + 0.1
    y, idx, counts = moe.share_block(x, lp, cfg)
    assert (np.asarray(idx) == 0).all()
    assert np.asarray(counts).tolist() == [256, 0, 0, 0]
    xt = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xt @ lp["router"], -1)
    want = (jax.nn.silu(xt @ lp["wg"][0]) * (xt @ lp["wu"][0])) @ lp["wd"][0]
    want = np.asarray(want * probs[:, :1])
    # float32 sums in another order, relative to the output's scale
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model), want, rtol=1e-4,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("q_lora", [None, 32], ids=["no_q_lora", "q_lora"])
def test_absorbed_decode_equals_the_expanded_latent_attention(f32_program, q_lora):
    """MLA with queries straight from d_model (DeepSeek-V2-Lite) and with
    q-LoRA (DeepSeek-V2): decoding over the latent cache with absorbed
    projections gives what the published expanded form gives."""
    cfg = _cfg(q_lora_rank=q_lora)
    attn = model_lib.build_schema(cfg)["layers"]["attn"]
    assert ("wq" in attn) == (q_lora is None) and ("wq_b" in attn) == (q_lora is not None)
    p = jax.tree.map(lambda a: a[0], _params(cfg)["layers"])["attn"]
    b, s = 2, 10
    x = jax.random.normal(jax.random.PRNGKey(6), (b, s, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    want = attention.mla_attention(x, p, cfg, pos)
    cache = jax.tree.map(lambda a: a[None], attention.mla_init_cache(cfg, b, s, jnp.float32))
    got = []
    for t in range(s):
        o, cache = attention.mla_decode(x[:, t: t + 1], p, cfg, cache, 0, jnp.full((b,), t))
        got.append(o)
    np.testing.assert_allclose(np.concatenate(got, 1), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_dense_then_moe_stack_matches_reference(ref, f32_program):
    """One dense layer of width d_ff, then the MoE layers: the schema holds
    the two stacks, and the full forward equals the reference's."""
    cfg = _cfg(experts_held=4, expert_first=4)
    schema = model_lib.build_schema(cfg)
    dense, routed = schema["dense_layers"], schema["layers"]
    assert dense["mlp"]["wg"].shape == (1, cfg.d_model, cfg.d_ff) and "moe" not in dense
    assert routed["moe"]["wg"].shape == (cfg.n_layers - 1, 4, cfg.d_model, cfg.d_ff_expert)
    assert routed["moe"]["router"].shape == (cfg.n_layers - 1, cfg.d_model, cfg.n_experts)
    params = _params(cfg, seed=7)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 16), 0, cfg.vocab_size)
    hidden, _ = transformer.lm_hidden(params, tokens, cfg, remat=False)
    want, routes = ref.forward(params, tokens, _backbone(cfg))
    assert routes.shape == (cfg.n_layers - 1, 2, 16, cfg.top_k)
    assert _gap(hidden, want).max() < 1e-4


def test_serve_exports_the_expert_counters():
    """``launch/serve.py --arch deepseek-v2-lite`` serves the smoke model
    and exports, at the end of its decode, each MoE layer's token-choices
    and rows per held expert as registry counters."""
    from repro.launch import serve

    try:
        serve.serve("deepseek-v2-lite", batch=2, gen_tokens=4, prompt_len=8, max_len=16)
        counters = telemetry.get().registry.snapshot()["counters"]
    finally:
        telemetry.disable()
    cfg = _cfg()
    n_moe = cfg.n_layers - cfg.first_k_dense
    tokens = {(r["labels"]["layer"], r["labels"]["expert"]): r["value"]
              for r in counters["odl_backbone_expert_tokens"]}
    rows = [r["value"] for r in counters["odl_backbone_expert_rows"]]
    assert len(tokens) == len(rows) == n_moe * cfg.experts_held
    assert rows == [4 * 2] * len(rows)  # 4 steps of 2 streams, every held expert
    for layer in range(n_moe):  # every token-choice lands on some expert: all held
        assert sum(v for (l, _), v in tokens.items() if l == str(layer)) == 4 * 2 * cfg.top_k
