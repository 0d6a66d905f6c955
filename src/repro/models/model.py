"""Top-level model API: build / train_step / prefill / serve_step.

This is where the paper's technique becomes a first-class feature of the
framework (DESIGN.md §3): every backbone carries an ODL head —

  train_step: backbone CE loss -> grads -> AdamW, PLUS the OS-ELM head
    trained by rank-k RLS on pooled features with P1P2 pruning deciding
    which rows may skip the teacher (label) entirely.  The pruning mask
    feeds the masked RLS update, so a skipped sample costs zero label
    traffic — the paper's comm saving, fused into the step.  It is still
    computed under the mask; on the serving fleet path a skipped row costs
    no P traffic when its reply's learn compacts to the answered rows
    (``engine.fleet.learn_rows``).

  serve_step: one decode token, plus the head's prediction and the
    P1P2/auto-theta gate per stream.  The gate's output (a ``GateOutput``
    with the ``queried`` mask and the plan-time decision context) is the
    cascade signal: which streams must consult the teacher.  Label
    application is asynchronous (BLE round-trip in the paper; a separate
    `serve_apply_labels` call here, fed the same GateOutput so delayed
    answers are judged at query-time context).  ``decode_step`` is the
    gate-free variant for the multiplexed serving path.

All functions are pure and pjit-friendly; `input_specs` yields weak-typed
ShapeDtypeStructs for the multi-pod dry-run.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core import drift as drift_mod
from repro.core import oselm, pruning
from repro.distributed import sharding
from repro.engine import fleet as engine
from repro.models import encdec, layers, transformer
from repro.models.layers import softmax_cross_entropy
from repro.optim import adam


# ---------------------------------------------------------------------------
# Schema / state
# ---------------------------------------------------------------------------


def build_schema(cfg: ModelConfig) -> dict:
    if cfg.enc_dec:
        return encdec.encdec_schema(cfg)
    return transformer.lm_schema(cfg)


def elm_config(cfg: ModelConfig) -> oselm.OSELMConfig:
    return oselm.OSELMConfig(
        n_in=cfg.d_model,
        n_hidden=cfg.odl.n_hidden,
        n_out=cfg.odl.n_out,
        variant=cfg.odl.variant,
        seed=cfg.odl.seed,
        ridge=cfg.odl.ridge,
        use_kernel=cfg.odl.use_kernel,
    )


def core_config(cfg: ModelConfig) -> engine.EngineConfig:
    """Fleet-engine config for this backbone's per-stream ODL heads."""
    ecfg = elm_config(cfg)
    return engine.EngineConfig(
        elm=ecfg,
        prune=pruning.PruneConfig.for_hidden(ecfg.n_hidden),
        drift=drift_mod.DriftConfig(),
    )


class ODLState(NamedTuple):
    elm: oselm.OSELMState
    prune: pruning.PruneState


class TrainState(NamedTuple):
    params: dict
    opt: adam.AdamState
    odl: ODLState


def init_odl_state(cfg: ModelConfig) -> ODLState:
    return ODLState(elm=oselm.init_state(elm_config(cfg)), prune=pruning.init_state())


def init_train_state(cfg: ModelConfig, key, tcfg: TrainConfig = TrainConfig()) -> TrainState:
    params = layers.init_params(build_schema(cfg), key, dtype=jnp.dtype(tcfg.param_dtype))
    return TrainState(params=params, opt=adam.init(params), odl=init_odl_state(cfg))


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def _forward_loss(params, batch, cfg: ModelConfig, remat: bool):
    if cfg.enc_dec:
        enc = encdec.encode(params, batch["frames"], cfg, remat=remat)
        hidden = encdec.decode_train(params, batch["tokens"], enc, cfg, remat=remat)
        logits = encdec.logits(params, hidden)
        aux = 0.0
    else:
        hidden, aux = transformer.lm_hidden(params, batch["tokens"], cfg, remat=remat)
        logits = transformer.lm_logits(params, hidden, cfg)
    ce = softmax_cross_entropy(logits, batch["labels"])
    loss = ce + 0.01 * aux
    feats = jnp.mean(hidden.astype(jnp.float32), axis=1)  # (B, d) pooled
    return loss, (ce, feats)


# ---------------------------------------------------------------------------
# ODL head update (the paper's technique, fused into the train step)
# ---------------------------------------------------------------------------


def odl_update(
    odl: ODLState,
    feats: jnp.ndarray,  # (B, d_model) f32
    odl_labels: jnp.ndarray,  # (B,) int32 teacher labels
    cfg: ModelConfig,
    drift_active: Optional[jnp.ndarray] = None,
) -> tuple[ODLState, dict]:
    ecfg = elm_config(cfg)
    pcfg = pruning.PruneConfig.for_hidden(ecfg.n_hidden)
    if drift_active is None:
        drift_active = jnp.zeros((), jnp.bool_)

    preds, outs = oselm.predict(odl.elm, feats, ecfg)  # (B,), (B, m)
    conf = pruning.confidence(outs)
    theta = pruning.theta_of(odl.prune, pcfg)
    warm = odl.elm.count >= pcfg.min_trained
    prune_mask = warm & jnp.logical_not(drift_active) & (conf > theta)
    queried = jnp.logical_not(prune_mask)  # (B,)

    y = jax.nn.one_hot(odl_labels, ecfg.n_out)
    new_elm = oselm.sequential_update(
        odl.elm, feats, y, ecfg, mask=queried.astype(jnp.float32)
    )
    agree = preds == odl_labels
    new_prune = pruning.scan_update(odl.prune, queried, agree, conf, pcfg)

    metrics = {
        "odl_query_frac": jnp.mean(queried.astype(jnp.float32)),
        "odl_acc": jnp.mean(agree.astype(jnp.float32)),
        "odl_theta": theta,
    }
    return ODLState(elm=new_elm, prune=new_prune), metrics


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def train_step(
    state: TrainState,
    batch: dict,
    cfg: ModelConfig,
    tcfg: TrainConfig = TrainConfig(),
) -> tuple[TrainState, dict]:
    """One optimizer step with optional gradient accumulation.

    batch: tokens/labels (B, S) [+ frames for enc-dec] + odl_labels (B,).
    """
    grad_fn = jax.value_and_grad(_forward_loss, has_aux=True)

    if tcfg.microbatches > 1:
        mb = tcfg.microbatches

        def split(x):
            return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])

        mbatch = {k: split(v) for k, v in batch.items()}

        def body(carry, mb_batch):
            gsum, lsum = carry
            (loss, (ce, feats)), grads = grad_fn(state.params, mb_batch, cfg, tcfg.remat)
            gsum = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / mb, gsum, grads
            )
            return (gsum, lsum + loss / mb), feats

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        (grads, loss), feats_mb = jax.lax.scan(body, (zeros, 0.0), mbatch)
        feats = feats_mb.reshape((-1, feats_mb.shape[-1]))
    else:
        (loss, (ce, feats)), grads = grad_fn(state.params, batch, cfg, tcfg.remat)

    new_params, new_opt, gnorm = adam.update(grads, state.opt, state.params, tcfg)
    new_odl, odl_metrics = odl_update(state.odl, feats, batch["odl_labels"], cfg)

    metrics = {"loss": loss, "grad_norm": gnorm, **odl_metrics}
    return TrainState(params=new_params, opt=new_opt, odl=new_odl), metrics


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


class ServeState(NamedTuple):
    caches: dict
    pos: jnp.ndarray  # (B,) int32
    odl: engine.EngineState  # fleet engine: elm/prune/drift/meter, leading B


def init_serve_state(cfg: ModelConfig, batch: int, max_len: int) -> ServeState:
    return ServeState(
        caches=transformer.init_caches(cfg, batch, max_len),
        pos=jnp.zeros((batch,), jnp.int32),
        odl=engine.init_fleet(core_config(cfg), batch),
    )


def serve_step(
    params: dict,
    state: ServeState,
    token: jnp.ndarray,  # (B, 1) int32
    cfg: ModelConfig,
) -> tuple[jnp.ndarray, ServeState, engine.GateOutput]:
    """One decode token + the fleet engine's predict/gate on stream features.

    Returns (logits (B, V), state', odl_out) where odl_out is the engine's
    ``GateOutput``: the per-stream prediction, confidence, ``queried`` mask
    (True -> this stream must consult the teacher), and the plan-time
    decision context (h/pred/confidence/theta) that ``serve_apply_labels``
    judges the — possibly delayed — teacher answer against.  The engine
    also runs the per-stream drift detector (a drifting stream is forced to
    query — pruning condition 2) and meters query traffic.
    """
    hidden, new_caches = transformer.lm_decode_hidden(
        params, token, state.caches, state.pos, cfg
    )
    logits = transformer.lm_logits(params, hidden, cfg)[:, 0]

    feats = hidden[:, 0].astype(jnp.float32)  # (B, d)
    new_odl, odl_out = engine.gate(state.odl, feats, core_config(cfg))
    new_state = ServeState(caches=new_caches, pos=state.pos + 1, odl=new_odl)
    return logits, new_state, odl_out


def decode_step(
    params: dict,
    state: ServeState,
    token: jnp.ndarray,  # (B, 1) int32
    cfg: ModelConfig,
) -> tuple[jnp.ndarray, jnp.ndarray, ServeState]:
    """One decode token, *without* the ODL gate: (logits, feats, state').

    The multiplexed serving path (``launch/serve.py`` + ``engine.multiplex``)
    runs the backbone once and fans the per-tick features out to N tenant
    fleets, each with its own engine state — so the gate/learn halves live
    in the tenants' ``StreamSession``s, not here.  ``state.odl`` passes
    through untouched.
    """
    hidden, new_caches = transformer.lm_decode_hidden(
        params, token, state.caches, state.pos, cfg
    )
    logits = transformer.lm_logits(params, hidden, cfg)[:, 0]
    feats = hidden[:, 0].astype(jnp.float32)  # (B, d)
    return logits, feats, state._replace(caches=new_caches, pos=state.pos + 1)


def expert_load(state: ServeState) -> Optional[dict]:
    """The expert share's counters from a decode state, on the host:
    ``tokens`` and ``rows`` (MoE layers, experts_held) int64, the
    token-choices routed to each held expert and the rows it computed,
    padding included, over the decode steps since the last prefill.  None
    for a backbone without an expert share."""
    layers_c = state.caches.get("layers", {})
    if "expert_tokens" not in layers_c:
        return None
    return {"tokens": np.asarray(layers_c["expert_tokens"], np.int64),
            "rows": np.asarray(layers_c["expert_rows"], np.int64)}


def sync_expert_counters(registry, load: dict, cfg: ModelConfig, **labels) -> None:
    """Export ``expert_load`` as registry counters, one series per MoE
    layer and held expert (labelled with its id among all ``n_experts``):
    ``odl_backbone_expert_tokens`` and ``odl_backbone_expert_rows``
    (absolute writes; the decode state is the source of truth)."""
    for name in ("tokens", "rows"):
        for (layer, held), v in np.ndenumerate(load[name]):
            registry.set_counter(f"odl_backbone_expert_{name}", float(v), layer=str(layer),
                                 expert=str(cfg.expert_first + held), **labels)


def serve_apply_labels(
    state: ServeState,
    ctx: engine.GateOutput,  # gate output captured at query time
    labels: jnp.ndarray,  # (B,) teacher labels (valid where mask)
    mask: jnp.ndarray,  # (B,) bool — streams whose teacher answered
    cfg: ModelConfig,
) -> ServeState:
    """Asynchronous label acquisition: RLS-train the per-stream heads.

    ``ctx`` is the ``GateOutput`` returned by the ``serve_step`` that issued
    the query, so a delayed reply trains on the query-time activations and
    is judged against the query-time prediction/threshold (never against
    weights that changed while the answer was in flight).
    """
    new_odl = engine.apply_labels(state.odl, ctx, labels, mask, core_config(cfg))
    return state._replace(odl=new_odl)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(params: dict, tokens: jnp.ndarray, cfg: ModelConfig, max_len: Optional[int] = None):
    """Forward the prompt once and build decode caches (single-pass).

    Returns (final_hidden, ServeState ready for serve_step).
    """
    b = tokens.shape[0]
    hidden, caches, pos = transformer.lm_prefill(params, tokens, cfg, max_len)
    state = ServeState(
        caches=caches,
        pos=pos,
        odl=engine.init_fleet(core_config(cfg), b),
    )
    return hidden, state


def encdec_prefill(params: dict, frames: jnp.ndarray, cfg: ModelConfig, max_len: int):
    enc = encdec.encode(params, frames, cfg)
    return enc, encdec.init_caches(params, enc, cfg, max_len)


# ---------------------------------------------------------------------------
# Abstract state builders (dry-run: ShapeDtypeStruct + NamedSharding only)
# ---------------------------------------------------------------------------


def _abstract_like(tree, axes_tree):
    """eval_shape pytree + logical-axes pytree -> SDS with NamedShardings."""

    def one(sds, axes):
        ns = sharding.named_sharding(*axes, shape=sds.shape)
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=ns)

    return jax.tree.map(one, tree, axes_tree)


def _axes_like(tree, fn):
    """Build an axes pytree with the same structure as `tree` via fn(path, leaf)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    axes = [fn(tuple(str(k) for k in path), leaf) for path, leaf in flat]
    return jax.tree.unflatten(treedef, axes)


def cache_axes(path: tuple, leaf) -> tuple:
    """Logical axes for one decode-cache leaf, keyed by leaf name + rank.

    KV/latent caches shard their sequence dim over 'model' (flash-decoding
    style length sharding — the natural decode TP axis) and batch over
    ('pod','data'); recurrent states shard heads/width over 'model'.
    """
    name = path[-1].strip("'[]")
    nd = leaf.ndim
    lead: tuple = ("layers",)  # stacked layer/group dim
    if name in ("k", "v"):  # (L, B, S, KV * hd)
        return lead + ("batch", "seq_kv", "kv_heads")[: nd - 1]
    if name in ("ckv", "k_rope"):  # (L, B, S, R)
        return lead + ("batch", "seq_kv", None)[: nd - 1]
    if name == "state":  # (L, B, H, P, N)
        return lead + ("batch", "ssm_heads", None, None)[: nd - 1]
    if name == "conv":  # (L, B, W-1, C)
        return lead + ("batch", None, "mlp")[: nd - 1]
    if name == "h":  # (L, B, W)
        return lead + ("batch", "mlp")[: nd - 1]
    if name in ("expert_tokens", "expert_rows"):  # (L, E_held)
        return lead + (None,)
    return lead + ("batch",) + (None,) * (nd - 2)


def abstract_serve_state(cfg: ModelConfig, batch: int, max_len: int) -> ServeState:
    """ServeState of ShapeDtypeStructs with shardings (no allocation)."""
    shapes = jax.eval_shape(lambda: init_serve_state(cfg, batch, max_len))
    caches = _abstract_like(shapes.caches, _axes_like(shapes.caches, cache_axes))
    pos = _sds((batch,), jnp.int32, "stream")

    def odl_axes(path, leaf):
        return ("stream",) + (None,) * (leaf.ndim - 1)

    odl = _abstract_like(shapes.odl, _axes_like(shapes.odl, odl_axes))
    return ServeState(caches=caches, pos=pos, odl=odl)


def abstract_train_state(cfg: ModelConfig, tcfg: TrainConfig = TrainConfig()) -> TrainState:
    """TrainState of ShapeDtypeStructs: params TP+FSDP-sharded, moments ZeRO."""
    schema = build_schema(cfg)
    params = layers.abstract_params(schema, dtype=jnp.dtype(tcfg.param_dtype))

    mesh = sharding.mesh_or_none()

    def moment_of(sds):
        """ZeRO-1: moments get 'data' (and 'pod') on a free dim — unless the
        param is already FSDP-sharded over data (then moments match it)."""
        spec = sds.sharding.spec if sds.sharding is not None else None
        if mesh is None or spec is None:
            return jax.ShapeDtypeStruct(sds.shape, jnp.float32)
        from jax.sharding import NamedSharding, PartitionSpec as P

        msh = dict(zip(mesh.axis_names, mesh.devices.shape))
        entries = list(spec) + [None] * (len(sds.shape) - len(spec))
        flat = []
        for e in entries:
            flat.extend(e if isinstance(e, tuple) else (e,))
        if "data" not in flat:
            for axes_try in ((("pod", "data") if "pod" in msh else None), "data"):
                if axes_try is None:
                    continue
                size = (
                    msh["pod"] * msh["data"] if isinstance(axes_try, tuple) else msh["data"]
                )
                placed = False
                for i, e in enumerate(entries):
                    if e is None and sds.shape[i] % size == 0 and sds.shape[i] >= size:
                        entries[i] = axes_try
                        placed = True
                        break
                if placed:
                    break
        return jax.ShapeDtypeStruct(
            sds.shape, jnp.float32, sharding=NamedSharding(mesh, P(*entries))
        )

    m = jax.tree.map(moment_of, params)
    v = jax.tree.map(moment_of, params)
    opt = adam.AdamState(step=jax.ShapeDtypeStruct((), jnp.int32), m=m, v=v)

    ecfg = elm_config(cfg)
    odl = ODLState(
        elm=oselm.OSELMState(
            beta=jax.ShapeDtypeStruct((ecfg.n_hidden, ecfg.n_out), jnp.float32),
            P=jax.ShapeDtypeStruct((ecfg.n_hidden, ecfg.n_hidden), jnp.float32),
            count=jax.ShapeDtypeStruct((), jnp.int32),
        ),
        prune=jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.eval_shape(pruning.init_state),
        ),
    )
    return TrainState(params=params, opt=opt, odl=odl)


# ---------------------------------------------------------------------------
# Dry-run input specs (weak-type-correct, shardable, no allocation)
# ---------------------------------------------------------------------------


def _sds(shape_tuple, dtype, *names):
    ns = sharding.named_sharding(*names, shape=shape_tuple)
    return jax.ShapeDtypeStruct(shape_tuple, dtype, sharding=ns)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for every model input of this (arch, shape) cell."""
    b, s = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        specs = {
            "tokens": _sds((b, s), jnp.int32, "batch", "seq"),
            "labels": _sds((b, s), jnp.int32, "batch", "seq"),
            "odl_labels": _sds((b,), jnp.int32, "batch"),
        }
        if cfg.enc_dec:
            specs["frames"] = _sds((b, s, cfg.d_model), jnp.float32, "batch", "seq", "embed")
        return specs
    if shape.kind == "prefill":
        if cfg.enc_dec:
            return {"frames": _sds((b, s, cfg.d_model), jnp.float32, "batch", "seq", "embed")}
        return {"tokens": _sds((b, s), jnp.int32, "batch", "seq")}
    # decode: one new token against an S-long cache
    return {"token": _sds((b, 1), jnp.int32, "batch", None)}
