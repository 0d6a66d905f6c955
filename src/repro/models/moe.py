"""Fine-grained MoE (DeepSeekMoE): shared experts + routed top-k experts.

Dispatch is scatter-based (capacity-bounded buffers), the standard pure-JAX
formulation whose FLOPs match the *active* parameter count (capacity slots =
top_k * tokens * capacity_factor), so roofline numbers reflect real MoE
compute rather than a dense-all-experts surrogate.

Sharding: expert weight tensors and the (E, C, d) dispatch buffers carry the
"experts" logical axis -> EP over the "model" mesh axis.  Token buffers stay
batch-sharded; XLA inserts the dispatch all-to-alls at the EP boundary.

Router: softmax over all experts, top-k selection, renormalize among the
selected (DeepSeek's gating), plus the standard load-balancing auxiliary
loss (Switch/GShard form).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding
from repro.models.layers import Leaf, cast, token_chunked


def moe_schema(cfg: ModelConfig) -> dict:
    """The router over all ``n_experts``; the routed experts' weights for
    the experts this layer holds (``experts_held``, else all)."""
    d, fe = cfg.d_model, cfg.d_ff_expert
    e = cfg.experts_held or cfg.n_experts
    s = {
        "router": Leaf((d, cfg.n_experts), ("embed", "experts"), scale=0.02),
        "wg": Leaf((e, d, fe), ("experts", "embed", "mlp")),
        "wu": Leaf((e, d, fe), ("experts", "embed", "mlp")),
        "wd": Leaf((e, fe, d), ("experts", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        fs = cfg.d_ff_expert * cfg.n_shared_experts
        s["shared"] = {
            "wg": Leaf((d, fs), ("embed", "mlp")),
            "wu": Leaf((d, fs), ("embed", "mlp")),
            "wd": Leaf((fs, d), ("mlp", "embed")),
        }
    return s


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(cfg.top_k * n_tokens * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for lane alignment


def moe_block(x: jnp.ndarray, p: dict, cfg: ModelConfig):
    """x: (B, S, d) -> (y, aux_loss).  The expert share when the layer is
    told which experts it holds (``experts_held``; no aux loss), else
    dispatches on cfg.moe_impl."""
    if cfg.experts_held:
        y, _, _ = share_block(x, p, cfg)
        return y, 0.0
    if cfg.moe_impl == "ep":
        mesh = sharding.mesh_or_none()
        if mesh is not None and "model" in mesh.axis_names:
            return moe_block_ep(x, p, cfg, mesh)
    return _moe_block_dense(x, p, cfg)


def _moe_block_dense(x: jnp.ndarray, p: dict, cfg: ModelConfig):
    """Scatter-dispatch top-k MoE under plain pjit (the baseline)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(cfg, t)
    xt = x.reshape(t, d)

    # --- router ---
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, -1)
    gate_k, idx_k = jax.lax.top_k(probs, k)  # (T, k)
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

    # Load-balancing aux loss (mean prob * mean assignment per expert).
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx_k, e, dtype=jnp.float32), axis=1), axis=0
    )
    aux = e * jnp.sum(me * ce)

    # --- dispatch: position of each (token, choice) within its expert ---
    flat_e = idx_k.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot  # exclusive cumsum
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]  # (T*k,)
    keep = pos < cap

    buf = jnp.zeros((e, cap, d), x.dtype)
    tok_rep = jnp.repeat(jnp.arange(t), k)
    buf = buf.at[flat_e, jnp.minimum(pos, cap - 1)].add(
        jnp.where(keep[:, None], xt[tok_rep], 0).astype(x.dtype)
    )
    buf = sharding.constrain(buf, "experts", "expert_cap", "embed")

    # --- expert computation: batched GEMMs over the expert axis ---
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, cast(p["wg"]))) * jnp.einsum(
        "ecd,edf->ecf", buf, cast(p["wu"])
    )
    h = sharding.constrain(h, "experts", "expert_cap", "mlp")
    out_e = jnp.einsum("ecf,efd->ecd", h, cast(p["wd"]))
    out_e = sharding.constrain(out_e, "experts", "expert_cap", "embed")

    # --- combine: gather each (token, choice) slot, weight by gate ---
    gathered = out_e[flat_e, jnp.minimum(pos, cap - 1)]  # (T*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    w = gate_k.reshape(-1)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok_rep].add(gathered * w)

    # --- shared experts (always-on dense path) ---
    if "shared" in p:
        y = y + _shared_experts(xt, p)

    return y.reshape(b, s, d), aux


def _shared_experts(xt, p):
    sh = p["shared"]
    hs = jax.nn.silu(xt @ cast(sh["wg"])) * (xt @ cast(sh["wu"]))
    return hs @ cast(sh["wd"])


# ---------------------------------------------------------------------------
# The expert share: one chip's routed experts, no capacity, no drop
# ---------------------------------------------------------------------------
#
# Under expert parallelism each chip holds a contiguous block of the routed
# experts.  The layer routes every token over all n_experts at the router's
# published width and top_k, and computes the held experts' part of the
# result; what the absent experts add is another chip's part.  The shared
# experts are computed once, here.  Every held expert runs over every token
# (capacity = tokens), weighted by its gate, which is 0 where the token did
# not choose it, so no token is ever dropped.  Compiled for a v5e at the
# DeepSeek-V2-Lite share (8 held of 64, width 1408, 512 tokens), sorting the
# token-choices into ``jax.lax.ragged_dot`` computes every one of the
# tokens * top_k rows (75% of these operations, not the 1/8 that reach
# held experts) and moves more bytes, 4.8 GB against 1.5 GB at 8192 tokens.


def route(xt, router, cfg: ModelConfig):
    """Softmax over all ``n_experts``, greedy top-k: (gates (T, k) f32,
    expert ids (T, k) int32), the gates renormalised when
    ``norm_topk_prob`` and times ``routed_scaling_factor``."""
    probs = jax.nn.softmax(xt.astype(jnp.float32) @ router.astype(jnp.float32), -1)
    gate_k, idx_k = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        gate_k = gate_k * cfg.routed_scaling_factor
    return gate_k, idx_k


def share_block(x: jnp.ndarray, p: dict, cfg: ModelConfig):
    """x: (B, S, d) -> (y, expert ids (B*S, top_k), token-choices routed to
    each held expert (experts_held,) int32).  y is the held experts' part
    of the routed output plus the shared experts."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe.route"):
        gate_k, idx_k = route(xt, p["router"], cfg)
        held = cfg.expert_first + jnp.arange(p["wg"].shape[0])
        hit = idx_k[:, :, None] == held  # (T, k, E_held)
        gates = jnp.sum(jnp.where(hit, gate_k[:, :, None], 0.0), axis=1)  # (T, E_held)
        counts = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("moe.held_experts"):
        wg, wu, wd = cast(p["wg"]), cast(p["wu"]), cast(p["wd"])

        def held(xc, gc):
            h = jax.nn.silu(jnp.einsum("td,edf->etf", xc, wg)) * jnp.einsum(
                "td,edf->etf", xc, wu)
            h = h * gc.T[:, :, None].astype(h.dtype)
            return jnp.einsum("etf,efd->td", h, wd)

        y = token_chunked(held, xt, gates)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + _shared_experts(xt, p)
    return y.reshape(b, s, d), idx_k, counts


# ---------------------------------------------------------------------------
# Explicit expert parallelism (shard_map + all-to-all) — §Perf H1
# ---------------------------------------------------------------------------
#
# Why: under plain pjit, the scatter that builds the (E, C, d) dispatch
# buffer has data-dependent indices, so XLA's SPMD partitioner REPLICATES
# the buffer and with it the expert GEMMs — measured 177x useful-FLOP waste
# on deepseek-moe-16b train_4k (EXPERIMENTS.md §Perf).  The fix is the
# production formulation: explicit shard_map where
#   * tokens stay local to their data shard (dispatch scatter is LOCAL),
#   * one all-to-all over the model axis routes capacity buffers to the
#     expert's home shard: (E, C_loc, d) -> (E/m, C_loc * m, d),
#   * expert GEMMs run on local weights (E/m, d, f),
#   * a reverse all-to-all brings expert outputs back to the token shard.
# Collective cost per layer: 2 all-to-alls of E*C_loc*d bytes, the textbook
# EP exchange (GShard), instead of replicated compute.


def moe_block_ep(x: jnp.ndarray, p: dict, cfg: ModelConfig, mesh):
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    m = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    # Tokens are sharded over EVERY mesh axis inside the MoE region (the
    # model axis included) — otherwise the m model-shards of a data shard
    # dispatch identical copies and the expert GEMMs run m-fold redundant.
    # Degrade gracefully for small token counts (decode: 128 streams) by
    # dropping axes from the right until the split divides.
    tok_axes = dp_axes + ("model",)
    while tok_axes:
        n_split = 1
        for a in tok_axes:
            n_split *= mesh.shape[a]
        if (b * s) % n_split == 0:
            break
        tok_axes = tok_axes[:-1]
    if not tok_axes:
        return _moe_block_dense(x, p, cfg)
    t_loc = (b * s) // n_split
    cap_loc = max(8, -(-int(k * t_loc * cfg.capacity_factor / e) // 8) * 8)
    e_loc = e // m

    from jax.sharding import PartitionSpec as P

    def local_moe(xt, router_w, wg, wu, wd):
        # xt: (T_loc, d) — this data shard's tokens (replicated over model).
        # wg/wu/wd: (E/m, d, f)-local expert weights.  All math below is
        # per-device; collectives are explicit.
        xt = xt.reshape(-1, d)
        logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, -1)
        gate_k, idx_k = jax.lax.top_k(probs, k)
        gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx_k, e, dtype=jnp.float32), 1), 0)
        aux = e * jnp.sum(me * ce)
        for a in tok_axes:
            aux = jax.lax.pmean(aux, a)

        flat_e = idx_k.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, 0) - onehot, flat_e[:, None], 1
        )[:, 0]
        keep = pos < cap_loc
        slot = jnp.minimum(pos, cap_loc - 1)
        tok_rep = jnp.repeat(jnp.arange(t_loc), k)

        buf = jnp.zeros((e, cap_loc, d), x.dtype)
        buf = buf.at[flat_e, slot].add(
            jnp.where(keep[:, None], xt[tok_rep], 0).astype(x.dtype)
        )

        # Dispatch a2a: (E, C_loc, d) -> (E/m, C_loc * m, d).
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1, tiled=True)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, cast(wg))) * jnp.einsum(
            "ecd,edf->ecf", buf, cast(wu)
        )
        out = jnp.einsum("ecf,efd->ecd", h, cast(wd))

        # Return a2a: (E/m, C_loc * m, d) -> (E, C_loc, d).
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0, tiled=True)

        gathered = out[flat_e, slot]
        gathered = jnp.where(keep[:, None], gathered, 0)
        w = gate_k.reshape(-1)[:, None].astype(x.dtype)
        y = jnp.zeros((t_loc, d), x.dtype).at[tok_rep].add(gathered * w)
        return y, aux

    tok_spec = tok_axes
    xt_all = x.reshape(b * s, d)
    y, aux = sharding.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(
            P(tok_spec, None),
            P(None, None),  # router replicated
            P("model", None, None),  # expert weights: E sharded locally
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(tok_spec, None), P()),
        check=False,
    )(xt_all, p["router"], p["wg"], p["wu"], p["wd"])
    y = y.reshape(b, s, d)

    if "shared" in p:
        sh = p["shared"]
        xt = x.reshape(b * s, d)
        hs = jax.nn.silu(xt @ cast(sh["wg"])) * (xt @ cast(sh["wu"]))
        hs = sharding.constrain(hs, "batch", "mlp")
        y = y + (hs @ cast(sh["wd"])).reshape(b, s, d)

    return y, aux
