"""OS-ELM: Online Sequential Extreme Learning Machine (paper §2.1).

Single-hidden-layer network.  ``alpha`` (input->hidden) is fixed random and
never trained; ``beta`` (hidden->output) is trained by recursive least squares
(rank-k Woodbury update of the inverse Gram matrix ``P``):

    H   = G(x @ alpha)                                  (k, N)
    S   = I_k + H P H^T                                 (k, k)
    P'  = P - P H^T S^{-1} H P                          (N, N)
    beta' = beta + P' H^T (Y - H beta)                  (N, m)

Variants (paper §2.3):
  * ``base``  — alpha stored dense (ODLBase).
  * ``hash``  — alpha regenerated on the fly from Xorshift16 (ODLHash); on
    TPU the Pallas kernel ``kernels/xorshift_proj.py`` generates alpha tiles
    in VMEM so they never touch HBM.

Training targets are one-hot labels; the output layer is linear (least
squares regresses E[y|x] = class posterior), so raw outputs are used directly
as the probabilities p1/p2 for the P1P2 confidence metric.

All functions are jit/vmap-friendly; a "fleet" of independent heads is just a
leading stream axis vmapped over ``OSELMState``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import xorshift


@dataclasses.dataclass(frozen=True)
class OSELMConfig:
    n_in: int = 561
    n_hidden: int = 128
    n_out: int = 6
    variant: str = "hash"  # 'base' | 'hash'
    seed: int = xorshift.DEFAULT_SEED
    activation: str = "sigmoid"  # 'sigmoid' | 'relu' | 'tanh' | 'identity'
    ridge: float = 1e-2  # epsilon for P_0 = (H0^T H0 + ridge I)^{-1}
    alpha_scale: float = 1.0  # scales alpha; sigmoid saturates if n_in large
    use_kernel: bool = False  # route hidden() through the Pallas kernel path

    def replace(self, **kw) -> "OSELMConfig":
        return dataclasses.replace(self, **kw)


class OSELMState(NamedTuple):
    """Trainable state of one ODL head (a pytree)."""

    beta: jnp.ndarray  # (N, m) f32
    P: jnp.ndarray  # (N, N) f32 inverse Gram
    count: jnp.ndarray  # () int32 — samples trained so far


def _activate(z: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "sigmoid":
        return jax.nn.sigmoid(z)
    if kind == "relu":
        return jax.nn.relu(z)
    if kind == "tanh":
        return jnp.tanh(z)
    if kind == "identity":
        return z
    raise ValueError(f"unknown activation {kind!r}")


def make_alpha(cfg: OSELMConfig) -> Optional[jnp.ndarray]:
    """Materialized alpha for 'base'; None for 'hash' (regenerated per call)."""
    if cfg.variant == "base":
        return xorshift.alpha_dense(cfg.seed, cfg.n_in, cfg.n_hidden, cfg.alpha_scale)
    return None


def hidden(
    x: jnp.ndarray, cfg: OSELMConfig, alpha: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Hidden activations H = G(x @ alpha * scale / sqrt(n)).  x: (..., n_in).

    The 1/sqrt(n_in) factor keeps pre-activations O(1) for any input width
    (the ASIC tunes fixed-point ranges instead; DESIGN.md §5).
    """
    inv_sqrt_n = jnp.float32(1.0) / jnp.sqrt(jnp.float32(cfg.n_in))
    if cfg.variant == "hash":
        if cfg.use_kernel:
            from repro.kernels import ops  # lazy: kernels are optional at import

            z = ops.xorshift_projection(
                x.astype(jnp.float32), cfg.seed, cfg.n_hidden, scale=cfg.alpha_scale
            )
        else:
            a = xorshift.alpha_hash(cfg.seed, cfg.n_in, cfg.n_hidden)
            z = x.astype(jnp.float32) @ (a * jnp.float32(cfg.alpha_scale))
    else:
        if alpha is None:
            alpha = make_alpha(cfg)
        z = x.astype(jnp.float32) @ alpha
    return _activate(z * inv_sqrt_n, cfg.activation)


def init_state(cfg: OSELMConfig) -> OSELMState:
    """Pure-online init: P_0 = I/ridge, beta_0 = 0 (no initial batch needed)."""
    return OSELMState(
        beta=jnp.zeros((cfg.n_hidden, cfg.n_out), jnp.float32),
        P=jnp.eye(cfg.n_hidden, dtype=jnp.float32) / jnp.float32(cfg.ridge),
        count=jnp.zeros((), jnp.int32),
    )


def init_state_batch(
    cfg: OSELMConfig,
    x0: jnp.ndarray,
    y0: jnp.ndarray,
    alpha: Optional[jnp.ndarray] = None,
) -> OSELMState:
    """Classic OS-ELM boot: P_0 = (H0^T H0 + ridge I)^{-1}, beta_0 = P0 H0^T Y0."""
    h0 = hidden(x0, cfg, alpha)
    gram = h0.T @ h0 + jnp.float32(cfg.ridge) * jnp.eye(cfg.n_hidden, dtype=jnp.float32)
    # Solve instead of explicit inverse for conditioning; P0 itself is needed
    # downstream, so invert via Cholesky solve against identity.
    p0 = jax.scipy.linalg.cho_solve(
        jax.scipy.linalg.cho_factor(gram), jnp.eye(cfg.n_hidden, dtype=jnp.float32)
    )
    beta0 = p0 @ (h0.T @ y0.astype(jnp.float32))
    return OSELMState(beta=beta0, P=p0, count=jnp.asarray(x0.shape[0], jnp.int32))


def predict_logits(
    state: OSELMState, x: jnp.ndarray, cfg: OSELMConfig, alpha=None
) -> jnp.ndarray:
    """Linear outputs O = H beta (approximate class posteriors)."""
    return hidden(x, cfg, alpha) @ state.beta


def predict(
    state: OSELMState, x: jnp.ndarray, cfg: OSELMConfig, alpha=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (predicted class c, outputs O) — Fig. 2(b)."""
    o = predict_logits(state, x, cfg, alpha)
    return jnp.argmax(o, axis=-1), o


def sequential_update(
    state: OSELMState,
    x: jnp.ndarray,
    y: jnp.ndarray,
    cfg: OSELMConfig,
    alpha: Optional[jnp.ndarray] = None,
    mask: Optional[jnp.ndarray] = None,
    use_kernel: Optional[bool] = None,
) -> OSELMState:
    """Rank-k RLS update (Fig. 2(d)).  x: (k, n_in) or (n_in,); y one-hot.

    ``mask`` (k,) in {0,1} soft-deletes rows (pruned samples inside a fixed
    batch shape — pruning must not change trace shapes under jit). A masked
    row contributes exactly nothing: H_row := 0 ⇒ S row/col = identity's,
    and the beta innovation term is zeroed.

    ``use_kernel`` defaults to ``cfg.use_kernel``, so configuring the Pallas
    path on the config reaches every training entry point.
    """
    if use_kernel is None:
        use_kernel = cfg.use_kernel
    if x.ndim == 1:
        x = x[None]
        y = y[None]
        if mask is not None:
            mask = mask[None]
    k = x.shape[0]
    h = hidden(x, cfg, alpha)  # (k, N)
    if mask is not None:
        h = h * mask[:, None].astype(h.dtype)
    y = y.astype(jnp.float32)
    if mask is not None:
        y = y * mask[:, None].astype(jnp.float32)

    if use_kernel:
        from repro.kernels import ops

        new_p, new_beta = ops.oselm_rls_update(state.P, state.beta, h, y)
    else:
        pht = state.P @ h.T  # (N, k)
        s = jnp.eye(k, dtype=jnp.float32) + h @ pht  # (k, k)
        g = jnp.linalg.solve(s, pht.T)  # (k, N) = S^{-1} H P
        new_p = state.P - pht @ g
        new_p = 0.5 * (new_p + new_p.T)  # enforce symmetry (numerics)
        new_beta = state.beta + new_p @ (h.T @ (y - h @ state.beta))

    inc = (
        jnp.sum(mask.astype(jnp.int32))
        if mask is not None
        else jnp.asarray(k, jnp.int32)
    )
    return OSELMState(beta=new_beta, P=new_p, count=state.count + inc)


def fit_closed_form(
    cfg: OSELMConfig, x: jnp.ndarray, y: jnp.ndarray, alpha=None
) -> jnp.ndarray:
    """Ridge least-squares solution over the whole dataset (test oracle).

    Sequential OS-ELM over all rows must converge to this beta exactly
    (Woodbury identity) — used by tests/test_oselm.py.
    """
    h = hidden(x, cfg, alpha)
    gram = h.T @ h + jnp.float32(cfg.ridge) * jnp.eye(cfg.n_hidden, dtype=jnp.float32)
    return jnp.linalg.solve(gram, h.T @ y.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Fleet helpers: many independent heads, one per stream (leading axis S).
# ---------------------------------------------------------------------------


def init_fleet(cfg: OSELMConfig, n_streams: int) -> OSELMState:
    one = init_state(cfg)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n_streams,) + a.shape), one)


def fleet_predict(state: OSELMState, x: jnp.ndarray, cfg: OSELMConfig):
    """x: (S, n_in) — one sample per stream."""
    return jax.vmap(lambda st, xx: predict(st, xx, cfg))(state, x)


def fleet_update(state: OSELMState, x: jnp.ndarray, y: jnp.ndarray, cfg: OSELMConfig,
                 mask: Optional[jnp.ndarray] = None,
                 use_kernel: Optional[bool] = None) -> OSELMState:
    """x: (S, n_in), y: (S, m), mask: (S,) — rank-1 update per stream.

    This is the vmap-of-rank-1 baseline; ``repro.engine`` and the serve path
    use :func:`fleet_rank1_update_h` (einsum-batched, kernel-routable)
    instead.  ``use_kernel`` (default: ``cfg.use_kernel``) dispatches to the
    batched Pallas entry rather than vmapping a scalar ``pallas_call``.
    """
    if mask is None:
        mask = jnp.ones(x.shape[0], jnp.float32)
    if use_kernel is None:
        use_kernel = cfg.use_kernel
    if use_kernel:
        return fleet_rank1_update(state, x, y, cfg, mask=mask, use_kernel=True)
    return jax.vmap(
        lambda st, xx, yy, mm: sequential_update(st, xx, yy, cfg, mask=mm, use_kernel=False)
    )(state, x, y, mask)


def fleet_rank1_update_h(
    state: OSELMState,  # leaves with leading S
    h: jnp.ndarray,  # (S, N) hidden activations, one row per stream
    y: jnp.ndarray,  # (S, m) one-hot targets
    cfg: OSELMConfig,
    mask: Optional[jnp.ndarray] = None,  # (S,) in {0, 1}
    use_kernel: Optional[bool] = None,
) -> OSELMState:
    """Fused fleet rank-1 RLS: the whole Woodbury update for S independent
    heads as batched einsums (one XLA fusion, no per-stream solve).

    Takes precomputed hidden activations so callers that already predicted
    this tick (the engine's fleet_step) never project twice.  A masked
    stream is an exact identity on (P, beta, count), same contract as
    ``sequential_update``.
    """
    if mask is None:
        mask = jnp.ones(h.shape[0], jnp.float32)
    if use_kernel is None:
        use_kernel = cfg.use_kernel
    hm = h * mask[:, None]
    ym = y.astype(jnp.float32) * mask[:, None]

    if use_kernel:
        from repro.kernels import ops  # lazy: kernels are optional at import

        new_p, new_beta = ops.oselm_rls_update_fleet(
            state.P, state.beta, hm[:, None, :], ym[:, None, :]
        )
    else:
        pht = jnp.einsum("snk,sk->sn", state.P, hm)  # (S, N) = P h
        den = 1.0 + jnp.einsum("sn,sn->s", hm, pht)  # (S,) = 1 + h P hᵀ
        new_p = state.P - pht[:, :, None] * (pht[:, None, :] / den[:, None, None])
        new_p = 0.5 * (new_p + new_p.transpose(0, 2, 1))  # symmetry (numerics)
        # Symmetrizing would move a masked stream's P that is symmetric only
        # to rounding (a batch-initialized one): keep it bit for bit.
        new_p = jnp.where(mask[:, None, None] > 0, new_p, state.P)
        err = ym - jnp.einsum("sn,snm->sm", hm, state.beta)  # (S, m)
        # Rank-1 identity: P' hᵀ = (P - P hᵀh P/den) hᵀ = pht/den, so the
        # innovation beta' = beta + P' hᵀ e needs no (N, N) x (N, m) matmul
        # — the classic RLS gain vector, O(S N m) instead of O(S N² m).
        gain = pht / den[:, None]
        new_beta = state.beta + gain[:, :, None] * err[:, None, :]

    return OSELMState(
        beta=new_beta, P=new_p, count=state.count + mask.astype(jnp.int32)
    )


def fleet_rank1_update(
    state: OSELMState,
    x: jnp.ndarray,  # (S, n_in)
    y: jnp.ndarray,  # (S, m)
    cfg: OSELMConfig,
    mask: Optional[jnp.ndarray] = None,
    use_kernel: Optional[bool] = None,
) -> OSELMState:
    """As :func:`fleet_rank1_update_h` but projecting ``x`` itself."""
    return fleet_rank1_update_h(
        state, hidden(x, cfg), y, cfg, mask=mask, use_kernel=use_kernel
    )
