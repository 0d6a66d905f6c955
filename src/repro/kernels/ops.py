"""Public jit'd wrappers for the Pallas kernels, with backend dispatch.

On the CPU backend kernels run in interpret mode — the kernel body executes
as Python/jnp, validating the exact tiling/accumulation logic the TPU would
run.  Every other backend compiles them (Mosaic on a TPU); a backend that
cannot fails loudly instead of quietly interpreting.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import oselm_update as _oselm_update
from repro.kernels import ref as _ref
from repro.kernels import xorshift_proj as _xorshift_proj


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def xorshift_projection(
    x: jnp.ndarray,
    seed: int,
    n_hidden: int,
    scale: float = 1.0,
    activation: str = "sigmoid",
) -> jnp.ndarray:
    """ODLHash projection H = G(x @ alpha(seed)); alpha generated in VMEM.

    Accepts (..., n_in); leading dims are flattened for the kernel.
    """
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    h = _xorshift_proj.xorshift_projection(
        x2, seed=seed, n_hidden=n_hidden, scale=scale, activation=activation,
        interpret=_interpret(),
    )
    return h.reshape(lead + (n_hidden,))


def oselm_rls_update(
    P: jnp.ndarray, beta: jnp.ndarray, H: jnp.ndarray, Y: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused rank-k RLS update (P', beta')."""
    return _oselm_update.oselm_rls_update(P, beta, H, Y, interpret=_interpret())


def oselm_rls_update_fleet(
    P: jnp.ndarray, beta: jnp.ndarray, H: jnp.ndarray, Y: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused rank-k RLS update for S independent heads (leading stream axis)."""
    return _oselm_update.oselm_rls_update_fleet(P, beta, H, Y, interpret=_interpret())


# Re-export oracles for benchmarking convenience.
xorshift_projection_ref = _ref.xorshift_projection_ref
oselm_rls_update_ref = _ref.oselm_rls_update_ref
