"""Pallas TPU kernel: ODLHash hidden projection with in-VMEM weight generation.

The paper's ODLHash stores NO input weights: a 16-bit Xorshift PRNG generates
``alpha`` on the fly (45nm state machine, §2.3).  The TPU adaptation
(DESIGN.md §2) regenerates ``alpha`` *tiles* inside the kernel from a
counter-based Xorshift16 hash, so the (n_in x N) matrix never exists in HBM:

    HBM traffic:  x block in, H block out — alpha costs zero bytes.
    MXU work:     unchanged dense (TB x TK) @ (TK x TN) dots.

This converts the projection from memory-bound (arithmetic intensity ~2 for
stored weights at batch 1-8, the ODL serving regime) to compute-bound, which
is exactly the insight of the ASIC translated to the TPU memory hierarchy:
SRAM scarcity there, HBM bandwidth scarcity here.

Grid: (B/TB, N/TN, K/TK), K innermost for accumulation.  Alpha tiles are
derived from *global* (row, col) indices so every grid cell generates
bit-identical values to the ``ref.py`` oracle (tested exact).

All integer work is done in uint32 lanes with explicit 16-bit masking —
bit-identical to uint16 semantics and portable across interpret/TPU backends.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.xorshift import DEFAULT_ROUNDS, MIX_CONSTANTS, SHIFT_A, SHIFT_B, SHIFT_C

# NOTE: constants inside the kernel body must be numpy scalars (inlined as
# literals) — jnp arrays would be captured consts, which pallas_call rejects.
_M16 = np.uint32(0xFFFF)


def _mix16_u32(x: jnp.ndarray, rounds: int) -> jnp.ndarray:
    """(7,9,8) Xorshift16 + odd-constant multiply per round, on uint32 lanes
    with 16-bit masking — bit-identical to core.xorshift.mix16."""
    for r in range(rounds):
        x = (x ^ ((x << SHIFT_A) & _M16)) & _M16
        x = x ^ (x >> SHIFT_B)
        x = (x ^ ((x << SHIFT_C) & _M16)) & _M16
        x = (x * np.uint32(MIX_CONSTANTS[r % len(MIX_CONSTANTS)])) & _M16
    return x


def _alpha_tile(
    seed: int, row0: jnp.ndarray, col0: jnp.ndarray, tk: int, tn: int, n_total: int
) -> jnp.ndarray:
    """Generate the (tk, tn) alpha tile at global offset (row0, col0)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.uint32, (tk, tn), 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.uint32, (tk, tn), 1)
    ctr = rows * np.uint32(n_total) + cols + np.uint32(1)
    x = (np.uint32(seed) ^ ctr) & _M16
    x = jnp.where(x == 0, np.uint32(0x9E37), x)  # avoid the zero fixed point
    x = _mix16_u32(x, DEFAULT_ROUNDS)
    # u16 -> [-1, 1).  Mosaic has no uint32 -> float32 cast; the values are
    # below 2^16, so the detour through int32 is exact.
    x = x.astype(jnp.int32).astype(jnp.float32)
    return x * np.float32(1.0 / 32768.0) - np.float32(1.0)


def _proj_kernel(
    x_ref,  # (TB, TK) VMEM
    o_ref,  # (TB, TN) VMEM, accumulated over the K grid axis
    *,
    seed: int,
    n_total: int,
    n_in: int,
    scale: float,
    activation: str,
    k_tiles: int,
):
    j = pl.program_id(1)  # N tile
    k = pl.program_id(2)  # K tile (innermost; sequential on TPU)
    tb, tk = x_ref.shape
    tn = o_ref.shape[1]

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    alpha = _alpha_tile(
        seed,
        (k * tk).astype(jnp.uint32),
        (j * tn).astype(jnp.uint32),
        tk,
        tn,
        n_total,
    )
    part = jnp.dot(
        x_ref[...].astype(jnp.float32), alpha, preferred_element_type=jnp.float32
    )
    o_ref[...] += part * np.float32(scale)

    @pl.when(k == k_tiles - 1)
    def _finish():
        z = o_ref[...] * np.float32(1.0 / np.sqrt(n_in))
        if activation == "sigmoid":
            o_ref[...] = jax.nn.sigmoid(z)
        elif activation == "relu":
            o_ref[...] = jnp.maximum(z, 0.0)
        else:  # identity
            o_ref[...] = z


def _ceil_to(v: int, m: int) -> int:
    return -(-v // m) * m


@functools.partial(
    jax.jit,
    static_argnames=("seed", "n_hidden", "scale", "activation", "tb", "tn", "tk", "interpret"),
)
def xorshift_projection(
    x: jnp.ndarray,
    seed: int,
    n_hidden: int,
    scale: float = 1.0,
    activation: str = "sigmoid",
    tb: int = 128,
    tn: int = 128,
    tk: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """H = G(x @ alpha(seed) * scale / sqrt(n_in)); x: (B, n_in) -> (B, n_hidden).

    Tile sizes default to MXU-aligned 128; inputs are zero-padded to tile
    multiples (zero x rows/cols contribute nothing) and the output sliced.
    """
    b, n_in = x.shape
    bp, np_, kp = _ceil_to(b, tb), _ceil_to(n_hidden, tn), _ceil_to(n_in, tk)
    xp = jnp.zeros((bp, kp), x.dtype).at[:b, :n_in].set(x)
    k_tiles = kp // tk

    out = pl.pallas_call(
        functools.partial(
            _proj_kernel,
            seed=seed,
            n_total=n_hidden,  # counter layout uses the *logical* N
            n_in=n_in,
            scale=scale,
            activation=activation,
            k_tiles=k_tiles,
        ),
        grid=(bp // tb, np_ // tn, k_tiles),
        in_specs=[pl.BlockSpec((tb, tk), lambda i, j, k: (i, k))],
        out_specs=pl.BlockSpec((tb, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        interpret=interpret,
    )(xp)
    return out[:b, :n_hidden]
