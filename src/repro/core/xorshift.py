"""Xorshift16 pseudo-random weight generation (paper §2.3, ODLHash).

The paper replaces the stored random input projection ``alpha`` of OS-ELM with
a 16-bit Xorshift function with shift coefficients (7, 9, 8), evaluated by a
sequential state machine inside the 45nm core.  Two semantics live here:

* ``xorshift16_stream`` — the paper's *sequential* generator (state machine
  semantics).  Used by the memory/cycle models and as a CPU-side oracle.
* ``alpha_hash`` — the TPU-native *counter-based* variant: each matrix entry
  ``alpha[k, j]`` is derived independently from ``seed ^ (k*N + j + 1)`` by
  applying the same (7, 9, 8) Xorshift step ``rounds`` times.  This gives the
  random-access addressing a systolic MXU needs (DESIGN.md §2) while keeping
  the paper's arithmetic (16-bit xor/shift only).

Both map uint16 lattice points to floats in [-1, 1) via ``u16_to_unit``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# Paper coefficients: x ^= x << 7; x ^= x >> 9; x ^= x << 8  (mod 2^16).
SHIFT_A, SHIFT_B, SHIFT_C = 7, 9, 8
DEFAULT_ROUNDS = 3
DEFAULT_SEED = 0x2D2A  # arbitrary nonzero 16-bit constant


def xorshift16_step(x: jnp.ndarray) -> jnp.ndarray:
    """One (7, 9, 8) Xorshift16 step.  Input/output dtype uint16."""
    x = x.astype(jnp.uint16)
    x = x ^ (x << SHIFT_A)
    x = x ^ (x >> SHIFT_B)
    x = x ^ (x << SHIFT_C)
    return x


def xorshift16_rounds(x: jnp.ndarray, rounds: int = DEFAULT_ROUNDS) -> jnp.ndarray:
    """Apply ``rounds`` Xorshift16 steps (counter-based hash round function)."""
    for _ in range(rounds):
        x = xorshift16_step(x)
    return x


def u16_to_unit(x: jnp.ndarray) -> jnp.ndarray:
    """Map uint16 -> float32 in [-1, 1): x/32768 - 1."""
    return x.astype(jnp.float32) * jnp.float32(1.0 / 32768.0) - jnp.float32(1.0)


def xorshift16_stream(seed: int, length: int) -> np.ndarray:
    """The paper's sequential Xorshift16 state machine (numpy, host-side).

    Zero state is a fixed point of xorshift; seeds are forced nonzero.
    Returns ``length`` uint16 values (the state after each step).
    """
    s = np.uint16(seed if (seed & 0xFFFF) != 0 else 1)
    out = np.empty(length, dtype=np.uint16)
    for i in range(length):
        s = np.uint16(s ^ np.uint16((int(s) << SHIFT_A) & 0xFFFF))
        s = np.uint16(s ^ np.uint16(int(s) >> SHIFT_B))
        s = np.uint16(s ^ np.uint16((int(s) << SHIFT_C) & 0xFFFF))
        out[i] = s
    return out


# Odd 16-bit constants interleaved between xorshift rounds.  Xorshift alone
# is LINEAR over GF(2): xorshift(a) ^ xorshift(b) = xorshift(a ^ b), so
# sequential counters produce structurally correlated outputs no matter how
# many rounds (measured adjacent-column corr ~ -0.3 on the raw variant —
# enough to cost the ELM ~7 accuracy points vs stored-random weights).
# One multiply per round is non-linear in GF(2) and removes the correlation;
# a multiplier is cheap for the MXU-class adaptation target (DESIGN.md §2).
MIX_CONSTANTS = (0x2D2B, 0x9E35, 0xC2B3)


def mix16(x: jnp.ndarray, rounds: int = DEFAULT_ROUNDS) -> jnp.ndarray:
    """Counter hash: (xorshift16 round; odd-constant multiply) x rounds."""
    x = x.astype(jnp.uint16)
    for r in range(rounds):
        x = xorshift16_step(x)
        x = x * jnp.uint16(MIX_CONSTANTS[r % len(MIX_CONSTANTS)])
    return x


def alpha_hash(
    seed: int,
    n_in: int,
    n_hidden: int,
    rounds: int = DEFAULT_ROUNDS,
    row_offset: int = 0,
    col_offset: int = 0,
) -> jnp.ndarray:
    """Counter-based ODLHash weights: alpha[k, j] for a tile of the matrix.

    ``alpha[k, j] = u16_to_unit(mix16(seed ^ (gk*N_total + gj + 1)))``
    where (gk, gj) are *global* indices — offsets let a Pallas kernel generate
    any tile independently with identical values (tested bit-exact vs this).

    Note ``n_hidden`` here is the *global* number of columns N (it fixes the
    linear counter layout); pass ``row_offset/col_offset`` + a smaller shape
    via broadcasting by slicing the returned tile externally if needed.
    """
    rows = jnp.arange(n_in, dtype=jnp.uint32) + jnp.uint32(row_offset)
    cols = jnp.arange(n_hidden, dtype=jnp.uint32) + jnp.uint32(col_offset)
    # Counter = gk * N + gj + 1 (mod 2^16), xor'd into the seed.
    ctr = rows[:, None] * jnp.uint32(n_hidden) + cols[None, :] + jnp.uint32(1)
    x = (jnp.uint32(seed) ^ ctr).astype(jnp.uint16)
    # Avoid the zero fixed point.
    x = jnp.where(x == 0, jnp.uint16(0x9E37), x)
    x = mix16(x, rounds)
    return u16_to_unit(x)


def alpha_dense(seed: int, n_in: int, n_hidden: int, scale: float = 1.0) -> jnp.ndarray:
    """ODLBase weights: stored dense random alpha ~ U[-1, 1) from a jax PRNG.

    The paper stores 32-bit random numbers; the exact distribution is not
    specified, so we use uniform [-1, 1) to match ODLHash's range.
    """
    import jax

    key = jax.random.PRNGKey(seed)
    return jax.random.uniform(
        key, (n_in, n_hidden), dtype=jnp.float32, minval=-1.0, maxval=1.0
    ) * jnp.float32(scale)


def alpha_for_variant(
    variant: str, seed: int, n_in: int, n_hidden: int
) -> jnp.ndarray | None:
    """Materialized alpha for 'base', or None for 'hash' (generated on the fly)."""
    if variant == "base":
        return alpha_dense(seed, n_in, n_hidden)
    if variant == "hash":
        return None
    raise ValueError(f"unknown ODL variant: {variant!r}")
