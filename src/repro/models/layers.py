"""Shared neural building blocks + a tiny param-schema system.

Params are plain nested dicts of jnp arrays.  Every leaf is declared once via
``Leaf(shape, axes, init)`` so the SAME declaration yields (a) materialized
arrays for real runs, (b) ShapeDtypeStructs for the dry-run, and (c)
PartitionSpecs (through ``distributed.sharding.resolve``) — no parallel
bookkeeping to drift out of sync.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding

# ---------------------------------------------------------------------------
# Param schema
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple
    axes: tuple  # logical axis names, len == len(shape)
    init: str = "normal"  # 'normal' | 'zeros' | 'ones' | 'embed'
    scale: Optional[float] = None  # override fan-in scaling

    def initializer(self, key, dtype):
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        fan_in = self.shape[0] if len(self.shape) > 1 else self.shape[-1]
        if self.init == "embed":
            s = 1.0
        else:
            s = self.scale if self.scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, self.shape, jnp.float32) * s).astype(dtype)


def _iter_leaves(schema, path=()):
    if isinstance(schema, Leaf):
        yield path, schema
        return
    for k, v in schema.items():
        yield from _iter_leaves(v, path + (k,))


def init_params(schema, key, dtype=jnp.float32):
    """Materialize a schema into arrays (per-leaf fold_in keys, from a
    checksum of the leaf's path, so a key gives the same arrays in every
    process)."""
    out = {}
    for path, leaf in _iter_leaves(schema):
        sub = out
        for k in path[:-1]:
            sub = sub.setdefault(k, {})
        lk = jax.random.fold_in(key, zlib.crc32("/".join(map(str, path)).encode()) % (2**31))
        sub[path[-1]] = leaf.initializer(lk, dtype)
    return out


_BIG = 1 << 20  # params above this get the ensure-model-sharded post-pass
_FSDP = 1 << 22  # params above this are additionally FSDP-sharded over data


def _leaf_spec(leaf: Leaf):
    spec = sharding.resolve(*leaf.axes, shape=leaf.shape)
    if leaf.init == "embed":
        # Gather-indexed tables only shard via their natural 'vocab' rule:
        # post-pass sharding of the feature dim trips XLA's gather
        # partitioner when the vocab is not mesh-divisible (50280, 51865).
        return spec
    n = int(np.prod(leaf.shape))
    if n >= _BIG:
        spec = sharding.ensure_axis_sharded(spec, leaf.shape, "model")
    if n >= _FSDP:
        # ZeRO-3: master params (and, via moment_of, the Adam moments)
        # shard over the data axis; XLA inserts the per-layer all-gather /
        # grad reduce-scatter.
        spec = sharding.ensure_axis_sharded(spec, leaf.shape, "data")
    return spec


def abstract_params(schema, dtype=jnp.float32):
    """ShapeDtypeStructs with NamedShardings (for .lower() without allocation)."""
    mesh = sharding.mesh_or_none()
    out = {}
    for path, leaf in _iter_leaves(schema):
        sub = out
        for k in path[:-1]:
            sub = sub.setdefault(k, {})
        ns = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            ns = NamedSharding(mesh, _leaf_spec(leaf))
        sub[path[-1]] = jax.ShapeDtypeStruct(leaf.shape, dtype, sharding=ns)
    return out


def param_specs(schema):
    """PartitionSpec pytree matching the schema structure."""
    out = {}
    for path, leaf in _iter_leaves(schema):
        sub = out
        for k in path[:-1]:
            sub = sub.setdefault(k, {})
        sub[path[-1]] = _leaf_spec(leaf)
    return out


def stack_schema(schema, n: int, axis_name: str = "layers"):
    """Add a leading stacked-layers dim to every leaf (for lax.scan)."""
    if isinstance(schema, Leaf):
        return Leaf(
            shape=(n,) + schema.shape,
            axes=(axis_name,) + schema.axes,
            init=schema.init,
            scale=schema.scale,
        )
    return {k: stack_schema(v, n, axis_name) for k, v in schema.items()}


def count_params(schema) -> int:
    return sum(int(np.prod(leaf.shape)) for _, leaf in _iter_leaves(schema))


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

COMPUTE_DTYPE = jnp.bfloat16


def cast(x):
    return x.astype(COMPUTE_DTYPE)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def swiglu(x, wg, wu, wd):
    """LLaMA-style gated MLP.  x: (..., d); wg/wu: (d, ff); wd: (ff, d)."""
    h = jax.nn.silu(x @ cast(wg)) * (x @ cast(wu))
    h = sharding.constrain(h, "batch", "seq", "mlp")
    return h @ cast(wd)


TOKEN_CHUNK = 8192  # tokens per pass of a wide per-token layer over a long input


def token_chunked(fn, x, *per_row, chunk: int = TOKEN_CHUNK):
    """``fn(x, *per_row)`` on inputs with a leading row axis (tokens, or
    sequences), over chunks of ``chunk`` rows when there are more rows
    than that and a multiple of it, so a wide layer's activations stay a
    chunk's size over a long prefill.  fn works row by row, so the result
    is the same; its outputs (any pytree) are stacked back in row order."""
    t = x.shape[0]
    if t <= chunk or t % chunk:
        return fn(x, *per_row)
    n = t // chunk
    split = lambda a: a.reshape((n, chunk) + a.shape[1:])
    y = jax.lax.map(lambda args: fn(*args), tuple(split(a) for a in (x,) + per_row))
    return jax.tree.map(lambda a: a.reshape((t,) + a.shape[2:]), y)


def gelu_mlp(x, wi, wo):
    h = jax.nn.gelu(x @ cast(wi), approximate=True)
    h = sharding.constrain(h, "batch", "seq", "mlp")
    return h @ cast(wo)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float) -> float:
    """DeepSeek's ``yarn_get_mscale``: the attention temperature YaRN pairs
    with a context stretched ``scale`` times."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_frequencies(dim: int, theta: float, factor: float, original_max_len: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies (dim/2,), float32, as DeepSeek-V2 computes
    them: the plain frequency for dimensions that turn more than
    ``beta_fast`` times over ``original_max_len`` positions, the frequency
    divided by ``factor`` for those that turn fewer than ``beta_slow``
    times, and a linear ramp between (``yarn_find_correction_range``)."""

    def correction_dim(rotations):
        return (dim * math.log(original_max_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1 where the plain frequency is kept
    return (extra / factor * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
               freqs=None, scale: float = 1.0):
    """x: (B, S, H, hd); positions: (B, S) int32.  ``freqs`` (hd/2,)
    replaces the plain inverse frequencies (YaRN), and ``scale``
    multiplies cos and sin (YaRN's mscale ratio)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def sinusoidal_embedding(n_pos: int, dim: int) -> jnp.ndarray:
    pos = np.arange(n_pos)[:, None]
    i = np.arange(dim // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * i / dim)
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, jnp.float32)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """logits (..., V) f32; labels (...) int32 -> mean loss (f32)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
