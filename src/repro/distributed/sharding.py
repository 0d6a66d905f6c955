"""Logical-axis sharding: one place that maps tensor axes onto the mesh.

Models annotate tensors with *logical* axis names ("batch", "heads", ...);
this module resolves them to mesh axes via a rule table, so the same model
code runs on a single CPU device (rules inactive -> no-ops), the 16x16
single-pod mesh, and the 2x16x16 multi-pod mesh.

Default rule set (DESIGN.md §3):
  batch   -> ("pod", "data")     data parallel over pods x data axis
  heads/kv_heads/mlp/experts/vocab -> "model"   tensor/expert parallel
  seq_sp  -> "model"             sequence parallel (Megatron-SP regions)
  stream  -> ("fleet", "pod", "data")   ODL fleet heads; a dedicated
            1-D ``fleet`` mesh (launch.mesh.make_fleet_mesh) takes the
            whole axis, and on LLM meshes it rides the data axis

Use ``activate(mesh, rules)`` as a context manager; ``constrain`` is an
identity outside it.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "stream": ("fleet", "pod", "data"),
    "seq": None,
    "seq_sp": "model",  # sequence-parallel regions (hillclimb variant)
    "seq_kv": "model",  # decode KV/latent cache length (flash-decoding style)
    "seq_attn": None,  # q rows in attention (enabled when heads don't divide)
    "embed": None,
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_cap": None,
    "ssm_heads": "model",
    "ssm_state": None,
    "elm_hidden": None,
    "elm_out": None,
    "classes": None,
    "layers": None,
    "frames": None,
}


def _current() -> tuple[Optional[Mesh], dict]:
    return getattr(_state, "mesh", None), getattr(_state, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def activate(mesh: Mesh, rules: Optional[dict] = None):
    """Enable sharding constraints for model code under this mesh."""
    prev = _current()
    _state.mesh = mesh
    _state.rules = dict(DEFAULT_RULES, **(rules or {}))
    try:
        with mesh:
            yield
    finally:
        _state.mesh, _state.rules = prev


@contextlib.contextmanager
def deactivate():
    """Temporarily disable sharding constraints inside an ``activate``
    scope.  For shard-*local* dispatch regions (e.g. a mesh-sharded
    stream session's per-shard plan/learn calls, each pinned to one
    device): under the enclosing mesh ``constrain`` would demand the
    full device set for single-device operands."""
    prev = _current()
    _state.mesh, _state.rules = None, DEFAULT_RULES
    try:
        yield
    finally:
        _state.mesh, _state.rules = prev


def resolve(*logical_axes: Optional[str], shape: Optional[tuple] = None) -> P:
    """Logical axis names -> PartitionSpec under the active rules.

    Rules that name mesh axes absent from the active mesh degrade to
    replication (so the same model runs on a 2-axis or 3-axis mesh).  When
    ``shape`` is given, mesh axes that do not divide the dim are dropped
    (greedy prefix for multi-axis rules) — e.g. batch=1 stays replicated,
    56 heads on a 16-way model axis fall back to replication (and a schema
    post-pass reassigns 'model' to a divisible dim, see layers.param_specs).
    """
    mesh, rules = _current()
    axis_names = set(mesh.axis_names) if mesh is not None else set()
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None else {}

    spec, used = [], set()
    for i, name in enumerate(logical_axes):
        if name is None:
            spec.append(None)
            continue
        target = rules.get(name, None)
        if target is None:
            spec.append(None)
            continue
        parts = (target,) if isinstance(target, str) else tuple(target)
        parts = tuple(p for p in parts if p in axis_names and p not in used)
        if shape is not None:
            dim = shape[i]
            kept, prod = [], 1
            for p in parts:  # greedy prefix that divides the dim
                if dim % (prod * mesh_shape[p]) == 0:
                    kept.append(p)
                    prod *= mesh_shape[p]
            parts = tuple(kept)
        used.update(parts)
        if not parts:
            spec.append(None)
        elif len(parts) == 1:
            spec.append(parts[0])
        else:
            spec.append(parts)
    return P(*spec)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; identity with no mesh."""
    mesh, _ = _current()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, resolve(*logical_axes, shape=x.shape))
    )


def named_sharding(
    *logical_axes: Optional[str], shape: Optional[tuple] = None
) -> Optional[NamedSharding]:
    mesh, _ = _current()
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve(*logical_axes, shape=shape))


def ensure_axis_sharded(spec: P, shape: tuple, axis: str) -> P:
    """Schema post-pass: add mesh axis `axis` to the largest divisible
    unsharded dim if the spec does not use it yet.

    Used twice on large params: (1) 'model' — memory safety for archs whose
    natural TP axis (e.g. 56 heads) does not divide the model axis; (2)
    'data' — FSDP/ZeRO-3 sharding of master params + moments, without which
    a 236B model's f32 state cannot fit 16 GB/chip on a 256-chip pod."""
    mesh, _ = _current()
    if mesh is None or axis not in mesh.axis_names:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    flat = []
    for e in entries:
        flat.extend(e if isinstance(e, tuple) else (e,))
    if axis in flat:
        return spec
    asize = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    cands = [
        (shape[i], i)
        for i, e in enumerate(entries)
        if e is None and shape[i] % asize == 0 and shape[i] >= asize
    ]
    if not cands:
        return spec
    _, idx = max(cands)
    entries[idx] = axis
    return P(*entries)


def ensure_model_sharded(spec: P, shape: tuple) -> P:
    return ensure_axis_sharded(spec, shape, "model")


def mesh_or_none() -> Optional[Mesh]:
    return _current()[0]


def fleet_axis_size() -> int:
    """Number of shards the ``stream`` rule resolves to under the active
    mesh: the product of the mesh-axis sizes that would split an (evenly
    divisible) fleet's leading axis.  1 with no mesh active — callers use
    this to size stream-axis padding before ``device_put``."""
    mesh, rules = _current()
    if mesh is None:
        return 1
    target = rules.get("stream", None)
    if target is None:
        return 1
    parts = (target,) if isinstance(target, str) else tuple(target)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for p in parts:
        n *= mesh_shape.get(p, 1)
    return n


def shard_map(f, mesh, in_specs, out_specs, check=False):
    """``jax.shard_map`` with replication checking off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check)


def constrain_fleet(tree):
    """Constrain the leading axis of every leaf to the ``stream`` rule.

    The ODL fleet (``repro.engine.EngineState``) carries one head per stream
    on the leading axis of every leaf; under an active mesh this splits the
    fleet over ``("pod", "data")`` (per DEFAULT_RULES) with zero
    cross-stream communication.  Identity with no mesh active, and streams
    that don't divide the axis degrade to replication (see ``resolve``).
    """
    mesh, _ = _current()
    if mesh is None:
        return tree
    return jax.tree.map(
        lambda a: constrain(a, "stream", *((None,) * (a.ndim - 1))), tree
    )


def fleet_sharding(leaf_ndim: int, shape: Optional[tuple] = None) -> Optional[NamedSharding]:
    """NamedSharding placing a fleet leaf's leading axis on the stream rule
    (for explicit ``jax.device_put`` of an EngineState onto a mesh)."""
    return named_sharding("stream", *((None,) * (leaf_ndim - 1)), shape=shape)
