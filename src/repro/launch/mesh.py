"""Production mesh construction (multi-pod dry-run §1).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before any jax initialization and only then calls this.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_dev_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU multi-device tests (subprocess with 4-8 devices)."""
    return _make_mesh((data, model), ("data", "model"))


def make_fleet_mesh(n: int = 0):
    """1-D ``("fleet",)`` mesh over ``n`` devices (default: all visible).

    The ODL fleet's stream axis shards over this axis (sharding
    DEFAULT_RULES maps ``stream -> ("fleet", ...)``).  On a CPU host, force
    the device count first: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    avail = len(jax.devices())
    if n <= 0:
        n = avail
    if n > avail:
        raise ValueError(f"mesh-fleet {n} > {avail} visible devices")
    return _make_mesh((n,), ("fleet",))
