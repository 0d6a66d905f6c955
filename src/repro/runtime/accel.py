"""Accelerator hygiene shared by every entry point: the persistent compile
cache, a check that this process has not claimed a device yet, and the
environment that pins a child process to one TPU chip.

A chip belongs to one process at a time.  A parent that has initialized a
JAX backend holds every chip it sees, and a child that needs one then fails
or hangs.  So launchers that start JAX children (worker fleets, the
crash-restart smoke) stay off JAX until those children are gone, and the
children that need no device (the label server) run on the CPU backend.
Importing this module initializes no backend.
"""

from __future__ import annotations

import glob
import os
import pathlib
import socket
from typing import Optional

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# Fixed, at the checkout root: a cache that moves between runs never hits.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache lives in ``.jax_cache`` at the root of
    the checkout.
    """
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def backend_initialized() -> bool:
    """True once this process has initialized a JAX backend (and so holds
    its devices).  Never initializes one itself."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def host_tpu_chips(env: Optional[dict] = None) -> int:
    """TPU chips attached to this host, counted from their device files
    (``/dev/accel*``, or ``/dev/vfio/<n>`` on newer hosts) without touching
    JAX.  0 off a TPU host, or when ``JAX_PLATFORMS`` in ``env`` (default:
    this process's environment) excludes the TPU."""
    platforms = (os.environ if env is None else env).get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return len(glob.glob("/dev/vfio/[0-9]*"))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def one_chip_env(chip: int) -> dict:
    """libtpu variables that give a child process chip ``chip`` of this
    host and no other.  Bounds of one chip are a strict subset of a
    multi-chip host, which lets several processes load libtpu at once;
    each gets its own slice-builder port so they do not collide."""
    port = _free_port()
    return {
        "JAX_PLATFORMS": "tpu",  # no quiet fallback to the CPU
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(port),
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
    }
