"""The chip the benchmark runs on: its description, its peaks, and the
compiles it does.

``device_line`` refuses anything but a TPU with at least the chips a cell
asks for; there is no fallback to the CPU.  ``peaks`` looks the chip up in
``peaks.json`` by ``device_kind`` and refuses a chip that is not there.
``CompileMeter`` counts backend compiles and persistent-cache hits and
misses from JAX's monitoring events, so set-up can be split into compile
and the rest, and a compile inside the measured window shows.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """The process sees no TPU, or fewer chips than the cell needs."""


def device_line(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count of the devices JAX sees; raises NoChip
    unless they are TPUs and at least ``chips`` of them."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and dev["platform"] != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX found platform "
                     f"{dev['platform']!r}")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX sees {dev['count']}")
    return dev


def peaks(kind: str) -> dict:
    """Published peaks of one chip of this kind (see ``peaks.json``)."""
    table = json.loads(PEAKS_FILE.read_text())["chips"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS_FILE.name}; "
                       f"known: {sorted(table)}")
    return table[kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no such statistic)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileMeter:
    """Backend compile seconds (persistent-cache loads included) and cache
    hits/misses, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.secs, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.secs, self.compiles, self.hits, self.misses
