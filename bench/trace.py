"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so the second can be checked on a small recorded
trace without a chip:

1. ``compact(path)`` reads the ``.xplane.pb`` the JAX profiler wrote and
   keeps what the metrics need: per device the intervals of its XLA ops and
   of its XLA modules (one per executed jitted function, named after it),
   the host's ``bench.*`` annotations, and the traced window (the
   ``bench.window`` annotation).  Times are nanoseconds.
2. ``Reading(compacted)`` reduces that to busy time, module time and idle
   gaps inside the window.
"""

from __future__ import annotations

import glob
import os
import re

_MODULE = re.compile(r"^(?:jit_)?([A-Za-z0-9_]+)")


def module_name(raw: str) -> str:
    """``jit_bench_decode(7630...)`` -> ``bench_decode``."""
    m = _MODULE.match(raw)
    return m.group(1) if m else raw


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def compact(path: str) -> dict:
    """The device ops, device modules and host annotations of one trace."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [[e.start_ns, e.start_ns + e.duration_ns] for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [[module_name(e.name), e.start_ns, e.start_ns + e.duration_ns]
                            for e in line.events]
            if ops or mods:
                devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        span = [e.start_ns, e.start_ns + e.duration_ns]
                        if e.name == "bench.window":
                            window = span
                        else:
                            host.append([e.name] + span)
    return {"window": window, "devices": devices, "host": host}


def _union(intervals, lo, hi):
    """Merged [s, e] intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Reading:
    """Busy time, module time and idle gaps of a compacted trace, inside
    its window.  Device numbers are averaged over the devices traced."""

    def __init__(self, doc: dict):
        if not doc.get("window") or not doc.get("devices"):
            raise ValueError("trace has no bench.window or no device plane")
        self.lo, self.hi = doc["window"]
        self.window_s = (self.hi - self.lo) / 1e9
        self.devices = doc["devices"]
        self.host = doc.get("host", [])
        self.busy = {name: _union(d["ops"], self.lo, self.hi)
                     for name, d in self.devices.items()}
        self.busy_s = (sum(sum(e - s for s, e in b) for b in self.busy.values())
                       / len(self.busy) / 1e9)

    def modules(self, names) -> list:
        """(start, end) of every execution of the named modules that starts
        inside the window, over all devices."""
        names = set(names)
        return [(s, e) for d in self.devices.values() for n, s, e in d["modules"]
                if n in names and self.lo <= s < self.hi]

    def module_s(self, names) -> float:
        """Device seconds of the named modules, averaged over devices."""
        return sum(e - s for s, e in self.modules(names)) / len(self.devices) / 1e9

    def module_count(self, names) -> int:
        """Executions of the named modules per device."""
        return round(len(self.modules(names)) / len(self.devices))

    def top_modules(self, n: int = 10) -> list:
        """The modules that took most device time, [name, seconds]."""
        tot: dict = {}
        for d in self.devices.values():
            for name, s, e in d["modules"]:
                if self.lo <= s < self.hi:
                    tot[name] = tot.get(name, 0.0) + (min(e, self.hi) - s) / 1e9
        return sorted(([k, v / len(self.devices)] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest stretches with no op on the first device, each named
        after the innermost ``bench.*`` host annotation at its middle
        (``idle`` where none is open), [name, seconds]."""
        busy = next(iter(self.busy.values()))
        edges = [self.lo] + [x for s, e in busy for x in (s, e)] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (s + e) / 2
            open_ = [h for h in self.host if h[1] <= mid < h[2]]
            name = min(open_, key=lambda h: h[2] - h[1])[0] if open_ else "idle"
            out.append([name, (e - s) / 1e9])
        return out
