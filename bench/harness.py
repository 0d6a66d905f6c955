"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name: ``configs/<config>.json`` (and, for a backbone,
its plain reference ``configs/<config>.ref.py``), ``traffic/<mix>.json``
with the source kind it names, ``sources/<kind>.py``, and
``metrics/<metric>.py``.  The run:

1. refuses anything but a TPU with the chips the cell asks for;
2. set-up (``setup_s``): weights or data from the seed, the program's
   serving session, the warm-up ticks the traffic needs, every program
   compiled or loaded from the persistent cache;
3. the window: ``--seconds`` of multiplexer rounds; with ``--trace 1`` the
   first ``TRACE_S`` of it under the profiler;
4. the close: rounds on until the ticks ``query_share`` counts have all
   been handed (when the window held fewer), then the tick sources end and
   every tenant drains; the peak device memory is read, the program's
   state is freed;
5. the check against the plain references, each number beside its limit;
6. the result line, last on standard output.
"""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_DIR = pathlib.Path(__file__).resolve().parent
TRACE_S = 3.0  # seconds of the window a --trace 1 run profiles


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(bench: dict, workload: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """The cell named ``workload`` with its configuration, mix, reference
    and metric readers, each found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    doc = json.loads((bench_dir / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads((bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())
    source = load_module(bench_dir / "sources" / f"{mix['source']}.py",
                         f"bench_source_{mix['source']}").Source
    ref = None
    if doc.get("backbone"):
        ref = load_module(bench_dir / "configs" / f"{cell['config']}.ref.py",
                          f"bench_ref_{cell['config'].replace('-', '_').replace('.', '_')}")
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"] if workload in m.get("workloads", [workload])]
    readers = {m["name"]: load_module(bench_dir / "metrics" / f"{m['name']}.py",
                                      f"bench_metric_{m['name']}")
               for m in per_layer}
    return {"cell": cell, "config": doc, "mix": mix, "source": source, "ref": ref,
            "end_to_end": e2e, "per_layer": per_layer, "readers": readers}


class Traced:
    """What a per-layer metric reader gets: the trace reading, the work the
    traced ticks needed (``Cell.work``), the count of traced ticks, the
    name of the jitted function that makes a tick (``Cell.tick_op``), the
    chip's peaks and the chip count."""

    def __init__(self, reading, work, ticks, tick_op, peaks, chips):
        self.reading, self.work, self.ticks = reading, work, ticks
        self.tick_op, self.peaks, self.chips = tick_op, peaks, chips


def run_cell(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, control: bool = False,
             bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """Run one cell once and return its result line (as a dict)."""
    t_start = time.perf_counter()
    import jax

    from bench import devices, trace as trace_mod

    spec = resolve(bench, workload, bench_dir)
    cell_doc, doc, mix = spec["cell"], spec["config"], spec["mix"]
    dev = devices.device_line(cell_doc["chips"], require_tpu)
    used = jax.devices()[: cell_doc["chips"]]
    meter = devices.CompileMeter()
    cell = spec["source"](doc, mix, seed, spec["ref"])
    cell.setup_program(log)
    cell.warm(log, lambda: sum(meter.snapshot()[1:]))
    setup_s = time.perf_counter() - t_start
    m0 = meter.snapshot()
    log(f"set-up {setup_s:.3f} s, of which backend compile {m0[0]:.3f} s "
        f"({m0[1]} compiles; persistent cache {m0[2]} hits / {m0[3]} misses)")
    jax.config.update("jax_log_compiles", True)  # names any compile in the window

    tdir, traced = None, None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        ta = cell.counters()["ticks"]
        t_stop = time.perf_counter() + min(seconds, TRACE_S)
    c0 = cell.counters()
    w0 = time.perf_counter()
    while time.perf_counter() - w0 < seconds:
        with jax.profiler.TraceAnnotation("bench.round"):
            cell.round()
        if tdir is not None and traced is None and time.perf_counter() >= t_stop:
            traced = (ta, cell.counters()["ticks"])
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
    window_s = time.perf_counter() - w0
    c1 = cell.counters()
    if tdir is not None and traced is None:
        traced = (ta, c1["ticks"])
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    m1 = meter.snapshot()
    jax.config.update("jax_log_compiles", False)
    log(f"window {window_s:.3f} s: {c1['ticks'] - c0['ticks']} ticks, "
        f"{c1['steps'] - c0['steps']} stream-steps, {c1['queries'] - c0['queries']} "
        f"queries; compiles in the window {m1[1] - m0[1]} (cache hits "
        f"{m1[2] - m0[2]}, misses {m1[3] - m0[3]})")

    # query_share counts a fixed range of ticks, whatever the window held.
    share_lo = mix["warm_ticks"]
    share_hi = share_lo + mix["share_ticks"]
    cell.run_to(share_hi)
    cell.close()
    log(f"query share on ticks {share_lo} to {share_hi}: "
        f"{100 * cell.share(share_lo, share_hi):.4f}%")
    dev["memory_peak_bytes"] = devices.memory_peak_bytes(used)
    steps = c1["steps"] - c0["steps"]
    out = {"correct": False, "attempted": steps,
           "failed": (c1["dropped"] - c0["dropped"]) + (c1["lost"] - c0["lost"]),
           "metrics": {}, "device": dev}
    if trace:
        peaks = devices.peaks(dev["kind"]) if require_tpu else {
            "bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
        reading = trace_mod.Reading(trace_mod.compact(trace_mod.find_xplane(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = Traced(reading, cell.work(*traced), traced[1] - traced[0], cell.tick_op,
                     peaks, cell_doc["chips"])
        for m in spec["per_layer"]:
            v = spec["readers"][m["name"]].read(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"], dev["window_s"] = reading.busy_s, reading.window_s
        out["breakdown"] = {"device_ops": reading.top_modules(),
                            "idle_gaps": reading.idle_gaps()}
    else:
        names = {m["name"] for m in spec["end_to_end"]}
        e2e = {"stream_steps_per_s": (steps / window_s, "steps/s"),
               "query_share": (100.0 * cell.share(share_lo, share_hi), "%"),
               "setup_s": (setup_s, "s")}
        out["metrics"] = {k: {"value": v, "unit": u} for k, (v, u) in e2e.items()
                          if k in names}

    numbers = cell.check(control, log)
    limits = doc["limits"]
    out["correct"] = all(v <= limits[k] for k, v in numbers.items())  # NaN fails
    # A number that is not finite is printed as a string, so the line stays JSON.
    checks = {k: {"value": v if math.isfinite(v) else repr(float(v)), "limit": limits[k]}
              for k, v in numbers.items()}
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    out["check"] = checks
    return out
