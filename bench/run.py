#!/usr/bin/env python3
"""The benchmark's one command.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` once, in this process, on the chip(s)
the cell asks for, and prints the result as the last line of standard
output: one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced), then ``check``,
each compared number beside its limit, which also ends standard error.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Off a TPU, or with fewer chips than
the cell needs, it prints no result and exits 2.

JAX's persistent compilation cache lives in ``.jax_cache`` at the root of
the checkout, so only a checkout's first run of a cell compiles.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    # The program takes its cache directory from this variable; set before
    # JAX is imported, at a fixed path inside the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.runtime import accel

    from bench import devices, harness

    accel.enable_compile_cache()
    bench = harness.load_benchmark(ROOT)
    try:
        out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                               bool(args.trace))
    except devices.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
