#!/usr/bin/env python3
"""Chip smoke: drive the serve path once, end to end, on a TPU.

Run from the root of a checkout, on a host with a TPU::

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a four-chip host only

One chip (the default) runs these phases in this one process:

  a. the device JAX sees; anything but a TPU fails the run (no fallback);
  b. ``repro.launch.serve`` at the published widths of h2o-danube-1.8b,
     two tenants over one 32-stream decode batch, first with the in-process
     latency teacher and then with the RPC label server (a CPU-only child
     process, so the chip stays this process's);
  c. the paper's protocol on the HAR surrogate: ``stream.run`` with a
     zero-latency teacher against ``engine.run_fleet`` — bitwise equal on
     the CPU; this phase reports whether they still are on the chip;
  d. the three Pallas kernels, compiled (not interpreted) at the serve
     widths, against their ``kernels/ref.py`` oracles.

``--four-chips`` runs only the two paths that exist across chips: first a
``serve --workers 4`` fleet (one worker process per chip, with a live
migration and a drain) while this process stays off JAX, then, once the
workers are gone, ``serve --mesh-fleet 4`` at full width and the three
sharded fleet paths against their single-device runs.

Every phase prints its findings on lines of its own.  A failed check
raises, so the run exits non-zero; the last line, printed only when every
phase passed, is ``{"ok": true, "device": {...}}``.  Times are of one run
and are not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH, VARIANT = "h2o-danube-1.8b", "full"
BATCH, TOKENS, TENANTS = 32, 64, 2
SERVE_ARGS = [
    "--arch", ARCH, "--variant", VARIANT, "--batch", str(BATCH),
    "--tokens", str(TOKENS), "--tenants", str(TENANTS),
    "--teacher-latency", "2", "--backpressure", "coalesce",
    "--fuse-cohorts", "on",
]
# Kernel comparisons: a few bf16 ulps, set before any chip run — the MXU
# may take an f32 dot in one bf16 pass.
KERNEL_RTOL = 2.0 ** -6
# Sharded fleet paths against one device: a partitioning bug moves results
# by O(1); reassociated f32 sums move them by far less than this.
SHARD_RTOL = 1e-2


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """Backend compile seconds (persistent-cache loads included) and cache
    hits/misses, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.secs, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.secs, self.hits, self.misses


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def all_finite(*trees) -> bool:
    import jax
    import numpy as np

    return all(
        bool(np.isfinite(a).all())
        for t in trees
        for a in map(np.asarray, jax.tree.leaves(t))
        if np.issubdtype(a.dtype, np.floating)
    )


def device_line() -> dict:
    """Phase a: the device JAX found.  Fails unless it is a TPU."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"phase a: jax {jax.__version__}, {dev['count']} x {dev['kind']} "
          f"(platform {dev['platform']})", flush=True)
    check(dev["platform"] == "tpu",
          f"chip smoke needs a TPU; JAX found platform {dev['platform']!r}")
    return dev


def phase_serve(serve_args: list, teacher: str, meter: CompileMeter,
                tmp: str, want_steps: int) -> dict:
    """Phase b: one ``serve`` run through its CLI entry point; it must
    take ``want_steps`` stream steps."""
    import jax

    from repro.launch import serve

    path = os.path.join(tmp, f"serve_{teacher}.json")
    secs0, hits0, misses0 = meter.snapshot()
    t0 = time.perf_counter()
    serve.main(serve_args + ["--teacher", teacher, "--metrics-json", path])
    wall = time.perf_counter() - t0
    secs1, hits1, misses1 = meter.snapshot()
    with open(path) as f:
        doc = json.load(f)
    rep = doc["report"]
    check(rep["identity_ok"], f"serve[{teacher}]: accounting identity broke")
    issued, steps = rep["queries_issued"], rep["stream_steps"]
    check(steps == want_steps,
          f"serve[{teacher}]: {steps} stream steps, want {want_steps}")
    ticks = []
    for name, t in sorted(doc["tenants"].items()):
        check(t["reconciled"], f"serve[{teacher}] {name}: not reconciled")
        check(t["state_finite"], f"serve[{teacher}] {name}: state not finite")
        check(math.isfinite(t["tick_p50_ms"]) and
              math.isfinite(t["tick_p95_ms"]),
              f"serve[{teacher}] {name}: tick latency not finite")
        ticks.append(f"{name} {t['tick_p50_ms']:.3f}/{t['tick_p95_ms']:.3f}")
    if teacher == "rpc":
        ls = doc["label_server"] or {}
        check(ls.get("asks_served", 0) > 0,
              "serve[rpc]: the label server answered no asks")
    mem = jax.devices()[0].memory_stats() or {}
    agg = doc["aggregate"]
    print(f"phase b [{teacher} teacher]: serve {' '.join(serve_args)}")
    print(f"phase b [{teacher} teacher]: tick p50/p95 ms: "
          f"{'; '.join(ticks)}; queries {issued}/{steps} "
          f"({100 * issued / max(steps, 1):.2f}% comm volume); accounting ok",
          flush=True)
    if teacher == "rpc":
        print(f"phase b [rpc teacher]: label server (CPU child) answered "
              f"{ls['asks_served']} asks over {ls['connections_accepted']} "
              f"connection(s)", flush=True)
    print(f"phase b [{teacher} teacher]: one run, not a benchmark: wall "
          f"{wall:.3f} s, of which backend compile {secs1 - secs0:.3f} s "
          f"(persistent cache {hits1 - hits0} hits / {misses1 - misses0} "
          f"misses); serve loop {agg['wall_s']:.3f} s "
          f"({agg['steps_per_s']:.1f} stream-steps/s); device peak "
          f"{mem.get('peak_bytes_in_use', 0) / 1e9:.3f} GB", flush=True)
    return doc


def phase_har(seed: int, s: int) -> dict:
    """Phase c: stream.run (zero-latency teacher) against run_fleet on the
    paper's HAR protocol; reports bitwise equality, or how far apart."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import engine
    from repro.configs import har_odl
    from repro.core import drift, oselm
    from repro.data import har
    from repro.engine import stream

    # The detector armed as the repro's paper loop arms it
    # (examples/har_drift_demo.py); with the defaults no stream of this
    # surrogate ever drifts, so nothing would be queried or learned.
    cfg = dataclasses.replace(har_odl.full(), drift=drift.DriftConfig(
        warmup=48, k_sigma=3.0, enter_hits=2, exit_calm=64))
    data = har.generate(seed=seed)
    init_elm = oselm.init_state_batch(
        cfg.elm, jnp.asarray(data.train_x),
        jax.nn.one_hot(data.train_y, cfg.elm.n_out))

    def fresh():  # run_fleet donates its state on the chip: one each
        return engine.broadcast_streams(
            engine.init_state(cfg)._replace(elm=init_elm), s)

    ticks, labels = har.drift_tick_stream(
        data, n_streams=s, seed=seed, calm=400,
        severities=np.linspace(1.0, 3.0, s))
    xs = np.stack(list(ticks))
    t_len = xs.shape[0]
    t0 = time.perf_counter()
    st_f, out_f = engine.run_fleet(fresh(), jnp.asarray(xs),
                                   jnp.asarray(labels), cfg, mode="algo1")
    jax.block_until_ready(st_f)
    fleet_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    st_s, out_s, stats = stream.run(
        fresh(), iter(xs), cfg,
        stream.LatencyTeacher(stream.array_labels(labels), latency=0),
        mode="algo1")
    jax.block_until_ready(st_s)
    stream_s = time.perf_counter() - t0
    check(all_finite(st_f, out_f, st_s, out_s), "phase c: non-finite output")
    check(stats.reconciled, "phase c: stream accounting does not reconcile")
    check(stats.labels_applied > 0, "phase c: the protocol never learned")

    outs_equal = all(
        np.array_equal(np.asarray(getattr(out_f, f)),
                       np.asarray(getattr(out_s, f)))
        for f in out_f._fields)
    states_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(st_f), jax.tree.leaves(st_s)))
    flips = int((np.asarray(out_f.queried) != np.asarray(out_s.queried)).sum())
    d_beta = float(np.abs(np.asarray(st_f.elm.beta) -
                          np.asarray(st_s.elm.beta)).max())
    d_p = float(np.abs(np.asarray(st_f.elm.P) - np.asarray(st_s.elm.P)).max())
    res = {
        "bitwise": outs_equal and states_equal, "outputs_equal": outs_equal,
        "states_equal": states_equal, "max_abs_d_beta": d_beta,
        "max_abs_d_P": d_p, "query_flips": flips, "decisions": t_len * s,
    }
    acc = [float((np.asarray(o.pred) == labels).mean()) for o in (out_f, out_s)]
    comm = [float(np.asarray(o.queried).mean()) for o in (out_f, out_s)]
    print(f"phase c: HAR protocol (algo1), {s} streams x {t_len} ticks, "
          f"n_in {cfg.elm.n_in}, N {cfg.elm.n_hidden}: stream.run vs "
          f"run_fleet bitwise equal: {res['bitwise']} (outputs "
          f"{outs_equal}, state {states_equal}); max |d beta| {d_beta:.3e}, "
          f"max |d P| {d_p:.3e}; query decisions flipped {flips} of "
          f"{t_len * s}", flush=True)
    print(f"phase c: comm volume run_fleet {100 * comm[0]:.2f}% / stream.run "
          f"{100 * comm[1]:.2f}%; accuracy {100 * acc[0]:.2f}% / "
          f"{100 * acc[1]:.2f}%; one run, not a benchmark: run_fleet "
          f"{fleet_s:.3f} s, stream.run {stream_s:.3f} s (compile included)",
          flush=True)
    return res


def phase_kernels(s: int, n_in: int, n_hidden: int, n_out: int,
                  seed: int) -> dict:
    """Phase d: the three Pallas kernels, compiled, against their oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import xorshift
    from repro.kernels import ops, ref

    check(not ops._interpret(), "phase d: kernels would run interpreted")
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jnp.tanh(jax.random.normal(k[0], (s, n_in)))
    hseed = xorshift.DEFAULT_SEED

    def proj(x):
        return ops.xorshift_projection(x, hseed, n_hidden)

    check("tpu_custom_call" in jax.jit(proj).lower(x).as_text(),
          "phase d: xorshift projection did not lower to a Mosaic kernel")

    h0 = np.asarray(jnp.tanh(jax.random.normal(k[1], (4 * n_hidden, n_hidden))),
                    np.float64)
    p0 = np.linalg.inv(h0.T @ h0 + 1e-2 * np.eye(n_hidden)).astype(np.float32)
    beta0 = 0.1 * jax.random.normal(k[2], (n_hidden, n_out))
    y = jax.nn.one_hot(jax.random.randint(k[3], (s,), 0, n_out), n_out)
    errs = {}
    with jax.default_matmul_precision("highest"):
        h = proj(x)
        errs["xorshift_projection"] = rel_err(
            h, ref.xorshift_projection_ref(x, hseed, n_hidden))
        got = ops.oselm_rls_update(jnp.asarray(p0), beta0, h, y)
        want = ref.oselm_rls_update_ref(jnp.asarray(p0), beta0, h, y)
        errs["rls_update P"] = rel_err(got[0], want[0])
        errs["rls_update beta"] = rel_err(got[1], want[1])
        ps = jnp.broadcast_to(jnp.asarray(p0), (s, n_hidden, n_hidden))
        betas = jnp.broadcast_to(beta0, (s, n_hidden, n_out))
        got = ops.oselm_rls_update_fleet(ps, betas, h[:, None], y[:, None])
        want = jax.vmap(ref.oselm_rls_update_ref)(ps, betas, h[:, None],
                                                  y[:, None])
        errs["rls_update_fleet P"] = rel_err(got[0], want[0])
        errs["rls_update_fleet beta"] = rel_err(got[1], want[1])
        check(all_finite(h, got), "phase d: non-finite kernel output")
    print(f"phase d: Pallas kernels compiled for {jax.devices()[0].device_kind}"
          f" at S {s}, n_in {n_in}, N {n_hidden}; max relative error vs "
          f"kernels/ref.py: "
          + ", ".join(f"{n} {e:.3e}" for n, e in errs.items()), flush=True)
    for n, e in errs.items():
        check(e <= KERNEL_RTOL, f"phase d: {n} error {e:.3e} > {KERNEL_RTOL}")
    return errs


def one_chip(seed: int) -> dict:
    from repro.runtime import accel

    cache = accel.enable_compile_cache()
    dev = device_line()
    print(f"phase a: persistent compile cache at {cache}", flush=True)
    meter = CompileMeter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        for teacher in ("latency", "rpc"):
            phase_serve(SERVE_ARGS, teacher, meter, tmp,
                        want_steps=TENANTS * TOKENS * BATCH)
            gc.collect()  # the first run's 7 GB of weights must go first
    phase_har(seed, BATCH)
    phase_kernels(BATCH, 2560, 128, 6, seed)
    return dev


def phase_workers(fleet_args: list) -> dict:
    """Four chips, first half: ``serve --workers 4``, one worker process
    per chip, with a live migration and a drain, while this process stays
    off JAX."""
    from repro.launch import serve
    from repro.runtime import accel

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        path = os.path.join(tmp, "fleet.json")
        t0 = time.perf_counter()
        serve.main(fleet_args + ["--metrics-json", path])
        wall = time.perf_counter() - t0
        with open(path) as f:
            doc = json.load(f)
    check(not accel.backend_initialized(),
          "the smoke process touched JAX while its workers held the chips")
    devs = doc["devices"]
    check(doc["workers_spawned"] == 4 and len(devs) == 4 and all(
        d["platform"] == "tpu" and d["count"] == 1 for d in devs.values()),
        f"workers did not each hold one TPU chip: {devs}")
    agg = doc["aggregate"]
    check(agg["reconciled"] and all(doc["per_tenant_reconciled"].values()),
          "fleet accounting does not reconcile")
    print(f"four chips [workers]: 4 worker processes "
          f"({'; '.join(d['name'] for d in devs.values())}); "
          f"{agg['stream_steps']} steps, queries {agg['queries_issued']}, "
          f"re-asked {agg['tickets_reasked']}; migration + drain reconciled; "
          f"this process never initialized JAX; one run, not a benchmark: "
          f"{wall:.3f} s", flush=True)
    return doc


def phase_mesh_serve(serve_args: list) -> dict:
    """Four chips, second half: one tenant's streams sharded over a
    four-device mesh behind the full-width backbone."""
    from repro.launch import serve

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        path = os.path.join(tmp, "mesh.json")
        serve.main(serve_args + ["--mesh-fleet", "4", "--metrics-json", path])
        with open(path) as f:
            doc = json.load(f)
    rep = doc["report"]
    check(rep["identity_ok"] and rep["sessions"] == 4 and doc["shards"] == 4,
          "mesh serve: per-shard accounting broke")
    print(f"four chips [mesh serve]: serve {' '.join(serve_args)} "
          f"--mesh-fleet 4: queries {rep['queries_issued']}/"
          f"{rep['stream_steps']}, per-shard accounting ok", flush=True)
    return doc


def four_chips(seed: int) -> dict:
    from repro.runtime import accel

    chips = accel.host_tpu_chips()
    check(chips >= 4, f"--four-chips needs a four-chip TPU host; this host "
          f"has {chips} TPU chip(s)")
    accel.enable_compile_cache()
    phase_workers(["--workers", "4", "--tenants", "8", "--batch", "32",
                   "--tokens", "3000", "--fleet-ticks", "synth"])
    dev = device_line()
    check(dev["count"] == 4, f"--four-chips sees {dev['count']} device(s)")
    phase_mesh_serve(["--arch", ARCH, "--variant", VARIANT, "--batch",
                      str(BATCH), "--tokens", str(TOKENS),
                      "--teacher-latency", "2", "--backpressure", "coalesce"])
    gc.collect()
    mesh_parity(seed)
    return dev


def mesh_parity(seed: int, s: int = 128, t_len: int = 8) -> None:
    """The three sharded fleet paths against their single-device runs at
    equal S, at the backbone's width, with each shard's state on its own
    chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs, engine
    from repro.distributed import sharding
    from repro.engine import stream
    from repro.launch import mesh as mesh_lib
    from repro.models import model as model_lib

    cfg = model_lib.core_config(configs.get_config(ARCH, VARIANT))
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    xs = jnp.tanh(jax.random.normal(kx, (t_len, s, cfg.elm.n_in)))
    ys = np.asarray(jax.random.randint(ky, (t_len, s), 0, cfg.elm.n_out),
                    np.int32)
    xs_host = list(np.asarray(xs))
    ref, _ = engine.run_fleet(engine.init_fleet(cfg, s), xs, jnp.asarray(ys),
                              cfg, mode="train_phase", chunk=t_len)
    solo, _, _ = stream.run(
        engine.init_fleet(cfg, s), iter(xs_host), cfg,
        stream.LatencyTeacher(stream.array_labels(ys), latency=2),
        mode="train_phase", capacity=16, collect=False)
    mesh = mesh_lib.make_fleet_mesh(4)
    chips = set(mesh.devices.flat)
    width = s // 4
    with sharding.activate(mesh):
        placed, n_pad = engine.shard_fleet(engine.init_fleet(cfg, s), cfg)
        check(n_pad == 0, f"S={s} should divide four shards")
        check({sh.device for sh in placed.elm.P.addressable_shards} == chips,
              "GSPMD fleet state is not spread over the four chips")
        gspmd, _ = engine.run_fleet_sharded(placed, xs, jnp.asarray(ys), cfg,
                                            mode="train_phase", chunk=t_len)
        blocks = engine.split_fleet(engine.init_fleet(cfg, s), cfg)
        on = [jax.tree.leaves(b)[0].devices() for b in blocks.states]
        check(all(len(d) == 1 for d in on) and set().union(*on) == chips,
              f"shard-local blocks are not one per chip: {on}")
        blocks, _ = engine.run_fleet_shards(blocks, xs, jnp.asarray(ys), cfg,
                                            mode="train_phase", chunk=t_len)
        merged = engine.merge_fleet(blocks)
        sharded, _, stats_list = stream.run_sharded(
            engine.init_fleet(cfg, s), iter(xs_host), cfg,
            lambda k: stream.LatencyTeacher(
                stream.array_labels(ys[:, k * width:(k + 1) * width]),
                latency=2),
            mode="train_phase", capacity=16, collect=False)
    check(all(st.reconciled for st in stats_list),
          "run_sharded: a shard's accounting does not reconcile")
    pairs = {
        "run_fleet_sharded (GSPMD)": (gspmd, ref),
        "run_fleet_shards (shard-local)": (merged, ref),
        "stream.run_sharded": (sharded, solo),
    }
    for name, (got, want) in pairs.items():
        check(all_finite(got), f"{name}: non-finite state")
        beta = np.asarray(jax.device_get(got.elm.beta))[:s]
        p = np.asarray(jax.device_get(got.elm.P))[:s]
        equal = (np.array_equal(beta, np.asarray(want.elm.beta)) and
                 np.array_equal(p, np.asarray(want.elm.P)))
        e_beta = rel_err(beta, want.elm.beta)
        e_p = rel_err(p, want.elm.P)
        print(f"four chips [mesh parity]: {name} vs one device, S {s}, "
              f"n_in {cfg.elm.n_in}, {t_len} ticks: bitwise equal {equal}; "
              f"max relative error beta {e_beta:.3e}, P {e_p:.3e}",
              flush=True)
        check(max(e_beta, e_p) <= SHARD_RTOL,
              f"{name}: sharded run diverged from one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the worker-fleet and mesh paths of a "
                    "four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = four_chips(args.seed) if args.four_chips else one_chip(args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
