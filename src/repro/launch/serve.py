"""Serving driver: prefill -> decode loop with multi-tenant ODL cascades.

The backbone decodes once per tick; the per-tick stream features fan out to
``--tenants`` independent ODL fleets multiplexed over this process by
``repro.engine.multiplex`` — each tenant has its own engine state, pending
ring, teacher connection, and backpressure policy (``--backpressure``:
drop_oldest / drop_newest / block / coalesce), while all tenants share one
compiled plan/learn executable through the engine's bounded runner LRUs.
Streams whose P1P2 confidence clears auto-theta SKIP the teacher — the
paper's data pruning as a serving-compute/communication saver.  Tenants
run the engine's ``serve`` mode: the per-stream drift detector runs live
and a drifting stream is forced to query (pruning condition 2), exactly
the ``gate`` decision logic the single-tenant ``model.serve_step`` path
uses.  Teacher answers arrive asynchronously (out of order, possibly
partial) and are applied against the *plan-time* decision context, so a
delayed reply is judged by the prediction/threshold the query was issued
under.

``--teacher rpc`` swaps the in-process latency model for a real loopback
TCP label server (``repro.engine.rpc``), with wall-clock timeout → loss.
All tenants share **one** batched connection per teacher host
(``rpc.BatchedRpcClient``): asks landing within
``--teacher-batch-window`` ms (up to ``--teacher-batch-max``) coalesce
into a single length-prefixed binary frame, amortizing the per-query
round-trip the paper's pruning argument is about.  ``--teacher-secret``
arms the HMAC challenge–response handshake on both ends (an
unauthenticated label server is refused) — once per connection, not once
per tenant.  ``--teacher-compress`` wraps the binary frames in zlib
envelopes (negotiated in the handshake when a secret is set).

``--mesh-fleet N`` is the mega-fleet path: a single tenant's stream axis
shards over an N-device ``("fleet",)`` mesh — one shard-local session
(engine-state rows on device k, pending ring, teacher handle, plan/learn
dispatch) per device, a teacher answer learning back only into the shard
that planned the query (``stream.run_sharded``).  On a CPU host, force
devices first: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--sched drr`` replaces the fixed quantum-tick round robin with deficit
round robin in stream-step units, so a huge tenant cannot starve small
ones.

``--fuse-cohorts`` (default on) decides how tenants group in the one tick
engine (``repro.engine.cohort``): on, same-shaped tenants — same engine
config, mode, and stream width — tick as one cohort whose states ride a
single batched plan/learn dispatch per tick; off, every tenant ticks in a
group of its own, one dispatch per tenant.  Everything tenant-visible
(pending rings, teachers, backpressure, accounting, snapshots, migration)
stays per-tenant and bit-for-bit identical either way.

Durable sessions (``repro.engine.snapshot``): ``--snapshot-dir`` +
``--snapshot-every`` publish per-tenant session snapshots atomically
(keep-k) as the decode loop runs; ``--resume`` restores every tenant from
its latest published snapshot (replaying the backbone decode up to the
recorded tick cursor) — kill the process mid-serve and it continues where
it stopped.  ``--migrate`` demonstrates live tenant migration: tenant0 is
quiesced mid-stream, snapshotted, extracted from the running multiplexer,
and restored into a second multiplexer with a *fresh* teacher connection
(in-flight tickets re-asked and metered) — the query-accounting identity
must still reconcile, and the report proves it.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --tokens 32 \
      --tenants 2 --backpressure coalesce --teacher-latency 2 \
      --snapshot-dir /tmp/serve_ckpt --snapshot-every 8
"""

from __future__ import annotations

import argparse
import contextlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, engine
from repro.engine import multiplex, rpc, snapshot, stream
from repro.models import model as model_lib
from repro.runtime import accel, telemetry


def _decode_feats(params, state, prompts, cfg, gen_tokens):
    """Tick source: one backbone decode step per tick, yielding (B, d)
    stream features (greedy next-token feedback, ODL state untouched).
    When it ends, a backbone with an expert share exports its per-layer
    expert counters to the telemetry registry."""
    step = jax.jit(lambda p, st, t: model_lib.decode_step(p, st, t, cfg))
    tok = prompts[:, -1:]
    for _ in range(gen_tokens):
        logits, feats, state = step(params, state, tok)
        tok = jnp.argmax(logits, -1)[:, None]
        yield feats
    load = model_lib.expert_load(state)
    tel = telemetry.get()
    if load is not None and tel is not None:
        model_lib.sync_expert_counters(tel.registry, load, cfg)


def _all_finite(tree) -> bool:
    """Every floating leaf of a (device) pytree is finite."""
    return all(
        bool(np.isfinite(a).all())
        for a in map(np.asarray, jax.tree.leaves(tree))
        if np.issubdtype(a.dtype, np.floating)
    )


def _print_stream_report(parsed: dict) -> dict:
    """ONE render for every serve path's per-session counter block.

    ``parsed`` is a ``telemetry.parse_prometheus`` view — the same shape
    whether it came from this process's registry (solo / mesh) or a
    worker scrape (fleet) — so the three reports cannot drift apart.
    Prints one line per label set carrying stream counters and returns
    the summed counters plus ``identity_ok`` / ``sessions``.
    """
    ident = telemetry.check_stream_identity(parsed)
    fields = ("queries_issued", "stream_steps", "labels_applied",
              "queries_dropped", "queries_lost", "queries_coalesced",
              "replies_orphaned", "tickets_reasked")
    totals = dict.fromkeys(fields, 0)
    for key in sorted(ident):
        def g(f, key=key):
            return int(parsed.get((f"odl_stream_{f}", key), 0))
        who = ",".join(
            f"{k}{v}" if k in ("shard", "cohort") else v for k, v in key
        ) or "session"
        recon = "ok" if ident[key] else "BROKEN"
        issued, steps = g("queries_issued"), g("stream_steps")
        print(f"{who}: queries {issued}/{steps} "
              f"({100 * issued / max(steps, 1):.1f}% comm volume), "
              f"labels {g('labels_applied')}, dropped {g('queries_dropped')}, "
              f"lost {g('queries_lost')}, coalesced {g('queries_coalesced')}, "
              f"orphaned {g('replies_orphaned')}, "
              f"reasked {g('tickets_reasked')}, accounting {recon}")
        for f in fields:
            totals[f] += g(f)
    totals["identity_ok"] = bool(ident) and all(ident.values())
    totals["sessions"] = len(ident)
    return totals


def _print_label_server_stats(ls: dict) -> None:
    """The label server's own counters, scraped over the wire
    (``rpc.server_stats``) — the server runs as a subprocess, so this is
    the only way the final report can include its side of the ledger."""
    comp = ""
    if ls.get("frames_compressed"):
        win_in = ls["raw_bytes_in"] / max(ls["compressed_bytes_in"], 1)
        win_out = ls["raw_bytes_out"] / max(ls["compressed_bytes_out"], 1)
        comp = (f", compression x{win_in:.1f} in / x{win_out:.1f} out over "
                f"{ls['frames_compressed']} frames")
    print(f"label server: {ls['asks_served']} asks "
          f"({ls['frames_v2']} v2 frames, {ls['requests_v1']} v1 requests), "
          f"frame errors {ls['frame_errors']}, auth failures "
          f"{ls['auth_failures']}, {ls['connections_accepted']} "
          f"connection(s), {ls['thread_count']} live thread(s){comp}")


def _write_metrics_json(path: str, doc: dict, traces: dict = None) -> None:
    """``--metrics-json``: machine-readable run metrics, plus one Chrome
    ``trace_event`` file per traced process (load it in chrome://tracing
    or https://ui.perfetto.dev)."""
    import json as json_mod

    with open(path, "w") as f:
        json_mod.dump(doc, f, indent=2, sort_keys=True, default=str)
    written = [path]
    for tag, trace in (traces or {}).items():
        tpath = f"{path}.{tag}.trace.json" if tag else f"{path}.trace.json"
        with open(tpath, "w") as f:
            json_mod.dump(trace, f)
        written.append(tpath)
    print(f"metrics written: {', '.join(written)}")


def _serve_mesh(cfg, odl_cfg, params, state, prompts, *, mesh_fleet, batch,
                gen_tokens, seed, teacher, teacher_latency, teacher_jitter,
                teacher_loss, pending_capacity, backpressure, rpc_timeout_s,
                teacher_batch_window_s, teacher_batch_max, teacher_secret,
                teacher_compress, metrics_json=None):
    """Mega-fleet path: one tenant, its stream axis sharded over a
    ``("fleet",)`` mesh — one shard-local session (pending ring, teacher
    connection, plan/learn dispatch) per device, a label learning back
    only into the shard that planned it (``stream.run_sharded``).  On a
    CPU host, force the device count first:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    from repro.distributed import sharding
    from repro.launch import mesh as mesh_lib

    fleet_mesh = mesh_lib.make_fleet_mesh(mesh_fleet)
    ticks = _decode_feats(params, state, prompts, cfg, gen_tokens)
    with contextlib.ExitStack() as stack:
        if teacher == "rpc":
            host, port = stack.enter_context(
                rpc.loopback_server(n_out=cfg.odl.n_out, secret=teacher_secret)
            )
            # One shared batched connection; each shard gets its own tenant
            # handle — shard asks coalesce into single frames on one socket
            # without breaking shard locality (the demux is per-handle).
            client = rpc.BatchedRpcClient(
                host, port, timeout_s=rpc_timeout_s, secret=teacher_secret,
                batch_window_s=teacher_batch_window_s,
                batch_max=teacher_batch_max, compress=teacher_compress,
            )
            stack.callback(client.close)

            def teachers(k):
                return client.tenant(name=f"shard{k}")
        else:
            def teachers(k):
                rng = np.random.default_rng(seed + k)
                return stream.LatencyTeacher(
                    label_fn=lambda tick, feats: rng.integers(
                        0, cfg.odl.n_out, size=np.asarray(feats).shape[0]
                    ),
                    latency=teacher_latency, jitter=teacher_jitter,
                    loss_prob=teacher_loss, seed=seed + k,
                )

        with sharding.activate(fleet_mesh):
            n_shards = sharding.fleet_axis_size()
            st, _, stats_list = stream.run_sharded(
                engine.init_fleet(odl_cfg, batch), ticks, odl_cfg, teachers,
                mode="serve", capacity=pending_capacity,
                backpressure=backpressure, collect=False,
            )
        rpc_bytes = client.wire_bytes if teacher == "rpc" else None
        label_server_stats = None
        if teacher == "rpc":
            client.sync_telemetry()
            label_server_stats = rpc.server_stats(host, port,
                                                  secret=teacher_secret)

    tel = telemetry.get() or telemetry.enable()
    for k, s in enumerate(stats_list):
        telemetry.sync_stream_stats(tel.registry, s, pending=0, shard=str(k))
    report = _print_stream_report(
        telemetry.parse_prometheus(tel.registry.prometheus_text()))
    queries = report["queries_issued"]
    skips = report["stream_steps"] - report["queries_issued"]
    if not report["identity_ok"]:
        raise AssertionError(
            "shard query accounting does not reconcile: "
            + "; ".join(s.summary() for s in stats_list))
    if label_server_stats is not None:
        _print_label_server_stats(label_server_stats)
    agg = stream.aggregate_stats(
        stats_list, padded_streams=(-batch) % max(n_shards, 1))
    meter_kb = float(np.asarray(st.meter.total).sum()) / 1e3
    rpc_note = f"; rpc wire {rpc_bytes / 1e3:.1f} kB" if rpc_bytes else ""
    print(f"mesh aggregate: {n_shards} shard(s) x {gen_tokens} tokens x "
          f"{batch} streams = {agg['stream_steps']} steps in "
          f"{agg['wall_s']:.2f}s ({agg['steps_per_s']:,.0f} steps/s); "
          f"padded {agg['padded_streams']} dead rows; "
          f"backpressure={backpressure}, teacher={teacher}"
          f"{rpc_note}; {meter_kb:.1f} kB metered")
    if metrics_json:
        _write_metrics_json(metrics_json, {
            "mode": "mesh", "shards": n_shards, "tokens": gen_tokens,
            "report": report, "aggregate": agg,
            "prometheus": tel.registry.prometheus_text(),
            "registry": tel.registry.snapshot(),
            "label_server": label_server_stats,
        }, {"": tel.tracer.chrome_trace()})
    return queries, skips


def serve_fleet(workers: int = 2, tenants: int = 4, batch: int = 4,
                gen_tokens: int = 2000, fleet_ticks: str = "synth",
                arch: str = "qwen3-4b", variant: str = "smoke", seed: int = 0,
                tick_sleep_ms: float = 1.0, teacher_latency: int = 2,
                teacher_jitter: int = 1, teacher_loss: float = 0.0,
                pending_capacity: int = 8, backpressure: str = "drop_oldest",
                worker_capacity: int = None, migrate: bool = True,
                drain: bool = True, snapshot_full_every: int = 8,
                metrics_json: str = None):
    """Elastic fleet path (``--workers N``): spin ``workers`` multiplexer
    worker subprocesses behind a shape-aware router
    (``repro.runtime.elastic``), admit ``tenants`` tenants by
    compiled-shape affinity, optionally live-migrate one mid-stream and
    drain a whole worker (scale-in), then reconcile the fleet-wide
    query-accounting identity across every migration."""
    import time as time_mod

    from repro.runtime import elastic
    from repro.runtime import worker as worker_mod

    if fleet_ticks == "decode":
        # Real backbone decode ticks: each worker builds (and caches) the
        # backbone once per distinct spec; n_in is the model dim.
        odl_cfg = model_lib.core_config(configs.get_config(arch, variant))
        ticks_spec = {"kind": "decode", "arch": arch, "variant": variant,
                      "batch": batch, "prompt_len": 16, "max_len": 128,
                      "seed": seed, "t_total": gen_tokens,
                      "tick_sleep_ms": tick_sleep_ms}
    else:
        # Synthetic per-tick-seeded features: O(1) seek, identical bytes in
        # any process — the migration-parity default.
        from repro.core import drift as drift_mod
        from repro.core import oselm, pruning

        odl_cfg = engine.EngineConfig(
            elm=oselm.OSELMConfig(n_in=16, n_hidden=16, n_out=4,
                                  variant="hash", ridge=1e-2),
            prune=pruning.PruneConfig(min_trained=1_000_000),
            drift=drift_mod.DriftConfig(),
        )
        ticks_spec = None  # per-tenant (distinct seeds), built below

    if worker_capacity is None:
        # Spread evenly so same-shape tenants split into fused cohorts
        # instead of all packing onto the first worker.
        worker_capacity = max(1, -(-tenants // workers))

    # One worker per chip, and the router itself stays off JAX: a router
    # holding the chips would leave none for its workers.
    chips = accel.host_tpu_chips()
    if chips and workers > chips:
        raise ValueError(f"--workers {workers} needs a TPU chip each; this "
                         f"host has {chips}")
    if chips and accel.backend_initialized():
        raise RuntimeError("the fleet router already holds a JAX backend; "
                           "its workers could not claim their chips")
    # Router-side telemetry: migrate.ship spans land in THIS process's
    # trace; each worker keeps its own registry, scraped over the control
    # socket (router.fleet_metrics).
    tel = telemetry.enable()
    tel.registry.clear()
    fleet = [elastic.spawn_worker(f"w{i}", chip=i) for i in range(workers)]
    router = elastic.Router(fleet, capacity=worker_capacity)
    collected: dict = {}
    try:
        devices = {w.name: w.status()["device"] for w in fleet}
        for name, dev in devices.items():
            print(f"worker {name} holds {dev['name']} ({dev['kind']})")
        specs = []
        for i in range(tenants):
            spec = worker_mod.tenant_spec(
                f"tenant{i}", odl_cfg, s=batch, mode="serve",
                capacity=pending_capacity, backpressure=backpressure,
                ticks=(ticks_spec or worker_mod.synth_ticks_spec(
                    seed=seed + 100 + i, t_total=gen_tokens,
                    tick_sleep_ms=tick_sleep_ms)),
                teacher=worker_mod.latency_teacher_spec(
                    n_out=odl_cfg.elm.n_out, latency=teacher_latency,
                    jitter=teacher_jitter, loss=teacher_loss, seed=seed + i),
            )
            specs.append(spec)
            w = router.admit(spec)
            print(f"placed {spec['name']} -> {w.name} "
                  f"(shape key {worker_mod.spec_shape_key(spec)})")

        if migrate:
            # Live migration: wait until tenant0 is mid-stream, then move it.
            target = max(2, gen_tokens // 2)
            deadline = time_mod.monotonic() + 300
            while time_mod.monotonic() < deadline:
                src = router.worker_of("tenant0")
                st = src.status()
                row = next((t for t in st["live"] if t["name"] == "tenant0"),
                           None)
                if row is None:
                    raise RuntimeError(
                        "tenant0 finished before the migration point "
                        "(--tokens too small): the requested migration did "
                        "not happen")
                if row["t"] >= target:
                    dst = router.migrate("tenant0")
                    print(f"tenant0 migrated {src.name} -> {dst.name} "
                          f"at tick >= {row['t']}")
                    break
                time_mod.sleep(0.02)
            else:
                raise TimeoutError("tenant0 never reached the migration "
                                   f"point (tick {target})")

        # Mid-run live scrape: every worker's registry over the control
        # socket, while tenants still stream.  The scraped identity
        # (issued == applied + dropped + lost + coalesced + pending) must
        # close at this instant — the CI observability smoke rides this.
        scrape = router.fleet_metrics()
        midrun = {}
        for wname, h in scrape["workers"].items():
            midrun.update(telemetry.check_stream_identity(
                telemetry.parse_prometheus(h["prometheus"])))
        print(f"mid-run scrape: {len(midrun)} tenant series across "
              f"{len(scrape['workers'])} worker(s), identity "
              f"{'ok' if midrun and all(midrun.values()) else 'BROKEN'}")
        if not midrun or not all(midrun.values()):
            raise AssertionError(
                f"mid-run scraped query accounting broken: {midrun}")

        if drain and len(router.workers) > 1:
            victim = router.workers[-1]
            moved, finished = router.scale_in(victim)
            collected.update(finished)
            print(f"scale-in: drained {victim.name} "
                  f"(migrated {moved or 'nothing'}; "
                  f"{len(finished)} finished there)")

        router.wait_finished([s["name"] for s in specs], timeout_s=600)

        # Final scrape (with traces when they'll be written) BEFORE the
        # workers go away, then ONE render over the collected stats — the
        # same `_print_stream_report` the solo and mesh paths use, fed
        # through a registry so the fleet report cannot drift from them.
        final_scrape = router.fleet_metrics(trace=bool(metrics_json))
        for w in router.workers:
            collected.update(w.report())
        reg = telemetry.Registry()
        for name, s in collected.items():
            telemetry.sync_stream_stats(
                reg, worker_mod.stats_from_wire(s), pending=0, tenant=name)
        report = _print_stream_report(
            telemetry.parse_prometheus(reg.prometheus_text()))
        agg = elastic.reconcile(collected)
        recon = "ok" if agg["reconciled"] else "BROKEN"
        print(f"fleet aggregate: {len(collected)} tenant(s) over "
              f"{workers} worker(s) -> {len(router.workers)} after scale-in; "
              f"{agg['stream_steps']} steps, queries {agg['queries_issued']}, "
              f"labels {agg['labels_applied']}, dropped "
              f"{agg['queries_dropped']}, lost {agg['queries_lost']}, "
              f"coalesced {agg['queries_coalesced']}, reasked "
              f"{agg['tickets_reasked']}, accounting {recon}")
        if not agg["reconciled"] or not all(agg["per_tenant"].values()):
            raise AssertionError(
                f"fleet query accounting does not reconcile: {agg}")
        if not report["identity_ok"]:
            raise AssertionError(
                "scraped metrics identity does not hold at end of run")
        if metrics_json:
            traces = dict(final_scrape["traces"])
            traces["router"] = tel.tracer.chrome_trace()
            _write_metrics_json(metrics_json, {
                "mode": "fleet", "workers_spawned": workers,
                "devices": devices,
                "workers": final_scrape["workers"],
                "report": report,
                "aggregate": {k: v for k, v in agg.items()
                              if k != "per_tenant"},
                "per_tenant_reconciled": agg["per_tenant"],
                "midrun_series": len(midrun),
            }, traces)
        return agg["queries_issued"], agg["stream_steps"] - agg["queries_issued"]
    finally:
        router.close()


def serve(arch: str, variant: str = "smoke", batch: int = 4, prompt_len: int = 16,
          gen_tokens: int = 32, max_len: int = 128, seed: int = 0,
          teacher_latency: int = 1, teacher_jitter: int = 0,
          teacher_loss: float = 0.0, pending_capacity: int = 8,
          tenants: int = 1, backpressure: str = "drop_oldest",
          teacher: str = "latency", rpc_timeout_s: float = 5.0,
          teacher_batch_window_s: float = rpc.DEFAULT_BATCH_WINDOW_S,
          teacher_batch_max: int = rpc.DEFAULT_BATCH_MAX,
          teacher_secret: str = None, sched: str = "rr",
          snapshot_dir: str = None, snapshot_every: int = 0,
          resume: bool = False, migrate: bool = False,
          fuse_cohorts: bool = True, teacher_compress: bool = False,
          mesh_fleet: int = 0, metrics_json: str = None):
    # Driver-level telemetry: spans + mirrored counters for this process.
    # The stream bench gates the instrumented overhead at <2%, so it is
    # on by default here.  Cleared per run — serve() may be called twice
    # in one process (tests) and stale tenant series must not leak.
    tel = telemetry.enable()
    tel.registry.clear()
    cfg = configs.get_config(arch, variant)
    key = jax.random.PRNGKey(seed)
    params = model_lib.layers.init_params(model_lib.build_schema(cfg), key)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)

    _, state = jax.jit(
        lambda p, t: model_lib.prefill(p, t, cfg, max_len=max_len)
    )(params, prompts)

    odl_cfg = model_lib.core_config(cfg)
    if mesh_fleet:
        if tenants != 1:
            raise ValueError(
                "--mesh-fleet shards ONE fleet's stream axis across devices; "
                "it does not compose with --tenants > 1 (run one sharded "
                "process per tenant instead)")
        if snapshot_dir is not None or resume or migrate:
            raise ValueError(
                "--mesh-fleet does not compose with snapshots/resume/migrate "
                "(per-shard sessions are not snapshot-capable yet)")
        return _serve_mesh(
            cfg, odl_cfg, params, state, prompts, mesh_fleet=mesh_fleet,
            batch=batch, gen_tokens=gen_tokens, seed=seed,
            teacher=teacher, teacher_latency=teacher_latency,
            teacher_jitter=teacher_jitter, teacher_loss=teacher_loss,
            pending_capacity=pending_capacity, backpressure=backpressure,
            rpc_timeout_s=rpc_timeout_s,
            teacher_batch_window_s=teacher_batch_window_s,
            teacher_batch_max=teacher_batch_max,
            teacher_secret=teacher_secret, teacher_compress=teacher_compress,
            metrics_json=metrics_json,
        )
    durable = snapshot_dir is not None
    # One backbone decode feeds every tenant: tee the tick source N ways
    # (the scheduler keeps tenants within one time slice of each other, so
    # the tee buffer stays bounded by the quantum).
    shared = itertools.tee(
        _decode_feats(params, state, prompts, cfg, gen_tokens), tenants
    )
    if durable:
        # Durability additionally needs a *seekable* source per tenant: the
        # live path keeps sharing the one tee'd decode (cursor 0), and only
        # an actual resume (cursor k > 0) pays for a fresh decode replayed
        # to the snapshot's tick cursor — the backbone is deterministic.
        # (Caveat: a tenant that resumes leaves its tee branch unconsumed,
        # pinning the tee buffer for this run — fine at serve scale, and
        # only on runs that actually resumed.)
        def make_feed(branch):
            def factory(start, branch=branch):
                if start == 0:
                    return branch
                return itertools.islice(
                    _decode_feats(params, state, prompts, cfg, gen_tokens),
                    start, None,
                )
            return snapshot.ResumableTicks(factory)

        feeds = [make_feed(b) for b in shared]
    else:
        feeds = shared

    with contextlib.ExitStack() as stack:
        def make_teacher(i):
            if teacher == "rpc":
                # Only the migration path lands here: a migrated tenant is
                # conceptually on a new host, so it gets a FRESH shared
                # connection (own handshake), not a handle on the old one.
                client = rpc.BatchedRpcClient(
                    host, port, timeout_s=rpc_timeout_s, secret=teacher_secret,
                    batch_window_s=teacher_batch_window_s,
                    batch_max=teacher_batch_max, compress=teacher_compress,
                )
                stack.callback(client.close)
                return client.tenant(name=f"tenant{i}")
            # The smoke teacher predicts random classes (a real deployment
            # points label_fn at the pod-side backbone ensemble);
            # latency/jitter/loss model the BLE/network round-trip in
            # decode ticks, per tenant.
            rng = np.random.default_rng(seed + i)
            return stream.LatencyTeacher(
                label_fn=lambda tick, feats: rng.integers(
                    0, cfg.odl.n_out, size=batch
                ),
                latency=teacher_latency, jitter=teacher_jitter,
                loss_prob=teacher_loss, seed=seed + i,
            )

        if teacher == "rpc":
            host, port = stack.enter_context(
                rpc.loopback_server(n_out=cfg.odl.n_out, secret=teacher_secret)
            )
            # Default transport: every tenant with the same endpoint shares
            # one batched connection — one socket, one HMAC handshake, asks
            # coalesced into single frames within the flush window.
            rpc_teachers, rpc_clients = multiplex.shared_rpc_teachers(
                [(host, port)] * tenants, timeout_s=rpc_timeout_s,
                secret=teacher_secret, batch_window_s=teacher_batch_window_s,
                batch_max=teacher_batch_max, compress=teacher_compress,
            )
            for client in rpc_clients:
                stack.callback(client.close)
            teachers = {f"tenant{i}": t for i, t in enumerate(rpc_teachers)}
        else:
            teachers = {f"tenant{i}": make_teacher(i) for i in range(tenants)}

        tenant_list = [
            multiplex.Tenant(
                name=f"tenant{i}",
                state=engine.init_fleet(odl_cfg, batch),
                ticks=feeds[i],
                cfg=odl_cfg,
                teacher=teachers[f"tenant{i}"],
                mode="serve",  # gate semantics: live drift detector,
                # condition-2 forced queries, controller always armed
                capacity=pending_capacity,
                backpressure=backpressure,
                collect=False,  # long-running servers keep no history
            )
            for i in range(tenants)
        ]
        if resume and snapshot_dir is None:
            raise ValueError("--resume needs --snapshot-dir (nothing to "
                             "restore from otherwise)")
        mux = multiplex.Multiplexer(
            tenant_list, sched=sched, snapshot_dir=snapshot_dir,
            snapshot_every=snapshot_every, resume=resume, fuse=fuse_cohorts,
            # Migration wants to stop mid-stream: schedule tick by tick so
            # the threshold check below lands before the stream drains.
            quantum=1 if migrate else multiplex.DEFAULT_QUANTUM,
        )
        if migrate:
            # Live migration demo: run until tenant0 is mid-stream, quiesce
            # + snapshot + extract it, restore it into a second multiplexer
            # behind a FRESH teacher (a migration lands on a new host: the
            # old socket/object is gone), finish both, merge the reports.
            while mux.round():
                if mux.session("tenant0").t >= max(2, gen_tokens // 2):
                    break
            if mux.finished("tenant0"):
                raise RuntimeError(
                    "tenant0 finished before the migration point (--tokens "
                    "too small): the requested migration did not happen")
            tree, rest_ticks = mux.extract("tenant0")
            results, agg = mux.run()  # finish the remaining tenants
            fresh = make_teacher(0)
            teachers["tenant0"] = fresh
            # pending="reask": the destination teacher is a new connection
            # on a (conceptually) new host — never restore the old teacher's
            # state into it, re-ask whatever is still in flight.
            mux_b = multiplex.Multiplexer([], sched=sched, pending="reask",
                                          fuse=fuse_cohorts)
            mux_b.admit(
                multiplex.Tenant(
                    name="tenant0", state=None, ticks=rest_ticks, cfg=odl_cfg,
                    teacher=fresh, mode="serve", capacity=pending_capacity,
                    backpressure=backpressure, collect=False,
                ),
                snapshot=tree,
                positioned=True,  # rest_ticks is extract()'s live iterator
            )
            results_b, agg_b = mux_b.run()
            migrated = results_b["tenant0"]
            print(f"tenant0 migrated at tick {snapshot.ticks_consumed(tree)} "
                  f"(re-asked {migrated.stats.tickets_reasked} in-flight "
                  f"tickets through the fresh teacher)")
            results = {**results, "tenant0": migrated}
            agg.stream_steps += agg_b.stream_steps
            agg.ticks += agg_b.ticks
            agg.wall_s += agg_b.wall_s
            agg.n_tenants = tenants
        else:
            results, agg = mux.run()

        # Pull every meter into the registry while the teachers are still
        # alive, and scrape the label server's own counters over the wire
        # (it is a subprocess — this is the only way to see them).
        mux.sync_telemetry()
        if migrate:
            mux_b.sync_telemetry()
        label_server_stats = None
        if teacher == "rpc":
            for client in rpc_clients:
                client.sync_telemetry()
            label_server_stats = rpc.server_stats(host, port,
                                                  secret=teacher_secret)

    # ONE render over the registry view — shared with the mesh and fleet
    # paths, so the per-tenant counter block cannot drift between them.
    report = _print_stream_report(
        telemetry.parse_prometheus(tel.registry.prometheus_text()))
    queries = report["queries_issued"]
    skips = report["stream_steps"] - report["queries_issued"]
    for name in sorted(results):  # details the registry doesn't carry
        r = results[name]
        s = r.stats
        meter_kb = float(np.asarray(r.state.meter.total).sum()) / 1e3
        rpc_note = (
            f"; rpc timeouts {teachers[name].timed_out}"
            if teacher == "rpc" else ""
        )
        print(f"  {name}: tick p50/p95 {s.tick_p50_ms:.2f}/{s.tick_p95_ms:.2f}"
              f" ms; label latency p50/p95 {s.label_latency_p50:.0f}/"
              f"{s.label_latency_p95:.0f} ticks; "
              f"{meter_kb:.1f} kB metered{rpc_note}")
        if not s.reconciled:
            raise AssertionError(f"{name}: query accounting does not reconcile: "
                                 f"{s.summary()}")
    if not report["identity_ok"]:
        raise AssertionError("scraped metrics identity does not hold")
    if label_server_stats is not None:
        _print_label_server_stats(label_server_stats)
    caches = stream.cache_stats()["plan_runner"]
    extras = f", sched={sched}"
    if durable:
        extras += f", snapshots under {snapshot_dir} every {snapshot_every} ticks"
    print(f"aggregate: {tenants} tenant(s) x {gen_tokens} tokens x {batch} streams "
          f"= {agg.stream_steps} steps in {agg.wall_s:.2f}s "
          f"({agg.steps_per_s:,.0f} steps/s); backpressure={backpressure}, "
          f"teacher={teacher}{extras}; plan-runner cache "
          f"{caches['hits']} hits / {caches['misses']} misses "
          f"(tenants share executables)")
    if metrics_json:
        _write_metrics_json(metrics_json, {
            "mode": "solo", "tenants": tenants, "tokens": gen_tokens,
            "report": report,
            "aggregate": {"stream_steps": agg.stream_steps,
                          "wall_s": agg.wall_s,
                          "steps_per_s": agg.steps_per_s},
            "tenants": {name: {"tick_p50_ms": r.stats.tick_p50_ms,
                               "tick_p95_ms": r.stats.tick_p95_ms,
                               "reconciled": r.stats.reconciled,
                               "state_finite": _all_finite(r.state)}
                        for name, r in results.items()},
            "prometheus": tel.registry.prometheus_text(),
            "registry": tel.registry.snapshot(),
            "label_server": label_server_stats,
        }, {"": tel.tracer.chrome_trace()})
    return queries, skips


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=configs.ARCH_IDS)
    ap.add_argument("--variant", default="smoke")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tenants", type=int, default=1,
                    help="independent ODL fleets multiplexed over this process")
    ap.add_argument("--backpressure", default="drop_oldest",
                    choices=stream.BACKPRESSURE_POLICIES,
                    help="pending-ring saturation policy (per tenant)")
    ap.add_argument("--sched", default="rr", choices=multiplex.SCHEDULERS,
                    help="rr: fixed quantum-tick round robin; drr: deficit "
                    "round robin in stream-step units (size-fair)")
    ap.add_argument("--fuse-cohorts", default="on", choices=("on", "off"),
                    help="on: same-shaped tenants tick as one cohort, one "
                    "batched plan/learn dispatch per tick; off: every tenant "
                    "ticks in a group of its own (bit-for-bit identical)")
    ap.add_argument("--teacher", default="latency", choices=("latency", "rpc"),
                    help="latency: in-process tick-granular model; "
                    "rpc: loopback TCP label server with timeout->loss")
    ap.add_argument("--teacher-latency", type=int, default=1,
                    help="teacher answer latency in decode ticks")
    ap.add_argument("--teacher-jitter", type=int, default=0,
                    help="extra uniform per-ticket latency in [0, J] ticks")
    ap.add_argument("--teacher-loss", type=float, default=0.0,
                    help="fraction of tickets silently lost by the teacher")
    ap.add_argument("--teacher-secret", default=None,
                    help="shared secret: HMAC-authenticate the rpc teacher "
                    "connection (both ends)")
    ap.add_argument("--rpc-timeout", type=float, default=5.0,
                    help="rpc teacher reply deadline in wall seconds")
    ap.add_argument("--teacher-batch-window", type=float,
                    default=rpc.DEFAULT_BATCH_WINDOW_S * 1e3,
                    help="rpc ask-coalescing flush window in ms (asks from "
                    "all tenants landing within it ride one frame; 0 sends "
                    "one frame per ask)")
    ap.add_argument("--teacher-batch-max", type=int,
                    default=rpc.DEFAULT_BATCH_MAX,
                    help="max asks coalesced into one rpc frame")
    ap.add_argument("--teacher-compress", action="store_true",
                    help="wrap rpc frames in zlib envelopes (negotiated in "
                    "the HMAC handshake when --teacher-secret is set)")
    ap.add_argument("--mesh-fleet", type=int, default=0,
                    help="shard the (single) tenant's stream axis over this "
                    "many devices on a ('fleet',) mesh — one shard-local "
                    "session (ring + teacher + dispatch) per device; 0: off "
                    "(on CPU, force devices via "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--pending-capacity", type=int, default=8,
                    help="in-flight query ring capacity (see --backpressure)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="publish per-tenant session snapshots here "
                    "(atomic, keep-k) — enables --resume")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot cadence in decode ticks (0: only explicit)")
    ap.add_argument("--resume", action="store_true",
                    help="restore every tenant from its latest published "
                    "snapshot under --snapshot-dir")
    ap.add_argument("--migrate", action="store_true",
                    help="demo: quiesce+snapshot tenant0 mid-stream and "
                    "restore it into a second multiplexer behind a fresh "
                    "teacher connection")
    ap.add_argument("--workers", type=int, default=0,
                    help="elastic fleet mode: spin this many multiplexer "
                    "worker subprocesses behind the shape-aware router "
                    "(repro.runtime.elastic); 0: single-process serve")
    ap.add_argument("--fleet-ticks", default="synth",
                    choices=("synth", "decode"),
                    help="fleet tick source: synth (per-tick-seeded "
                    "features, O(1) seek) or decode (each worker drives "
                    "the real backbone)")
    ap.add_argument("--fleet-tick-sleep", type=float, default=1.0,
                    help="per-tick sleep in ms (keeps tenants mid-stream "
                    "long enough to migrate)")
    ap.add_argument("--worker-capacity", type=int, default=None,
                    help="max live tenants per worker (default: spread "
                    "--tenants evenly over --workers)")
    ap.add_argument("--no-fleet-migrate", action="store_true",
                    help="fleet mode: skip the mid-stream live migration")
    ap.add_argument("--no-fleet-drain", action="store_true",
                    help="fleet mode: skip the scale-in worker drain")
    ap.add_argument("--snapshot-full-every", type=int, default=8,
                    help="worker cadence saves ship only changed leaves; "
                    "every k-th save is full (1: all saves full)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write run metrics (registry snapshot + Prometheus "
                    "text + per-tenant report) to PATH, plus Chrome "
                    "trace_event files PATH.<tag>.trace.json")
    args = ap.parse_args(argv)
    accel.enable_compile_cache()
    if args.workers:
        return serve_fleet(
            workers=args.workers, tenants=args.tenants, batch=args.batch,
            gen_tokens=args.tokens, fleet_ticks=args.fleet_ticks,
            arch=args.arch, variant=args.variant,
            tick_sleep_ms=args.fleet_tick_sleep,
            teacher_latency=args.teacher_latency,
            teacher_jitter=args.teacher_jitter,
            teacher_loss=args.teacher_loss,
            pending_capacity=args.pending_capacity,
            backpressure=args.backpressure,
            worker_capacity=args.worker_capacity,
            migrate=not args.no_fleet_migrate,
            drain=not args.no_fleet_drain,
            snapshot_full_every=args.snapshot_full_every,
            metrics_json=args.metrics_json)
    return serve(
        args.arch, args.variant, batch=args.batch, gen_tokens=args.tokens,
        teacher_latency=args.teacher_latency, teacher_jitter=args.teacher_jitter,
        teacher_loss=args.teacher_loss, pending_capacity=args.pending_capacity,
        tenants=args.tenants, backpressure=args.backpressure,
        teacher=args.teacher, rpc_timeout_s=args.rpc_timeout,
        teacher_batch_window_s=args.teacher_batch_window / 1e3,
        teacher_batch_max=args.teacher_batch_max,
        teacher_secret=args.teacher_secret, sched=args.sched,
        snapshot_dir=args.snapshot_dir, snapshot_every=args.snapshot_every,
        resume=args.resume, migrate=args.migrate,
        fuse_cohorts=args.fuse_cohorts == "on",
        teacher_compress=args.teacher_compress,
        mesh_fleet=args.mesh_fleet, metrics_json=args.metrics_json)


if __name__ == "__main__":
    main()
