"""Fleet telemetry tests (ISSUE 9): registry/tracer/exporter basics, the
StreamStats<->registry cross-check lock for every backpressure policy,
bit-for-bit parity of instrumented vs uninstrumented runs, snapshot
restore semantics for the load-signal gauges vs the trace ring, the
LabelServer wire ``stats`` scrape, and profiler mode: the program's spans
on a ``jax.profiler`` trace, nested as they ran, with ``SPAN_NAMES`` the
catalog of every name the program records."""

import ast
import collections
import dataclasses
import glob
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import drift as drift_mod
from repro.core import oselm, pruning
from repro.engine import multiplex, rpc, snapshot, stream
from repro.runtime import telemetry

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
TICK_SPANS = ("stream.tick", "cohort.tick")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Telemetry is a process-wide global — every test starts and ends
    with it off so nothing leaks across tests (or into other files)."""
    telemetry.disable()
    yield
    telemetry.disable()


def _cfg(n_in=24, n_hidden=16, n_out=4, min_trained=16):
    return engine.EngineConfig(
        elm=oselm.OSELMConfig(
            n_in=n_in, n_hidden=n_hidden, n_out=n_out, variant="hash", ridge=1e-2
        ),
        prune=pruning.PruneConfig(min_trained=min_trained),
        drift=drift_mod.DriftConfig(warmup=16, k_sigma=3.0, enter_hits=2, exit_calm=16),
    )


def _stream_data(cfg, t, s, seed=0):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    xs = np.array(jnp.tanh(jax.random.normal(kx, (t, s, cfg.elm.n_in))))
    ys = np.asarray(jax.random.randint(ky, (t, s), 0, cfg.elm.n_out), np.int32)
    return jnp.asarray(xs), ys


# ---------------------------------------------------------------------------
# Registry / tracer / exporter basics
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms_roundtrip_prometheus():
    reg = telemetry.Registry()
    reg.count("odl_mux_rounds", 3, worker="w0")
    reg.count("odl_mux_rounds", 2, worker="w0")
    reg.set_counter("odl_stream_ticks", 41, tenant="t0")
    reg.gauge("odl_stream_tick_rate_ema", 12.5, tenant="t0")
    reg.observe("odl_rpc_batch_occupancy", 3)
    reg.observe("odl_rpc_batch_occupancy", 5)

    assert reg.get_counter("odl_mux_rounds", worker="w0") == 5
    assert reg.get_counter("odl_mux_rounds", worker="nope") == 0
    assert reg.get_gauge("odl_stream_tick_rate_ema", tenant="t0") == 12.5

    text = reg.prometheus_text()
    parsed = telemetry.parse_prometheus(text)
    assert parsed[("odl_mux_rounds", (("worker", "w0"),))] == 5
    assert parsed[("odl_stream_ticks", (("tenant", "t0"),))] == 41
    assert parsed[("odl_stream_tick_rate_ema", (("tenant", "t0"),))] == 12.5
    assert parsed[("odl_rpc_batch_occupancy_count", ())] == 2
    assert parsed[("odl_rpc_batch_occupancy_sum", ())] == 8
    # Integral counters print without a trailing .0 (exact cross-checks).
    assert "odl_stream_ticks{tenant=\"t0\"} 41\n" in text

    snap = reg.snapshot()
    assert snap["counters"]["odl_stream_ticks"] == [
        {"labels": {"tenant": "t0"}, "value": 41.0}
    ]
    assert snap["histograms"]["odl_rpc_batch_occupancy"][0]["max"] == 5.0


def test_prometheus_label_escaping_roundtrips():
    reg = telemetry.Registry()
    reg.set_counter("odl_stream_ticks", 1, tenant='we"ird\\na\nme')
    parsed = telemetry.parse_prometheus(reg.prometheus_text())
    assert parsed[("odl_stream_ticks", (("tenant", 'we"ird\\na\nme'),))] == 1


def test_parse_prometheus_rejects_malformed_lines():
    with pytest.raises(ValueError):
        telemetry.parse_prometheus("justonetoken\n")
    with pytest.raises(ValueError):
        telemetry.parse_prometheus('bad{tenant=unquoted} 1\n')


def test_tracer_spans_events_sampling_and_bounded_ring():
    tr = telemetry.SpanTracer(capacity=4, sample=2)
    for i in range(6):
        tok = tr.begin("stream.tick")
        tr.end(tok, t=i)
    tr.event("rpc.reconnect", endpoint="x:1")
    spans = tr.spans()
    # sample=2 keeps every other begin; capacity=4 bounds the ring.
    assert tr.dropped == 3
    assert len(spans) <= 4
    names = {s[0] for s in spans}
    assert "rpc.reconnect" in names

    trace = tr.chrome_trace()
    phases = {ev["name"]: ev["ph"] for ev in trace["traceEvents"]}
    assert phases["rpc.reconnect"] == "i"  # instant
    assert phases.get("stream.tick", "X") == "X"  # complete span

    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_enable_is_idempotent_and_disable_resets():
    assert telemetry.get() is None
    tel = telemetry.enable()
    tel.registry.count("odl_mux_rounds")
    assert telemetry.enable() is tel  # existing instance kept
    assert tel.registry.get_counter("odl_mux_rounds") == 1
    telemetry.disable()
    assert telemetry.get() is None


def test_check_stream_identity_flags_broken_accounting():
    reg = telemetry.Registry()
    telemetry.sync_stream_stats(reg, stream.StreamStats(
        queries_issued=10, labels_applied=6, queries_dropped=2,
        queries_lost=1, queries_coalesced=0), pending=1, tenant="ok")
    telemetry.sync_stream_stats(reg, stream.StreamStats(
        queries_issued=10, labels_applied=6), pending=0, tenant="broken")
    out = telemetry.check_stream_identity(
        telemetry.parse_prometheus(reg.prometheus_text()))
    by_tenant = {dict(k)["tenant"]: v for k, v in out.items()}
    assert by_tenant == {"ok": True, "broken": False}
    # An empty scrape yields an empty dict — callers treat that as failure.
    assert telemetry.check_stream_identity({}) == {}


# ---------------------------------------------------------------------------
# Satellite 1: registry counters identical to StreamStats for every
# backpressure policy, and telemetry never perturbs the run.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", stream.BACKPRESSURE_POLICIES)
def test_registry_mirrors_stream_stats_and_never_perturbs_run(policy):
    """The lock: after a run, every odl_stream_* counter equals the
    StreamStats field verbatim, and the instrumented run's final state is
    bit-for-bit the uninstrumented one (telemetry reads clocks and
    appends to rings; it must never touch the device op sequence)."""
    cfg = _cfg(min_trained=1)
    t_len, s_len = 50, 4
    xs, ys = _stream_data(cfg, t_len, s_len, seed=3)

    def run_once():
        # latency 7 >> capacity 3 saturates the ring so the policy under
        # test actually fires (drops / deferrals / coalescing — not just
        # the happy path).
        teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=7)
        return stream.run(
            engine.init_fleet(cfg, s_len), (xs[t] for t in range(t_len)), cfg,
            teacher, mode="train_phase", capacity=3, backpressure=policy,
        )

    telemetry.disable()
    st_plain, _, stats_plain = run_once()

    tel = telemetry.enable()
    st_instr, _, stats = run_once()

    # Bit-for-bit parity of the instrumented run.
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(st_plain)[0],
        jax.tree_util.tree_flatten_with_path(st_instr)[0],
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"telemetry perturbed state leaf {path} under {policy}",
        )
    for f in telemetry.STREAM_COUNTER_FIELDS:
        assert getattr(stats_plain, f) == getattr(stats, f), f

    # The policy actually exercised its branch.
    if policy in ("drop_oldest", "drop_newest"):
        assert stats.queries_dropped > 0
    elif policy == "block":
        assert stats.asks_deferred > 0
    else:
        assert stats.queries_coalesced > 0

    # Registry view == StreamStats view, field for field.
    for f in telemetry.STREAM_COUNTER_FIELDS:
        assert tel.registry.get_counter(f"odl_stream_{f}") == getattr(stats, f), f
    for f in telemetry.STREAM_GAUGE_FIELDS:
        assert tel.registry.get_gauge(f"odl_stream_{f}") == float(getattr(stats, f)), f

    # And the scraped identity holds after the drain (pending gauge 0).
    checks = telemetry.check_stream_identity(
        telemetry.parse_prometheus(tel.registry.prometheus_text()))
    assert checks and all(checks.values())
    assert tel.registry.get_gauge("odl_stream_queries_pending") == 0
    # The hot path traced ticks too.
    assert any(s[0] == "stream.tick" for s in tel.tracer.spans())


def test_midrun_scrape_identity_includes_pending_queries():
    """Mid-run (ring non-empty) the four terminal buckets do NOT cover
    queries_issued — the exported pending gauge is what closes the
    identity at any instant."""
    cfg = _cfg(min_trained=1)
    t_len, s_len = 12, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=5)
    tel = telemetry.enable()
    sess = stream.StreamSession(
        engine.init_fleet(cfg, s_len), cfg,
        stream.LatencyTeacher(stream.array_labels(ys), latency=50),
        mode="train_phase", capacity=64,
    )
    sess.telemetry_labels = {"tenant": "t0"}
    sess.start(xs[0])
    for t in range(1, t_len):
        sess.advance(xs[t])
    sess.sync_telemetry()
    assert sess.pending_queries() > 0  # nothing answered yet (latency 50)
    parsed = telemetry.parse_prometheus(tel.registry.prometheus_text())
    checks = telemetry.check_stream_identity(parsed)
    key = (("tenant", "t0"),)
    assert checks[key] is True
    assert parsed[("odl_stream_queries_pending", key)] == sess.pending_queries()
    # Without the pending gauge the identity would be violated mid-run.
    del parsed[("odl_stream_queries_pending", key)]
    assert telemetry.check_stream_identity(parsed)[key] is False
    sess.advance(None)
    sess.finish()


# ---------------------------------------------------------------------------
# Satellite 3: load-signal gauges ride snapshots; the trace ring does not.
# ---------------------------------------------------------------------------


def test_snapshot_restores_load_signal_gauges_but_not_trace_ring():
    cfg = _cfg(min_trained=1)
    t_len, s_len = 20, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=9)
    tel = telemetry.enable()
    teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=2)
    sess = stream.StreamSession(
        engine.init_fleet(cfg, s_len), cfg, teacher, mode="train_phase",
        capacity=8,
    )
    sess.start(xs[0])
    for t in range(1, 12):
        sess.advance(xs[t])
    assert sess.stats.tick_rate_ema > 0
    assert sess.stats.ring_occupancy_hwm > 0
    tree = snapshot.capture(sess)

    # The load signals travel in the snapshot meta "stats"...
    meta_stats = snapshot._meta_of(tree)["stats"]
    assert meta_stats["tick_rate_ema"] == sess.stats.tick_rate_ema
    assert meta_stats["ring_occupancy_hwm"] == sess.stats.ring_occupancy_hwm
    # ...while nothing of the telemetry registry/tracer is in the tree.
    assert "telemetry" not in tree
    assert any(s[0] == "snapshot.save" for s in tel.tracer.spans())

    # Simulate landing in a fresh process: new telemetry instance.
    telemetry.disable()
    tel2 = telemetry.enable()
    fresh = stream.LatencyTeacher(stream.array_labels(ys), latency=2)
    sess2 = snapshot.restore(tree, fresh, cfg=cfg)
    assert sess2.stats.tick_rate_ema == sess.stats.tick_rate_ema
    assert sess2.stats.ring_occupancy_hwm == sess.stats.ring_occupancy_hwm
    # The destination tracer carries only what happened here (the restore
    # span) — no stream.tick spans from the source process.
    names = {s[0] for s in tel2.tracer.spans()}
    assert "snapshot.restore" in names
    assert "stream.tick" not in names


def test_all_stream_stats_counters_are_mirrored():
    """Growth guard: every integer accounting counter StreamStats gains
    must be added to STREAM_COUNTER_FIELDS (or STREAM_MIRROR_EXCLUDED).
    The exclusion set lives in telemetry so odlint's ODL003 rule and this
    runtime check enforce the same partition."""
    excluded = set(telemetry.STREAM_MIRROR_EXCLUDED) | set(
        telemetry.STREAM_GAUGE_FIELDS
    )
    fields = {f.name for f in dataclasses.fields(stream.StreamStats)}
    assert fields - excluded == set(telemetry.STREAM_COUNTER_FIELDS)
    assert {"learns_compact", "learns_full", "learn_rows"} <= set(
        telemetry.STREAM_COUNTER_FIELDS
    )
    assert set(telemetry.STREAM_GAUGE_FIELDS) < fields
    # the three partitions are disjoint
    assert not set(telemetry.STREAM_MIRROR_EXCLUDED) & set(
        telemetry.STREAM_COUNTER_FIELDS
    )
    assert not set(telemetry.STREAM_MIRROR_EXCLUDED) & set(
        telemetry.STREAM_GAUGE_FIELDS
    )


# ---------------------------------------------------------------------------
# Satellite 2: LabelServer counters scraped over the wire.
# ---------------------------------------------------------------------------


def test_label_server_stats_scrape_over_the_wire():
    server = rpc.LabelServer(n_out=4).start()
    try:
        teacher = rpc.RpcTeacher(server.host, server.port, timeout_s=10.0)
        feats = np.zeros((3, 4), np.float32)
        mask = np.array([True, False, True])
        teacher.ask(feats, mask, tick=0)
        replies = []
        import time as _time
        t0 = _time.monotonic()
        while not replies and _time.monotonic() - t0 < 10.0:
            replies = teacher.poll(0)
        teacher.close()
        assert replies

        stats = rpc.server_stats(server.host, server.port)
        assert stats["asks_served"] >= 1
        assert stats["frames_v2"] >= 1
        assert stats["frame_errors"] == 0
        assert stats["thread_count"] >= 0
        assert stats["n_out"] == 4
        # The scrape itself is not an ask.
        again = rpc.server_stats(server.host, server.port)
        assert again["asks_served"] == stats["asks_served"]
        assert again["connections_accepted"] > stats["connections_accepted"]
    finally:
        server.close()


def test_label_server_stats_scrape_respects_hmac_secret():
    server = rpc.LabelServer(n_out=4, secret="s3kr1t").start()
    try:
        stats = rpc.server_stats(server.host, server.port, secret="s3kr1t")
        assert stats["auth_failures"] == 0
        with pytest.raises((ConnectionError, OSError)):
            rpc.server_stats(server.host, server.port, secret="wrong",
                             timeout_s=2.0)
        assert rpc.server_stats(server.host, server.port,
                                secret="s3kr1t")["auth_failures"] >= 1
    finally:
        server.close()


def test_rpc_client_mirrors_wire_meters_into_registry():
    tel = telemetry.enable()
    server = rpc.LabelServer(n_out=4).start()
    try:
        client = rpc.BatchedRpcClient(server.host, server.port,
                                      timeout_s=10.0, batch_window_s=0.0)
        h = client.tenant("t0")
        h.ask(np.zeros((2, 4), np.float32), np.array([True, True]), 0)
        import time as _time
        t0 = _time.monotonic()
        while not h.poll(0) and _time.monotonic() - t0 < 10.0:
            _time.sleep(1e-3)
        client.sync_telemetry()
        ep = f"{server.host}:{server.port}"
        assert tel.registry.get_counter("odl_rpc_wire_messages", endpoint=ep) > 0
        assert tel.registry.get_counter("odl_rpc_wire_bytes", endpoint=ep) > 0
        assert tel.registry.get_counter("odl_rpc_asks_sent", endpoint=ep) >= 1
        # The flush span + batch occupancy histogram landed too.
        assert any(s[0] == "rpc.flush" for s in tel.tracer.spans())
        snap = tel.registry.snapshot()
        assert snap["histograms"]["odl_rpc_batch_occupancy"][0]["count"] >= 1
        client.close()
    finally:
        server.close()


# ---------------------------------------------------------------------------
# Profiler mode: the program's spans on the profiler's host plane.
# ---------------------------------------------------------------------------


def _mux_run():
    """Two same-width tenants fused into one cohort and a third of another
    width ticking solo, through the multiplexer; a teacher with jitter, so
    some ticks learn twice."""
    cfg = _cfg(min_trained=1)
    tenants = []
    for i, s_len in enumerate((4, 4, 3)):
        xs, ys = _stream_data(cfg, 9, s_len, seed=20 + i)
        teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=1,
                                        jitter=2, seed=i)
        tenants.append(multiplex.Tenant(
            f"t{i}", engine.init_fleet(cfg, s_len), [xs[t] for t in range(9)], cfg,
            teacher, mode="train_phase", capacity=4, backpressure="drop_oldest"))
    return multiplex.run(tenants, quantum=3, fuse=True)[0]


def _profiler_spans(trace_dir):
    """``(name, start, end, line, stats)`` of every host event of the trace
    whose name the program records; ``line`` tells the host's threads
    apart."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         (plane.name, i), dict(e.stats))
                        for e in line.events if e.name in telemetry.SPAN_NAMES]
    return out


def _encloses(outer, inner):
    return outer[3] == inner[3] and outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_mode_puts_every_span_on_the_trace_nested_in_its_tick(tmp_path):
    tel = telemetry.enable(profiler=True)
    with jax.profiler.trace(str(tmp_path)):
        _mux_run()
    spans = _profiler_spans(str(tmp_path))
    # The ring and the profiler hold the same spans (events are instants,
    # recorded in the ring only).
    ring = collections.Counter(s[0] for s in tel.tracer.spans() if s[2] > 0)
    assert collections.Counter(s[0] for s in spans) == ring
    assert {"mux.round", "mux.source", *TICK_SPANS, "tick.sync", "tick.ask",
            "tick.poll", "tick.ship", "tick.dispatch"} <= set(ring)

    ticks = [s for s in spans if s[0] in TICK_SPANS]
    rounds = [s for s in spans if s[0] == "mux.round"]
    for sp in spans:
        if sp[0].startswith("tick."):
            assert any(_encloses(t, sp) for t in ticks), sp
        if sp[0] in TICK_SPANS or sp[0] == "mux.source":
            assert any(_encloses(r, sp) for r in rounds), sp
    for t in ticks:
        # Every tick reads its query mask back from the device.
        assert any(c[0] == "tick.sync" and _encloses(t, c) for c in spans), t
        assert "learns" in t[4] and "t" in t[4]
    assert max(t[4]["learns"] for t in ticks) >= 2
    # A fused cohort syncs on the next tick's features, a solo tick does not.
    syncs = collections.Counter(
        (t[0], c[4]["what"]) for t in ticks for c in spans
        if c[0] == "tick.sync" and _encloses(t, c))
    assert syncs[("cohort.tick", "feats")] and not syncs[("stream.tick", "feats")]
    assert syncs[("stream.tick", "mask")] and syncs[("stream.tick", "cols")]


def test_telemetry_off_builds_no_annotation_and_leaves_the_run_bitwise(
        monkeypatch, tmp_path):
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    plain = _mux_run()
    assert built == []

    tel = telemetry.enable(profiler=True)
    with jax.profiler.trace(str(tmp_path)):
        traced = _mux_run()
    assert len(built) == sum(1 for s in tel.tracer.spans() if s[2] > 0) > 0
    for name, r in plain.items():
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(r.state)[0],
            jax.tree_util.tree_flatten_with_path(traced[name].state)[0],
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name} leaf {path}")
        np.testing.assert_array_equal(r.outputs.pred, traced[name].outputs.pred)
        for f in telemetry.STREAM_COUNTER_FIELDS:
            assert getattr(r.stats, f) == getattr(traced[name].stats, f), f


def test_span_names_catalog_every_name_the_program_records():
    used = set()
    for path in SRC.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("begin", "span", "event") and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                used.add(node.args[0].value)
    assert used == set(telemetry.SPAN_NAMES)
    assert len(telemetry.SPAN_NAMES) == len(set(telemetry.SPAN_NAMES))


def test_profiler_mode_needs_a_fresh_instance_and_plain_mode_stays_off_jax():
    telemetry.enable()
    with pytest.raises(RuntimeError, match="profiler mode"):
        telemetry.enable(profiler=True)
    # The router and the crash-smoke parent import telemetry and record
    # spans without loading JAX.
    code = ("import sys\n"
            "from repro.runtime import telemetry\n"
            "tel = telemetry.enable()\n"
            "tel.tracer.end(tel.tracer.begin('migrate.ship'), tenant='a')\n"
            "assert tel.tracer.chrome_trace()['traceEvents']\n"
            "print(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib')))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH=str(SRC)), timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
