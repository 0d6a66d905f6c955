"""Durable sessions (ISSUE 4): bit-for-bit snapshot/restore parity for every
backpressure policy, kill-mid-write recovery, crash-restart supervision, and
live tenant migration with the query-accounting identity intact."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import drift as drift_mod
from repro.core import oselm, pruning
from repro.engine import durable, multiplex, snapshot, stream
from repro.runtime.checkpoint import CheckpointManager

# Deterministic stats fields a resumed run must reproduce exactly (the
# wall-clock ones — wall_s, tick_ms — obviously cannot match).
DETERMINISTIC_STATS = (
    "ticks", "stream_steps", "tickets_issued", "queries_issued",
    "labels_applied", "tickets_dropped", "queries_dropped",
    "replies_orphaned", "tickets_lost", "queries_lost",
    "tickets_coalesced", "queries_coalesced", "asks_deferred",
    "learns_compact", "learns_full", "learn_rows",
)


def _cfg(n_in=24, n_hidden=16, n_out=4, min_trained=1_000_000):
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
    return xs, ys


def _assert_state_equal(a, b, msg=""):
    for (path, la), (_, lb) in zip(
        jax.tree_util.tree_flatten_with_path(a)[0],
        jax.tree_util.tree_flatten_with_path(b)[0],
    ):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb), err_msg=f"{msg} leaf {path} diverged"
        )


def _assert_stats_equal(a, b, msg=""):
    for f in DETERMINISTIC_STATS:
        assert getattr(a, f) == getattr(b, f), (
            f"{msg}: stats.{f} diverged: {getattr(a, f)} != {getattr(b, f)}"
        )
    assert list(a.label_latency_ticks) == list(b.label_latency_ticks), msg


def _lossy_teacher(ys):
    return stream.LatencyTeacher(
        stream.array_labels(ys), latency=2, jitter=3, loss_prob=0.2,
        partial_prob=0.2, seed=11,
    )


def _drive(sess, xs, start):
    """The stream.run drive loop from tick ``start`` (resume-aware)."""
    it = (xs[i] for i in range(start, len(xs)))
    if not sess.started():
        x0 = next(it, None)
        if x0 is not None:
            sess.start(x0)
    while sess._p is not None:
        sess.advance(next(it, None))
    return sess.finish()


# ---------------------------------------------------------------------------
# Tentpole: bit-for-bit resume parity, every backpressure policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", stream.BACKPRESSURE_POLICIES)
def test_resume_parity_bit_for_bit(policy, tmp_path):
    """A session snapshotted at tick k, published through CheckpointManager,
    and restored into a fresh session + fresh (state-restored) teacher must
    reproduce the uninterrupted run's final EngineState, outputs, and
    deterministic stats exactly — under latency + jitter + loss + partial
    answers, for every backpressure policy."""
    cfg = _cfg()
    t_len, s_len, k = 40, 4, 17
    xs, ys = _stream_data(cfg, t_len, s_len, seed=7)

    ref_state, ref_outs, ref_stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, _lossy_teacher(ys),
        mode="train_phase", capacity=3, backpressure=policy,
    )

    sess = stream.StreamSession(
        engine.init_fleet(cfg, s_len), cfg, _lossy_teacher(ys),
        mode="train_phase", capacity=3, backpressure=policy,
    )
    it = iter(xs)
    sess.start(next(it))
    for _ in range(k):
        sess.advance(next(it, None))
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(sess.t, sess.snapshot())
    consumed = snapshot.ticks_consumed(mgr.restore()[1])
    assert consumed == k + 1
    del sess, it  # the "crashed process"

    step, tree = mgr.restore()
    assert step == k
    fresh_teacher = _lossy_teacher(ys)  # state overwritten by the restore
    sess2 = stream.StreamSession.restore(tree, fresh_teacher, cfg=cfg)
    st2, outs2, stats2 = _drive(sess2, xs, consumed)

    _assert_state_equal(ref_state, st2, msg=policy)
    _assert_stats_equal(ref_stats, stats2, msg=policy)
    assert stats2.reconciled, stats2.summary()
    assert stats2.tickets_reasked == 0  # the teacher state came along
    for name in ref_outs._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref_outs, name)),
            np.asarray(getattr(outs2, name)),
            err_msg=f"{policy}: output {name!r} diverged",
        )


def test_learn_counters_ride_the_snapshot_and_default_to_zero_when_absent():
    """The learn-dispatch counters travel in a snapshot, over the wire too;
    a snapshot written before they existed restores them at 0."""
    cfg = _cfg()
    s_len = 2 * stream.LEARN_ROWS_FLOOR
    xs, ys = _stream_data(cfg, 12, s_len, seed=8)

    def teacher():  # most of each ticket unanswered: the learns compact
        return stream.LatencyTeacher(stream.array_labels(ys), latency=1,
                                     partial_prob=0.6, seed=4)

    sess = stream.StreamSession(engine.init_fleet(cfg, s_len), cfg, teacher(),
                                mode="train_phase", capacity=4)
    sess.start(xs[0])
    for x in xs[1:]:
        sess.advance(x)
    fields = ("learns_compact", "learns_full", "learn_rows")
    assert sess.stats.learns_compact > 0
    tree = snapshot.decode_snapshot(snapshot.encode_snapshot(sess.snapshot()))
    back = stream.StreamSession.restore(tree, teacher(), cfg=cfg)
    for f in fields:
        assert getattr(back.stats, f) == getattr(sess.stats, f), f

    meta = json.loads(np.asarray(tree["meta"]).item())
    for f in fields:
        del meta["stats"][f]
    old = stream.StreamSession.restore(
        {**tree, "meta": np.asarray(json.dumps(meta))}, teacher(), cfg=cfg)
    assert all(getattr(old.stats, f) == 0 for f in fields)
    assert old.stats.labels_applied == sess.stats.labels_applied


def test_restore_without_teacher_state_reasks_in_flight(tmp_path):
    """A teacher that cannot be snapshot (sockets): restore re-asks every
    in-flight ring entry through the fresh teacher — metered, original
    ticket order preserved, and the accounting identity still reconciles."""
    cfg = _cfg()
    t_len, s_len = 12, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=8)

    class NoSnapshotTeacher:
        """LatencyTeacher minus the snapshot support."""

        def __init__(self):
            self.inner = stream.LatencyTeacher(stream.array_labels(ys), latency=4)

        def ask(self, feats, mask, tick):
            return self.inner.ask(feats, mask, tick)

        def poll(self, tick):
            return self.inner.poll(tick)

        def in_flight(self):
            return self.inner.in_flight()

    sess = stream.StreamSession(
        engine.init_fleet(cfg, s_len), cfg, NoSnapshotTeacher(),
        mode="train_phase", capacity=8,
    )
    it = iter(xs)
    sess.start(next(it))
    for _ in range(5):
        sess.advance(next(it, None))
    tree = sess.snapshot()
    in_flight = len(sess.ring)
    assert in_flight > 0  # latency 4 > ticks run: queries still pending
    issued_before = sess.stats.tickets_issued

    fresh = NoSnapshotTeacher()
    sess2 = stream.StreamSession.restore(tree, fresh, cfg=cfg)
    assert sess2.stats.tickets_reasked == in_flight
    assert sess2.stats.tickets_issued == issued_before + in_flight
    assert fresh.in_flight() == in_flight  # the re-asks actually hit the wire
    st2, outs2, stats2 = _drive(sess2, xs, snapshot.ticks_consumed(tree))
    assert stats2.reconciled, stats2.summary()
    assert stats2.labels_applied == stats2.queries_issued == t_len * s_len
    assert outs2.trained.all()  # every re-asked query eventually trained

    # pending="drop": the in-flight queries become terminal losses instead.
    sess3 = stream.StreamSession.restore(
        tree, NoSnapshotTeacher(), cfg=cfg, pending="drop"
    )
    assert sess3.stats.tickets_reasked == 0
    assert sess3.stats.queries_lost >= in_flight
    st3, _, stats3 = _drive(sess3, xs, snapshot.ticks_consumed(tree))
    assert stats3.reconciled, stats3.summary()


def test_kill_mid_write_recovers_previous_good_snapshot(tmp_path):
    """A crash mid-snapshot-write leaves a .tmp staging dir; restore must
    fall back to the previous published step and resume losslessly."""
    cfg = _cfg()
    t_len, s_len = 30, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=9)

    ref_state, _, ref_stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, _lossy_teacher(ys),
        mode="train_phase", capacity=4,
    )

    sess = stream.StreamSession(
        engine.init_fleet(cfg, s_len), cfg, _lossy_teacher(ys),
        mode="train_phase", capacity=4,
    )
    it = iter(xs)
    sess.start(next(it))
    mgr = CheckpointManager(str(tmp_path), keep=3)
    for _ in range(10):
        sess.advance(next(it, None))
    mgr.save(sess.t, sess.snapshot())  # the good snapshot (tick 10)
    for _ in range(5):
        sess.advance(next(it, None))
    # The crashed write: a later step staged but never atomically renamed.
    crashed = tmp_path / "step_000000015.tmp"
    os.makedirs(crashed)
    (crashed / "MANIFEST.json").write_text("{\"step\": 15}")
    (crashed / "meta.npy").write_bytes(b"truncated garbage")
    del sess

    assert mgr.latest_step() == 10  # the .tmp is invisible
    step, tree = mgr.restore()
    sess2 = stream.StreamSession.restore(tree, _lossy_teacher(ys), cfg=cfg)
    st2, _, stats2 = _drive(sess2, xs, snapshot.ticks_consumed(tree))
    _assert_state_equal(ref_state, st2, msg="kill-mid-write")
    _assert_stats_equal(ref_stats, stats2, msg="kill-mid-write")
    assert stats2.reconciled


# ---------------------------------------------------------------------------
# Multiplexer durability: cadence snapshots, resume, supervision
# ---------------------------------------------------------------------------


def _tenants(cfg, datas, make_teacher, **kw):
    return [
        multiplex.Tenant(
            name=f"tenant{i}",
            state=engine.init_fleet(cfg, xs.shape[1]),
            ticks=snapshot.array_ticks(xs),
            cfg=cfg,
            teacher=make_teacher(i),
            mode="train_phase",
            capacity=4,
            collect=False,
            **kw,
        )
        for i, (xs, ys) in enumerate(datas)
    ]


def test_multiplex_resume_matches_uninterrupted(tmp_path):
    """Kill the multiplexer after some rounds; a resumed run restores every
    tenant from its latest published snapshot and finishes with exactly the
    states an uninterrupted multiplexed run produces."""
    cfg = _cfg()
    datas = [_stream_data(cfg, 40, 3, seed=20), _stream_data(cfg, 30, 2, seed=21)]

    def make_teacher(i, datas=datas):
        return stream.LatencyTeacher(
            stream.array_labels(datas[i][1]), latency=2, jitter=2,
            loss_prob=0.2, seed=30 + i,
        )

    ref, _ = multiplex.run(_tenants(cfg, datas, make_teacher))

    snap_dir = str(tmp_path / "snaps")
    mux = multiplex.Multiplexer(
        _tenants(cfg, datas, make_teacher),
        snapshot_dir=snap_dir, snapshot_every=6,
    )
    for _ in range(4):  # run a few rounds, then "crash" (abandon the object)
        mux.round()
    for name in ("tenant0", "tenant1"):
        latest = CheckpointManager(os.path.join(snap_dir, name)).latest_step()
        assert latest is not None and latest > 0, name
    del mux

    results, agg = multiplex.run(
        _tenants(cfg, datas, make_teacher),
        snapshot_dir=snap_dir, snapshot_every=6, resume=True,
    )
    for name in ref:
        _assert_state_equal(ref[name].state, results[name].state, msg=name)
        _assert_stats_equal(ref[name].stats, results[name].stats, msg=name)
        assert results[name].stats.reconciled
    assert agg.snapshots > 0


def test_run_supervised_crash_restart(tmp_path):
    """The fault.run_with_restarts supervisor around the durable
    multiplexer: an injected mid-run crash restarts the attempt, which
    resumes from the published snapshots and still matches the
    uninterrupted run bit-for-bit."""
    cfg = _cfg()
    datas = [_stream_data(cfg, 36, 3, seed=22), _stream_data(cfg, 24, 2, seed=23)]
    crash = {"armed": True}

    class CrashingTeacher:
        """Delegates to a LatencyTeacher; raises once at tick >= 20."""

        def __init__(self, i):
            self.inner = stream.LatencyTeacher(
                stream.array_labels(datas[i][1]), latency=1, jitter=1,
                loss_prob=0.1, seed=40 + i,
            )

        def ask(self, feats, mask, tick):
            if crash["armed"] and tick >= 20:
                crash["armed"] = False
                raise RuntimeError("injected node failure")
            return self.inner.ask(feats, mask, tick)

        def poll(self, tick):
            return self.inner.poll(tick)

        def in_flight(self):
            return self.inner.in_flight()

        def snapshot_state(self):
            return self.inner.snapshot_state()

        def restore_snapshot(self, tree):
            self.inner.restore_snapshot(tree)

    def make_plain(i):
        return stream.LatencyTeacher(
            stream.array_labels(datas[i][1]), latency=1, jitter=1,
            loss_prob=0.1, seed=40 + i,
        )

    ref, _ = multiplex.run(_tenants(cfg, datas, make_plain))

    results, agg = multiplex.run_supervised(
        lambda: _tenants(cfg, datas, CrashingTeacher),
        snapshot_dir=str(tmp_path / "snaps"),
        snapshot_every=5,
        max_restarts=2,
    )
    assert not crash["armed"]  # the crash really fired
    for name in ref:
        _assert_state_equal(ref[name].state, results[name].state, msg=name)
        _assert_stats_equal(ref[name].stats, results[name].stats, msg=name)
        assert results[name].stats.reconciled


# ---------------------------------------------------------------------------
# Live tenant migration
# ---------------------------------------------------------------------------


def test_live_migration_preserves_accounting_identity(tmp_path):
    """Quiesce → snapshot → extract a tenant mid-stream and restore it into
    a second multiplexer behind a FRESH teacher (quiesce disabled so
    in-flight tickets must be re-asked): the migrated tenant completes and
    the accounting identity reconciles across the move; the tenant left
    behind is untouched (bit-for-bit vs its solo run)."""
    cfg = _cfg()
    datas = [_stream_data(cfg, 30, 3, seed=24), _stream_data(cfg, 30, 2, seed=25)]

    def make_teacher(i):
        return stream.LatencyTeacher(
            stream.array_labels(datas[i][1]), latency=3, seed=50 + i
        )

    solo1_state, _, solo1_stats = stream.run(
        engine.init_fleet(cfg, 2), (x for x in datas[1][0]), cfg,
        make_teacher(1), mode="train_phase", capacity=4, collect=False,
    )

    mux = multiplex.Multiplexer(_tenants(cfg, datas, make_teacher))
    while mux.round():
        if mux.session("tenant0").t >= 15:
            break
    # quiesce_ticks=0: leave the in-flight tickets pending so the restore
    # MUST re-ask them through the new teacher.
    tree, rest_ticks = mux.extract("tenant0", quiesce_ticks=0)
    in_flight = len(tree["ring"])
    assert in_flight > 0
    results_a, _ = mux.run()

    # pending="reask": the new host's teacher starts fresh even though a
    # LatencyTeacher could technically restore — this is the
    # migrated-to-a-different-teacher path, so in-flight tickets re-ask.
    mux_b = multiplex.Multiplexer([], pending="reask")
    fresh = stream.LatencyTeacher(
        stream.array_labels(datas[0][1]), latency=3, seed=99
    )
    mux_b.admit(
        multiplex.Tenant(
            name="tenant0", state=None, ticks=rest_ticks, cfg=cfg,
            teacher=fresh, mode="train_phase", capacity=4, collect=False,
        ),
        snapshot=tree,
    )
    results_b, _ = mux_b.run()

    mig = results_b["tenant0"].stats
    assert mig.ticks == 30
    assert mig.tickets_reasked == in_flight
    assert mig.queries_issued == 30 * 3
    assert mig.reconciled, mig.summary()
    # The stay-behind tenant is oblivious to the migration.
    _assert_state_equal(solo1_state, results_a["tenant1"].state, msg="tenant1")
    _assert_stats_equal(solo1_stats, results_a["tenant1"].stats, msg="tenant1")


def test_migration_with_restorable_teacher_is_bit_for_bit(tmp_path):
    """When the destination teacher CAN restore the snapshot state (same
    LatencyTeacher semantics), migration is invisible: the migrated tenant
    finishes exactly like an unmigrated multiplexed/solo run."""
    cfg = _cfg()
    t_len, s_len = 30, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=26)

    def make_teacher():
        return stream.LatencyTeacher(
            stream.array_labels(ys), latency=2, jitter=2, loss_prob=0.2, seed=60
        )

    ref_state, _, ref_stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, make_teacher(),
        mode="train_phase", capacity=4, collect=False,
    )

    mux = multiplex.Multiplexer([
        multiplex.Tenant(
            name="t", state=engine.init_fleet(cfg, s_len),
            ticks=snapshot.array_ticks(xs), cfg=cfg, teacher=make_teacher(),
            mode="train_phase", capacity=4, collect=False,
        )
    ])
    while mux.round():
        if mux.session("t").t >= 13:
            break
    tree, rest = mux.extract("t", quiesce_ticks=0)

    mux_b = multiplex.Multiplexer([])
    mux_b.admit(
        multiplex.Tenant(
            name="t", state=None, ticks=rest, cfg=cfg, teacher=make_teacher(),
            mode="train_phase", capacity=4, collect=False,
        ),
        snapshot=tree,
    )
    results, _ = mux_b.run()
    _assert_state_equal(ref_state, results["t"].state, msg="migrated")
    _assert_stats_equal(ref_stats, results["t"].stats, msg="migrated")
    assert results["t"].stats.tickets_reasked == 0


# ---------------------------------------------------------------------------
# Durable single-session driver + misc contracts
# ---------------------------------------------------------------------------


def test_run_durable_resume_parity(tmp_path):
    """durable.run_durable: run to completion once; then run with a tick
    budget cut short (simulated crash via a truncated source), resume, and
    match the full run bit-for-bit."""
    cfg = _cfg()
    t_len, s_len = 32, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=27)

    def teacher():
        return stream.LatencyTeacher(
            stream.array_labels(ys), latency=1, jitter=2, loss_prob=0.1, seed=70
        )

    ref_state, ref_outs, ref_stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher(),
        mode="train_phase", capacity=4,
    )

    d = str(tmp_path / "snaps")
    # "Crashed" first run: the source dies at tick 19 (mid-stream) — the
    # exception fires after several snapshots were published.
    def dying(start):
        for t in range(start, t_len):
            if t == 19:
                raise RuntimeError("simulated ingest crash")
            yield xs[t]

    with pytest.raises(RuntimeError, match="ingest crash"):
        durable.run_durable(
            engine.init_fleet(cfg, s_len), snapshot.ResumableTicks(dying),
            cfg, teacher(), snapshot_dir=d, snapshot_every=5,
            mode="train_phase", capacity=4,
        )
    mgr = CheckpointManager(d)
    assert (mgr.latest_step() or 0) >= 5

    st2, outs2, stats2 = durable.run_durable(
        None, snapshot.array_ticks(xs), cfg, teacher(),
        snapshot_dir=d, snapshot_every=5, resume=True,
        mode="train_phase", capacity=4,
    )
    _assert_state_equal(ref_state, st2, msg="run_durable")
    _assert_stats_equal(ref_stats, stats2, msg="run_durable")
    for name in ref_outs._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref_outs, name)),
            np.asarray(getattr(outs2, name)),
            err_msg=f"run_durable output {name!r}",
        )


def test_resume_requires_seekable_ticks(tmp_path):
    cfg = _cfg()
    xs, ys = _stream_data(cfg, 8, 2, seed=28)
    d = str(tmp_path / "snaps")
    durable.run_durable(
        engine.init_fleet(cfg, 2), snapshot.array_ticks(xs), cfg,
        stream.LatencyTeacher(stream.array_labels(ys), latency=0),
        snapshot_dir=d, snapshot_every=3, mode="train_phase",
    )
    with pytest.raises(ValueError, match="seekable"):
        durable.run_durable(
            None, (x for x in xs), cfg,
            stream.LatencyTeacher(stream.array_labels(ys), latency=0),
            snapshot_dir=d, snapshot_every=3, resume=True, mode="train_phase",
        )


def test_snapshot_contract_validation():
    cfg = _cfg()
    xs, ys = _stream_data(cfg, 4, 2, seed=29)
    sess = stream.StreamSession(
        engine.init_fleet(cfg, 2), cfg,
        stream.LatencyTeacher(stream.array_labels(ys), latency=0),
        mode="train_phase",
    )
    it = iter(xs)
    sess.start(next(it))
    sess.advance(next(it))
    tree = sess.snapshot()
    with pytest.raises(ValueError, match="pending"):
        stream.StreamSession.restore(
            tree, stream.LatencyTeacher(stream.array_labels(ys)), pending="yolo"
        )
    # Snapshotting a finished session is meaningless and refused.
    while sess._p is not None:
        sess.advance(next(it, None))
    sess.finish()
    with pytest.raises(RuntimeError, match="finished"):
        sess.snapshot()
