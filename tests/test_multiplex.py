"""Multi-tenant multiplexer, backpressure policies, query-accounting
reconciliation, the RpcTeacher loopback transport, and the serve path's
plan-time (stale-reply) semantics — ISSUE 3."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import drift as drift_mod
from repro.core import oselm, pruning
from repro.engine import multiplex, rpc, stream


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
    return xs, ys


def _assert_state_equal(a, b, msg=""):
    for (path, la), (_, lb) in zip(
        jax.tree_util.tree_flatten_with_path(a)[0],
        jax.tree_util.tree_flatten_with_path(b)[0],
    ):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb), err_msg=f"{msg} leaf {path} diverged"
        )


def _assert_reconciled(stats, policy="drop_oldest"):
    """The ISSUE-3 acceptance identity, exact."""
    assert stats.reconciled, stats.summary()
    if policy != "coalesce":
        assert stats.queries_coalesced == 0
        assert stats.queries_issued == (
            stats.labels_applied + stats.queries_dropped + stats.queries_lost
        ), stats.summary()


# ---------------------------------------------------------------------------
# Tentpole: multiplexer == N solo runs, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantum", [1, 3])
def test_two_tenants_bit_for_bit_vs_two_solo_runs(quantum):
    """Two tenants with *different* configs multiplexed over one process
    must end in exactly the states (and outputs) two independent
    ``stream.run`` calls produce, zero-latency teacher — at any scheduler
    quantum (the time slice changes interleaving, never results)."""
    cfgs = [_cfg(n_hidden=16, min_trained=4), _cfg(n_hidden=32, min_trained=8)]
    datas = [_stream_data(cfgs[0], 40, 3, seed=1), _stream_data(cfgs[1], 25, 2, seed=2)]

    solo = []
    for cfg, (xs, ys) in zip(cfgs, datas):
        teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=0)
        solo.append(
            stream.run(
                engine.init_fleet(cfg, xs.shape[1]), (x for x in xs), cfg,
                teacher, mode="train_phase",
            )
        )

    tenants = [
        multiplex.Tenant(
            name=f"tenant{i}",
            state=engine.init_fleet(cfg, xs.shape[1]),
            ticks=(x for x in xs),
            cfg=cfg,
            teacher=stream.LatencyTeacher(stream.array_labels(ys), latency=0),
            mode="train_phase",
        )
        for i, (cfg, (xs, ys)) in enumerate(zip(cfgs, datas))
    ]
    results, agg = multiplex.run(tenants, quantum=quantum)

    assert agg.n_tenants == 2
    assert agg.stream_steps == sum(s[2].stream_steps for s in solo)
    for i, (st, outs, stats) in enumerate(solo):
        r = results[f"tenant{i}"]
        _assert_state_equal(st, r.state, msg=f"tenant{i}")
        for name in outs._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(outs, name)),
                np.asarray(getattr(r.outputs, name)),
                err_msg=f"tenant{i} output {name!r} diverged",
            )
        assert r.stats.queries_issued == stats.queries_issued > 0
        assert r.stats.labels_applied == stats.labels_applied
        _assert_reconciled(r.stats)


def test_tenants_with_equal_configs_share_compiled_runners():
    """The whole point of multiplexing fleets over one process: tenants
    whose (cfg, mode, donate) hash equal reuse the same compiled runner
    (LRU hit), never a second executable (miss)."""
    cfg = _cfg(n_hidden=16, min_trained=4)
    xs, ys = _stream_data(cfg, 6, 2, seed=3)

    def tenant(name):
        return multiplex.Tenant(
            name=name,
            state=engine.init_fleet(cfg, xs.shape[1]),
            ticks=(x for x in xs),
            cfg=cfg,
            teacher=stream.LatencyTeacher(stream.array_labels(ys), latency=0),
            mode="train_phase",
        )

    multiplex.run([tenant("warm")])  # compile once
    before = multiplex.cache_stats()
    multiplex.run([tenant("a"), tenant("b"), tenant("c")])
    after = multiplex.cache_stats()
    for runner in ("plan_runner", "learn_runner", "learn_plan_runner"):
        assert after[runner]["misses"] == before[runner]["misses"], runner
    assert after["plan_runner"]["hits"] >= before["plan_runner"]["hits"] + 3


# ---------------------------------------------------------------------------
# Query-accounting reconciliation (satellite 2) — property over fault modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "latency,jitter,loss,partial,outage,capacity,policy",
    [
        (0, 0, 0.0, 0.0, None, 64, "drop_oldest"),  # clean zero-latency
        (2, 5, 0.3, 0.0, None, 4, "drop_oldest"),  # loss + jitter + overflow
        (3, 2, 0.2, 0.3, None, 2, "drop_oldest"),  # + partial answers
        (5, 0, 0.0, 0.5, None, 2, "drop_newest"),  # refuse-new + partial
        (3, 4, 0.2, 0.2, None, 2, "block"),  # deferred asks + loss
        (4, 3, 0.1, 0.25, None, 3, "coalesce"),  # merged asks + partial
        (1, 0, 0.0, 0.0, 5, 8, "drop_oldest"),  # permanent outage
    ],
)
def test_query_accounting_identity(latency, jitter, loss, partial, outage,
                                   capacity, policy):
    """queries_issued == labels_applied + queries_dropped + queries_lost
    (+ queries_coalesced under the coalesce policy) — exactly, under every
    combination of teacher loss, jitter, partial answers, ring overflow,
    and backpressure policy."""
    cfg = _cfg(min_trained=1_000_000)  # cold heads: every tick queries
    t_len, s_len = 40, 4
    xs, ys = _stream_data(cfg, t_len, s_len, seed=7)
    teacher = stream.LatencyTeacher(
        stream.array_labels(ys), latency=latency, jitter=jitter, loss_prob=loss,
        partial_prob=partial, outage_after=outage, seed=11,
    )
    st, outs, stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher,
        mode="train_phase", capacity=capacity, backpressure=policy,
    )
    assert stats.queries_issued == t_len * s_len
    _assert_reconciled(stats, policy)
    # labels actually applied == trained marks == per-stream counts.
    assert stats.labels_applied == int(np.asarray(st.elm.count).sum())
    assert stats.labels_applied == int(outs.trained.sum())
    if partial and not outage:
        assert stats.queries_lost > 0  # the partial-answer residue is metered


def test_partial_answer_residue_is_metered():
    """A ticket answered for only some of its asked streams applies n labels
    and meters the residue as queries_lost — previously unaccounted."""
    cfg = _cfg(min_trained=1_000_000)
    t_len, s_len = 20, 6
    xs, ys = _stream_data(cfg, t_len, s_len, seed=8)
    teacher = stream.LatencyTeacher(
        stream.array_labels(ys), latency=1, partial_prob=0.4, seed=9
    )
    st, outs, stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher,
        mode="train_phase",
    )
    assert 0 < stats.labels_applied < stats.queries_issued
    assert stats.queries_lost > 0
    assert stats.queries_dropped == 0
    _assert_reconciled(stats)


# ---------------------------------------------------------------------------
# Backpressure policies (tentpole)
# ---------------------------------------------------------------------------


def test_drop_newest_keeps_oldest_tickets():
    """drop_newest refuses the new ask when the ring is full: the *first*
    ``capacity`` tickets survive (mirror image of drop_oldest, which keeps
    the last ones — locked by test_stream.py)."""
    cfg = _cfg(min_trained=1_000_000)
    t_len, s_len = 6, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=10)
    teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=50)
    st, outs, stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher,
        mode="train_phase", capacity=2, backpressure="drop_newest",
    )
    assert stats.tickets_issued == 2  # refused asks never hit the wire
    assert stats.tickets_dropped == t_len - 2
    assert stats.queries_dropped == (t_len - 2) * s_len
    assert stats.labels_applied == 2 * s_len
    assert stats.replies_orphaned == 0  # nothing evicted -> nothing orphaned
    np.testing.assert_array_equal(outs.trained[:2], np.ones((2, s_len), bool))
    assert not outs.trained[2:].any()
    _assert_reconciled(stats, "drop_newest")


def test_block_defers_asks_and_loses_nothing():
    """block parks the ask host-side until a ring slot frees: with enough
    drain every decided query is eventually asked and answered — zero drops
    despite a ring much smaller than the teacher's latency window."""
    cfg = _cfg(min_trained=1_000_000)
    t_len, s_len = 12, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=11)
    teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=3)
    st, outs, stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher,
        mode="train_phase", capacity=2, backpressure="block",
    )
    assert stats.asks_deferred > 0
    assert stats.tickets_issued == t_len  # every ask eventually submitted
    assert stats.queries_dropped == 0
    assert stats.labels_applied == stats.queries_issued == t_len * s_len
    assert outs.trained.all()
    _assert_reconciled(stats, "block")


def test_coalesce_merges_requeries_into_in_flight_ticket():
    """coalesce: a stream re-querying while its query is in flight rides the
    in-flight ticket instead of duplicating teacher traffic — with a
    teacher slower than the whole stream, one ticket serves every tick."""
    cfg = _cfg(min_trained=1_000_000)
    t_len, s_len = 6, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=12)
    teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=50)
    st, outs, stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher,
        mode="train_phase", capacity=4, backpressure="coalesce",
    )
    assert stats.tickets_issued == 1  # tick 0; ticks 1..5 fully covered
    assert stats.tickets_coalesced == t_len - 1
    assert stats.queries_coalesced == (t_len - 1) * s_len
    assert stats.labels_applied == s_len  # the one in-flight ticket answers
    assert stats.queries_dropped == 0 and stats.replies_orphaned == 0
    np.testing.assert_array_equal(outs.trained[0], np.ones(s_len, bool))
    assert not outs.trained[1:].any()
    _assert_reconciled(stats, "coalesce")


def test_coalesce_does_not_credit_a_ticket_it_evicts():
    """Regression: when the residual ask of a coalesce submit evicts the
    oldest in-flight ticket (full ring), streams covered only by that
    ticket must ride the new ask — not be credited as coalesced against a
    covering ticket that just became an orphan (they would silently never
    get a label)."""
    cfg = _cfg(min_trained=1_000_000)
    s_len = 2
    xs, ys = _stream_data(cfg, 3, s_len, seed=22)
    teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=50)
    sess = stream.StreamSession(
        engine.init_fleet(cfg, s_len), cfg, teacher,
        mode="train_phase", capacity=1, backpressure="coalesce",
    )
    # Tick 0: only stream 0 queries -> ticket T0 covers {0}.  Tick 1: both
    # streams query; stream 1 forces a residual ask on the full ring, which
    # evicts T0 — so stream 0's re-query must NOT coalesce into T0.
    sess.stats.queries_issued += 1
    sess._submit(xs[0], np.array([True, False]), None, 0)
    sess.stats.queries_issued += 2
    sess._submit(xs[1], np.array([True, True]), None, 1)
    assert sess.stats.queries_coalesced == 0  # nothing falsely settled
    assert sess.stats.tickets_dropped == 1 and sess.stats.queries_dropped == 1
    (ent,) = sess.ring.entries()  # the surviving ticket carries BOTH streams
    np.testing.assert_array_equal(ent.queried, [True, True])


def test_backpressure_policy_is_validated():
    cfg = _cfg()
    with pytest.raises(ValueError, match="backpressure"):
        stream.StreamSession(
            engine.init_fleet(cfg, 2), cfg,
            stream.LatencyTeacher(lambda t, f: np.zeros(2, np.int32)),
            backpressure="yolo",
        )


# ---------------------------------------------------------------------------
# Drain polls while EITHER ring or in-flight is non-empty (satellite 3)
# ---------------------------------------------------------------------------


class _ScriptedTeacher:
    """Teacher answering ticket i at an explicit due tick (full mask)."""

    def __init__(self, labels_row, dues):
        self.labels_row = np.asarray(labels_row, np.int32)
        self.dues = dues  # ticket -> due tick
        self._pending = {}
        self._next = 0

    def ask(self, feats, mask, tick):
        ticket = self._next
        self._next += 1
        self._pending[ticket] = (self.dues[ticket], np.asarray(mask, bool))
        return ticket

    def poll(self, tick):
        out = []
        for ticket in sorted(self._pending):
            due, mask = self._pending[ticket]
            if due <= tick:
                out.append(stream.TeacherReply(ticket, self.labels_row, mask))
        for r in out:
            del self._pending[r.ticket]
        return out

    def in_flight(self):
        return len(self._pending)


def test_drain_polls_after_ring_empties_so_orphans_are_metered():
    """Regression: the youngest (ring-resident) ticket answers early and the
    evicted tickets answer late — the ring empties mid-drain while replies
    are still in flight.  Draining only while *both* ring and in-flight
    were non-empty silently discarded those replies with replies_orphaned
    staying 0; the fixed loop polls while either holds."""
    cfg = _cfg(min_trained=1_000_000)
    t_len, s_len = 3, 2
    xs, ys = _stream_data(cfg, t_len, s_len, seed=13)
    # Tickets 0,1 get evicted (capacity 1); ticket 2 survives.  Ticket 2
    # answers first (t=3) — ring empties — tickets 0,1 answer at t=6.
    teacher = _ScriptedTeacher(ys[0], dues={0: 6, 1: 6, 2: 3})
    st, outs, stats = stream.run(
        engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher,
        mode="train_phase", capacity=1,
    )
    assert teacher.in_flight() == 0  # the late replies WERE polled
    assert stats.replies_orphaned == 2
    assert stats.labels_applied == s_len
    assert stats.tickets_dropped == 2 and stats.queries_dropped == 2 * s_len
    _assert_reconciled(stats)


# ---------------------------------------------------------------------------
# Serve-path stale-reply semantics (satellite 1)
# ---------------------------------------------------------------------------


def test_delayed_reply_judged_at_plan_time_context_matches_run_fleet():
    """A query's answer that lands after the weights (and the ladder) moved
    must be judged by the *plan-time* pred/confidence/theta — the same
    transition run_fleet makes for that query — not recomputed from the
    current state.  Locks the gate/apply_labels stale-reply fix."""
    cfg = _cfg(min_trained=1_000_000)  # everyone queries, drift irrelevant
    s_len = 2
    x0 = jnp.tanh(jax.random.normal(jax.random.PRNGKey(20), (s_len, cfg.elm.n_in)))

    # Arm the ladder at level 3 so step-ups stay observable throughout.
    st0 = engine.init_fleet(cfg, s_len)
    st0 = st0._replace(prune=st0.prune._replace(level=jnp.full((s_len,), 3, jnp.int32)))

    st1, ctx0 = engine.gate(st0, x0, cfg)
    assert bool(ctx0.queried.all())
    # The teacher will answer class (pred+1) — a plan-time DISAGREEMENT.
    labels0 = jnp.asarray((np.asarray(ctx0.pred) + 1) % cfg.elm.n_out, jnp.int32)

    # run_fleet anchor: same state, same tick, zero-latency labels — the
    # disagreement on a low-confidence query steps theta UP (level - 1).
    ref_st, _ = engine.run_fleet(
        st0, x0[None], labels0[None], cfg, mode="train_phase"
    )
    ref_delta = np.asarray(ref_st.prune.level) - np.asarray(st0.prune.level)
    np.testing.assert_array_equal(ref_delta, [-1, -1])

    # While labels0 is in flight, later replies train the SAME streams until
    # the local prediction flips to agree with labels0 (out-of-order answers
    # landing first — the jitter case).
    st = st1
    for _ in range(8):
        st_probe, ctx_i = engine.gate(st, x0, cfg)
        st = engine.apply_labels(
            st_probe, ctx_i, labels0, jnp.ones((s_len,), bool), cfg
        )
        _, ctx_now = engine.gate(st, x0, cfg)
        if bool(jnp.all(ctx_now.pred == labels0)):
            break
    assert bool(jnp.all(ctx_now.pred == labels0)), "intervening training failed"
    base = np.asarray(st.prune.level)
    assert (base >= 1).all(), "need headroom to observe the step-up"

    mask = jnp.ones((s_len,), bool)
    # Fixed path: plan-time judgment — the delayed disagreement steps the
    # ladder up (level - 1), exactly the run_fleet transition above.
    st_new = engine.apply_labels(st, ctx0, labels0, mask, cfg)
    np.testing.assert_array_equal(
        np.asarray(st_new.prune.level) - base, ref_delta
    )
    # The recompute path (judge against the *current* weights, where the
    # prediction now agrees and the stale judgment would miss the step-up)
    # is gone for good: raw features are rejected outright.
    with pytest.raises(TypeError, match="plan-time"):
        engine.apply_labels(st, ctx0.feats, labels0, mask, cfg)
    # And the fixed path trains on the plan-time activations of x0.
    assert float(jnp.max(jnp.abs(st_new.elm.beta - st.elm.beta))) > 0


def test_serve_mode_plan_learn_is_gate_apply_labels_bit_for_bit():
    """``plan(mode='serve')``/``learn`` must be the same state machine as
    ``gate``/``apply_labels`` — the multiplexed serve driver keeps the live
    drift detector (pruning condition 2) the single-tenant gate path has."""
    cfg = _cfg(min_trained=2)
    s_len = 3
    st_gate = st_plan = engine.init_fleet(cfg, s_len)
    key = jax.random.PRNGKey(21)
    for t in range(12):
        key, kx = jax.random.split(key)
        x = jnp.tanh(jax.random.normal(kx, (s_len, cfg.elm.n_in))) * (1 + t % 3)
        labels = jnp.asarray([t % cfg.elm.n_out] * s_len, jnp.int32)

        st_gate2, gout = engine.gate(st_gate, x, cfg)
        st_gate = engine.apply_labels(st_gate2, gout, labels, gout.queried, cfg)

        st_plan2, pout = engine.plan(st_plan, x, cfg, mode="serve")
        st_plan = engine.learn(
            st_plan2, pout.h, labels, pout.pred, pout.confidence, pout.queried,
            pout.controller_on, cfg, theta=pout.theta,
        )
        np.testing.assert_array_equal(
            np.asarray(gout.queried), np.asarray(pout.queried), err_msg=f"tick {t}"
        )
        _assert_state_equal(st_gate, st_plan, msg=f"tick {t}")
    assert int(np.asarray(st_plan.elm.count).sum()) > 0  # the loop trained


# ---------------------------------------------------------------------------
# RpcTeacher loopback (tentpole) — real socket, timeout -> loss
# ---------------------------------------------------------------------------


def test_rpc_teacher_loopback_roundtrip_through_stream_run():
    """The full runtime against a real TCP label server: every query is
    answered with the server's deterministic labels and the accounting
    reconciles — LatencyTeacher is no longer the only latency model."""
    cfg = _cfg(min_trained=1_000_000)
    t_len, s_len = 6, 3
    xs, _ = _stream_data(cfg, t_len, s_len, seed=14)
    with rpc.loopback_server(n_out=cfg.elm.n_out) as (host, port):
        with rpc.RpcTeacher(host, port, timeout_s=30.0) as teacher:
            st, outs, stats = stream.run(
                engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher,
                mode="train_phase",
            )
    assert stats.labels_applied == stats.queries_issued == t_len * s_len
    assert outs.trained.all()
    assert int(np.asarray(st.elm.count).sum()) == t_len * s_len
    _assert_reconciled(stats)


def test_rpc_teacher_timeout_maps_to_loss():
    """A server slower than the client deadline: every ticket expires out of
    in_flight, the ring drains as queries_lost, and the straggler replies
    are never applied."""
    cfg = _cfg(min_trained=1_000_000)
    t_len, s_len = 3, 2
    xs, _ = _stream_data(cfg, t_len, s_len, seed=15)
    with rpc.loopback_server(n_out=cfg.elm.n_out, delay_s=1.0) as (host, port):
        with rpc.RpcTeacher(host, port, timeout_s=0.05) as teacher:
            st, outs, stats = stream.run(
                engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher,
                mode="train_phase",
            )
            assert teacher.in_flight() == 0
    assert stats.labels_applied == 0
    assert not outs.trained.any()
    assert stats.queries_lost == stats.queries_issued == t_len * s_len
    assert int(np.asarray(st.elm.count).sum()) == 0  # stragglers never train
    _assert_reconciled(stats)


# ---------------------------------------------------------------------------
# Multiplexed faults: per-tenant isolation of accounting and state
# ---------------------------------------------------------------------------


def test_multiplex_mixed_policies_and_faults_reconcile_per_tenant():
    """Tenants with different backpressure policies and fault models run
    side by side; each tenant's accounting reconciles independently."""
    cfg_a = _cfg(n_hidden=16, min_trained=1_000_000)
    cfg_b = _cfg(n_hidden=32, min_trained=1_000_000)
    xs_a, ys_a = _stream_data(cfg_a, 30, 3, seed=16)
    xs_b, ys_b = _stream_data(cfg_b, 20, 2, seed=17)
    tenants = [
        multiplex.Tenant(
            name="lossy",
            state=engine.init_fleet(cfg_a, 3),
            ticks=(x for x in xs_a),
            cfg=cfg_a,
            teacher=stream.LatencyTeacher(
                stream.array_labels(ys_a), latency=2, jitter=3, loss_prob=0.3,
                partial_prob=0.2, seed=18,
            ),
            mode="train_phase",
            capacity=3,
            backpressure="drop_oldest",
        ),
        multiplex.Tenant(
            name="coalescing",
            state=engine.init_fleet(cfg_b, 2),
            ticks=(x for x in xs_b),
            cfg=cfg_b,
            teacher=stream.LatencyTeacher(
                stream.array_labels(ys_b), latency=6, seed=19
            ),
            mode="train_phase",
            capacity=2,
            backpressure="coalesce",
        ),
    ]
    results, agg = multiplex.run(tenants)
    assert results["lossy"].stats.queries_issued == 30 * 3
    assert results["coalescing"].stats.queries_coalesced > 0
    for name, policy in (("lossy", "drop_oldest"), ("coalescing", "coalesce")):
        _assert_reconciled(results[name].stats, policy)
    assert agg.stream_steps == 30 * 3 + 20 * 2


def test_multiplex_rejects_duplicate_names_and_empty():
    cfg = _cfg()
    with pytest.raises(ValueError, match="at least one"):
        multiplex.run([])
    t = multiplex.Tenant(
        name="dup", state=engine.init_fleet(cfg, 2), ticks=iter(()), cfg=cfg,
        teacher=stream.LatencyTeacher(lambda t_, f: np.zeros(2, np.int32)),
    )
    with pytest.raises(ValueError, match="unique"):
        multiplex.run([t, t])


# ---------------------------------------------------------------------------
# Deficit round robin (ISSUE 4 satellite): size-fair scheduling
# ---------------------------------------------------------------------------


def test_drr_is_bit_for_bit_and_does_not_let_big_tenants_starve_small():
    """DRR charges a tick its stream count: a big tenant advances ~1 tick
    per round while a small one keeps its full quantum — many more scheduler
    rounds than rr's fixed quantum-tick slices (the observable fairness
    property) — and per-tenant results stay bit-for-bit identical to rr
    (scheduling order can never change results)."""
    cfg_small, cfg_big = _cfg(n_hidden=16, min_trained=4), _cfg(n_hidden=16, min_trained=4)
    t_len = 24
    xs_s, ys_s = _stream_data(cfg_small, t_len, 2, seed=30)
    xs_b, ys_b = _stream_data(cfg_big, t_len, 16, seed=31)

    def tenants():
        return [
            multiplex.Tenant(
                name="small", state=engine.init_fleet(cfg_small, 2),
                ticks=(x for x in xs_s), cfg=cfg_small,
                teacher=stream.LatencyTeacher(stream.array_labels(ys_s), latency=0),
                mode="train_phase",
            ),
            multiplex.Tenant(
                name="big", state=engine.init_fleet(cfg_big, 16),
                ticks=(x for x in xs_b), cfg=cfg_big,
                teacher=stream.LatencyTeacher(stream.array_labels(ys_b), latency=0),
                mode="train_phase",
            ),
        ]

    res_rr, agg_rr = multiplex.run(tenants(), sched="rr")

    # Drive drr round by round and watch the big tenant's per-round tick
    # budget while the small tenant is still live.
    mux = multiplex.Multiplexer(tenants(), sched="drr")
    big_while_small_live = []
    while mux.round():
        if mux._slot("small").result is None:
            big_while_small_live.append(mux._slot("big").unit.last_ticks)
    res_drr, agg_drr = mux.results()

    for name in ("small", "big"):
        _assert_state_equal(res_rr[name].state, res_drr[name].state, msg=name)
        for field in res_rr[name].outputs._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(res_rr[name].outputs, field)),
                np.asarray(getattr(res_drr[name].outputs, field)),
                err_msg=f"{name} output {field!r} diverged under drr",
            )
        _assert_reconciled(res_drr[name].stats)
    assert agg_drr.stream_steps == agg_rr.stream_steps
    # rr would give the big tenant its full 8-tick slice every round,
    # blocking the small tenant for 8 heavy (8x-S) ticks at a time.  drr's
    # per-round credit is quantum * S_small = one big tick (+carry): while
    # the small tenant is live, the big one never hogs the device — and
    # once the small tenant finishes, drr is work-conserving (the credit
    # recomputes over live tenants, so the big one speeds back up).
    assert big_while_small_live, "small tenant never observed live"
    assert max(big_while_small_live) <= 2, big_while_small_live
    assert agg_drr.rounds >= agg_rr.rounds


def test_scheduler_is_validated():
    cfg = _cfg()
    t = multiplex.Tenant(
        name="t", state=engine.init_fleet(cfg, 2), ticks=iter(()), cfg=cfg,
        teacher=stream.LatencyTeacher(lambda t_, f: np.zeros(2, np.int32)),
    )
    with pytest.raises(ValueError, match="scheduler"):
        multiplex.run([t], sched="fifo")


# ---------------------------------------------------------------------------
# RPC teacher auth (ISSUE 4 satellite): HMAC challenge-response on connect
# ---------------------------------------------------------------------------


def test_rpc_auth_roundtrip_and_rejection():
    """A client with the right secret round-trips labels; the wrong secret
    (or none) gets the connection closed before any label — the asks map to
    timeout->loss and the fleet never trains on an unauthenticated server."""
    cfg = _cfg(min_trained=1_000_000)
    t_len, s_len = 4, 2
    xs, _ = _stream_data(cfg, t_len, s_len, seed=32)
    server = rpc.LabelServer(n_out=cfg.elm.n_out, secret="paper-s3cret").start()
    try:
        with rpc.RpcTeacher("127.0.0.1", server.port, timeout_s=30.0,
                            secret="paper-s3cret") as teacher:
            st, outs, stats = stream.run(
                engine.init_fleet(cfg, s_len), (x for x in xs), cfg, teacher,
                mode="train_phase",
            )
        assert stats.labels_applied == stats.queries_issued == t_len * s_len
        assert outs.trained.all()
        _assert_reconciled(stats)

        # Wrong secret: the server rejects the digest and closes without
        # proving itself, so the client fails fast at connect.
        with pytest.raises(ConnectionError):
            rpc.RpcTeacher("127.0.0.1", server.port, timeout_s=0.3,
                           secret="wrong")
        # No secret at all: the client skips the handshake, the server
        # closes the unauthenticated connection, and every ask maps to
        # timeout->loss — the fleet never trains.
        with rpc.RpcTeacher("127.0.0.1", server.port, timeout_s=0.3,
                            secret=None) as teacher:
            st, outs, stats = stream.run(
                engine.init_fleet(cfg, s_len), (x for x in xs), cfg,
                teacher, mode="train_phase",
            )
        assert stats.labels_applied == 0
        assert stats.queries_lost == stats.queries_issued == t_len * s_len
        assert int(np.asarray(st.elm.count).sum()) == 0
        _assert_reconciled(stats)
        assert server.auth_failures >= 2
    finally:
        server.close()


def test_rpc_client_refuses_unauthenticated_server():
    """A client configured with a secret must refuse a server that opens
    with no challenge (it is not speaking the authenticated protocol)."""
    server = rpc.LabelServer(n_out=4).start()  # no secret on the server
    try:
        with pytest.raises((ConnectionError, OSError)):
            rpc.RpcTeacher("127.0.0.1", server.port, timeout_s=1.0,
                           connect_timeout_s=0.5, secret="expects-auth")
    finally:
        server.close()


def test_rpc_client_refuses_imposter_server():
    """Auth is mutual: a rogue endpoint that emits a challenge (to fish for
    the client's digest) but cannot answer the client's nonce must be
    refused before any of its labels can train the fleet."""
    import json as json_mod
    import socket
    import threading

    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.bind(("127.0.0.1", 0))
    sock.listen(1)
    port = sock.getsockname()[1]

    def imposter():
        conn, _ = sock.accept()
        with conn, conn.makefile("rwb") as f:
            f.write(b'{"challenge": "00"}\n')
            f.flush()
            f.readline()  # harvest the client's digest...
            # ...but answer the client's nonce with garbage (no secret).
            f.write((json_mod.dumps({"auth_ok": "deadbeef"}) + "\n").encode())
            f.flush()

    t = threading.Thread(target=imposter, daemon=True)
    t.start()
    try:
        with pytest.raises(ConnectionError, match="prove knowledge"):
            rpc.RpcTeacher("127.0.0.1", port, timeout_s=1.0,
                           connect_timeout_s=2.0, secret="the-real-secret")
    finally:
        sock.close()
