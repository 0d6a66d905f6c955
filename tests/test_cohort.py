"""Cohort fusion — ISSUE 6.

Same-shaped tenants stack into one batched plan/learn dispatch per
quantum (``repro.engine.cohort``) with everything tenant-visible kept
per-tenant.  Covers: fused == unfused == solo bit-for-bit (both
schedulers, several quanta, faulty teachers, unequal stream lengths so
members detach mid-run), mixed-shape packing into separate cohorts,
migration OUT of a live fused cohort and restore INTO a cohort slot
(exact accounting reconciliation), mid-stream admission into a running
fused multiplexer, and the patch-learn runner's bitwise identity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import drift as drift_mod
from repro.core import oselm, pruning
from repro.engine import cohort, fleet, multiplex, snapshot, stream

DETERMINISTIC_STATS = (
    "ticks", "stream_steps", "tickets_issued", "queries_issued",
    "labels_applied", "tickets_dropped", "queries_dropped",
    "replies_orphaned", "tickets_lost", "queries_lost",
    "tickets_coalesced", "queries_coalesced", "asks_deferred",
    "tickets_reasked",
)


def _cfg(n_in=24, n_hidden=16, n_out=4, min_trained=4):
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


def _lossy_teacher(ys, seed=11):
    return stream.LatencyTeacher(
        stream.array_labels(ys), latency=2, jitter=2, loss_prob=0.15,
        partial_prob=0.15, seed=seed,
    )


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


def _assert_outputs_equal(a, b, msg=""):
    for name in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{msg} output {name!r} diverged",
        )


def _solo(cfg, xs, teacher, **kw):
    return stream.run(
        engine.init_fleet(cfg, xs.shape[1]), (x for x in xs), cfg, teacher,
        mode="train_phase", **kw,
    )


def _fused(mux):
    """The tenant names of each live unit that holds several tenants."""
    return sorted(sorted(s.tenant.name for s in u.slots)
                  for u in mux._live if len(u.slots) > 1)


def _tenant(name, cfg, xs, teacher, **kw):
    return multiplex.Tenant(
        name=name, state=engine.init_fleet(cfg, xs.shape[1]),
        ticks=(x for x in xs), cfg=cfg, teacher=teacher,
        mode="train_phase", **kw,
    )


# ---------------------------------------------------------------------------
# Tentpole acceptance: fused == unfused == solo, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sched", ["rr", "drr"])
@pytest.mark.parametrize("quantum", [1, 3])
def test_fused_matches_unfused_and_solo_bit_for_bit(sched, quantum):
    """Four same-shaped tenants under faulty teachers, UNEQUAL stream
    lengths (members exhaust and detach mid-run, the cohort restacks and
    finally ticks its last member as a group of one): the fused
    multiplexer must reproduce both the
    unfused multiplexer and four solo runs exactly — states, collected
    outputs, every deterministic counter, label-latency histogram."""
    cfg = _cfg()
    lens = [30, 30, 22, 14]  # detach + shrink paths, not just lockstep
    datas = [_stream_data(cfg, t, 4, seed=70 + i) for i, t in enumerate(lens)]

    solo = [
        _solo(cfg, xs, _lossy_teacher(ys, seed=80 + i), capacity=4,
              backpressure="coalesce")
        for i, (xs, ys) in enumerate(datas)
    ]

    def tenants():
        return [
            _tenant(f"tenant{i}", cfg, xs, _lossy_teacher(ys, seed=80 + i),
                    capacity=4, backpressure="coalesce")
            for i, (xs, ys) in enumerate(datas)
        ]

    unfused, _ = multiplex.run(tenants(), sched=sched, quantum=quantum,
                               fuse=False)

    mux = multiplex.Multiplexer(tenants(), sched=sched, quantum=quantum,
                                fuse=True)
    assert mux.round()  # one scheduling round forms the cohort...
    assert _fused(mux) == [[f"tenant{i}" for i in range(4)]], (
        "4 same-shaped tenants must actually fuse (else this test "
        "proves nothing)"
    )
    fused, agg = mux.run()

    assert agg.n_tenants == 4
    for i, (st, outs, stats) in enumerate(solo):
        for label, results in (("fused", fused), ("unfused", unfused)):
            r = results[f"tenant{i}"]
            _assert_state_equal(st, r.state, msg=f"{label} tenant{i}")
            _assert_outputs_equal(outs, r.outputs, msg=f"{label} tenant{i}")
            _assert_stats_equal(stats, r.stats, msg=f"{label} tenant{i}")
            assert r.stats.reconciled, r.stats.summary()


def test_fused_cohort_wide_enough_to_compact_matches_solo_bit_for_bit():
    """Two tenants each narrower than twice the floor learn at full width
    alone; stacked, their fused learns run over the answered rows alone
    (``stream.learn_rows_index``) — and states, outputs and every
    deterministic counter are still the solo runs', bit for bit."""
    cfg = _cfg()
    width = stream.LEARN_ROWS_FLOOR + 64
    datas = []
    for i in range(2):
        xs, ys = _stream_data(cfg, 50, width, seed=90 + i)
        ys = ys.copy()
        ys[:, np.arange(width) % 4 != 0] = 0  # three in four streams go quiet
        datas.append((xs, ys))

    def teacher(ys, i):
        return stream.LatencyTeacher(
            stream.array_labels(ys), latency=1, jitter=1, loss_prob=0.1,
            partial_prob=0.15, seed=95 + i,
        )

    solo = [_solo(cfg, xs, teacher(ys, i), capacity=8)
            for i, (xs, ys) in enumerate(datas)]
    mux = multiplex.Multiplexer(
        [_tenant(f"t{i}", cfg, xs, teacher(ys, i), capacity=8)
         for i, (xs, ys) in enumerate(datas)],
        quantum=2, fuse=True)
    assert mux.round()
    assert _fused(mux) == [["t0", "t1"]]
    fused, _ = mux.run()

    for i, (st, outs, stats) in enumerate(solo):
        r = fused[f"t{i}"]
        assert stats.learns_compact == 0 < r.stats.learns_compact
        _assert_state_equal(st, r.state, msg=f"t{i}")
        _assert_outputs_equal(outs, r.outputs, msg=f"t{i}")
        _assert_stats_equal(stats, r.stats, msg=f"t{i}")
        assert r.stats.reconciled, r.stats.summary()


# ---------------------------------------------------------------------------
# Mixed shapes: separate cohorts, same results
# ---------------------------------------------------------------------------


def test_mixed_shapes_pack_into_separate_cohorts():
    """Different (cfg) pairs land in different cohorts; a tenant with a
    different stream width joins neither.  Everyone still matches solo."""
    cfg_a, cfg_b = _cfg(n_hidden=16), _cfg(n_hidden=32)
    specs = [  # (name, cfg, S)
        ("a0", cfg_a, 3), ("a1", cfg_a, 3),
        ("b0", cfg_b, 3), ("b1", cfg_b, 3),
        ("w", cfg_a, 2),  # same cfg as a*, different width: stays solo
    ]
    datas = {
        name: _stream_data(c, 18, s, seed=110 + i)
        for i, (name, c, s) in enumerate(specs)
    }

    solo = {
        name: _solo(c, datas[name][0], _lossy_teacher(datas[name][1], seed=5))
        for name, c, s in specs
    }

    mux = multiplex.Multiplexer([
        _tenant(name, c, datas[name][0], _lossy_teacher(datas[name][1], seed=5))
        for name, c, s in specs
    ], fuse=True)
    assert mux.round()
    # "w" fused nowhere
    assert _fused(mux) == [["a0", "a1"], ["b0", "b1"]]
    results, _ = mux.run()

    for name, c, s in specs:
        st, outs, stats = solo[name]
        _assert_state_equal(st, results[name].state, msg=name)
        _assert_outputs_equal(outs, results[name].outputs, msg=name)
        _assert_stats_equal(stats, results[name].stats, msg=name)


# ---------------------------------------------------------------------------
# Migration out of a fused cohort / restore into a cohort slot
# ---------------------------------------------------------------------------


def test_migrate_out_of_fused_cohort_and_restore_into_cohort_slot():
    """Mid-stream, extract a tenant OUT of a live fused cohort (quiesce
    disabled: its pending ring travels with the snapshot), restore it
    into a second fused multiplexer where it lands in a new cohort slot
    (its restored in-flight solo plans ride the patch path), and finish.
    Both the migrated tenant and every stay-behind must be bit-for-bit
    equal to uninterrupted solo runs, with exact accounting."""
    cfg = _cfg()
    t_len, s_len = 30, 3
    datas = [_stream_data(cfg, t_len, s_len, seed=90 + i) for i in range(3)]
    xs_d, ys_d = _stream_data(cfg, t_len, s_len, seed=94)  # mux_b companion

    def teacher(i):
        return stream.LatencyTeacher(
            stream.array_labels(datas[i][1] if i < 3 else ys_d),
            latency=2, jitter=2, loss_prob=0.2, seed=60 + i,
        )

    solo = [
        _solo(cfg, datas[i][0], teacher(i), capacity=4, collect=False)
        for i in range(3)
    ]
    solo_d = _solo(cfg, xs_d, teacher(3), capacity=4, collect=False)

    mux = multiplex.Multiplexer([
        multiplex.Tenant(
            name=f"t{i}", state=engine.init_fleet(cfg, s_len),
            ticks=snapshot.array_ticks(datas[i][0]), cfg=cfg,
            teacher=teacher(i), mode="train_phase", capacity=4, collect=False,
        )
        for i in range(3)
    ], fuse=True)
    while mux.round():
        if mux.session("t0").t >= 13:
            break
    assert len(mux._slot("t0").unit.slots) > 1, "t0 must be fused when extracted"
    tree, rest = mux.extract("t0", quiesce_ticks=0)
    results_a, _ = mux.run()  # t1/t2 keep going (re-fused as a pair)

    mux_b = multiplex.Multiplexer([
        multiplex.Tenant(
            name="d", state=engine.init_fleet(cfg, s_len),
            ticks=(x for x in xs_d), cfg=cfg, teacher=teacher(3),
            mode="train_phase", capacity=4, collect=False,
        )
    ], fuse=True)
    mux_b.admit(
        multiplex.Tenant(
            name="t0", state=None, ticks=rest, cfg=cfg, teacher=teacher(0),
            mode="train_phase", capacity=4, collect=False,
        ),
        snapshot=tree,
        positioned=True,  # rest is extract()'s live iterator
    )
    assert mux_b.round()
    assert _fused(mux_b) == [["d", "t0"]], (
        "the restored tenant must land in a cohort slot"
    )
    results_b, _ = mux_b.run()

    _assert_state_equal(solo[0][0], results_b["t0"].state, msg="migrated t0")
    _assert_stats_equal(solo[0][2], results_b["t0"].stats, msg="migrated t0")
    assert results_b["t0"].stats.tickets_reasked == 0  # teacher state travelled
    assert results_b["t0"].stats.reconciled, results_b["t0"].stats.summary()
    _assert_state_equal(solo_d[0], results_b["d"].state, msg="companion d")
    _assert_stats_equal(solo_d[2], results_b["d"].stats, msg="companion d")
    for i in (1, 2):
        _assert_state_equal(solo[i][0], results_a[f"t{i}"].state, msg=f"t{i}")
        _assert_stats_equal(solo[i][2], results_a[f"t{i}"].stats, msg=f"t{i}")
        assert results_a[f"t{i}"].stats.reconciled


# ---------------------------------------------------------------------------
# Mid-stream admission into a running fused multiplexer
# ---------------------------------------------------------------------------


def test_admit_into_running_fused_mux_joins_cohort_and_matches_solo():
    cfg = _cfg()
    datas = [_stream_data(cfg, 24, 3, seed=100 + i) for i in range(3)]
    solo = [
        _solo(cfg, xs, _lossy_teacher(ys, seed=50 + i))
        for i, (xs, ys) in enumerate(datas)
    ]

    mux = multiplex.Multiplexer([
        _tenant(f"t{i}", cfg, datas[i][0], _lossy_teacher(datas[i][1], seed=50 + i))
        for i in range(2)
    ], fuse=True, quantum=2)
    for _ in range(4):
        assert mux.round()
    mux.admit(_tenant("t2", cfg, datas[2][0],
                      _lossy_teacher(datas[2][1], seed=52)))
    assert mux.round()
    assert _fused(mux) == [["t0", "t1", "t2"]], "late tenant must join the cohort"
    results, _ = mux.run()

    for i, (st, outs, stats) in enumerate(solo):
        _assert_state_equal(st, results[f"t{i}"].state, msg=f"t{i}")
        _assert_outputs_equal(outs, results[f"t{i}"].outputs, msg=f"t{i}")
        _assert_stats_equal(stats, results[f"t{i}"].stats, msg=f"t{i}")


# ---------------------------------------------------------------------------
# Patch-learn runner: bitwise unit identity
# ---------------------------------------------------------------------------


def test_patch_learn_runner_is_bitwise_solo_learn_on_the_slice():
    """``fleet._patch_learn_runner(cfg, lo, hi)`` == slice the stacked
    state, run the solo masked learn, write the slice back — bitwise,
    and rows outside [lo, hi) are untouched."""
    cfg = _cfg(min_trained=1)
    total, lo, hi = 7, 2, 5
    key = jax.random.PRNGKey(3)
    x = jnp.tanh(jax.random.normal(key, (total, cfg.elm.n_in)))
    state, p = fleet.plan(
        engine.init_fleet(cfg, total), x, cfg, mode="train_phase"
    )
    labels = jnp.asarray(np.arange(total) % cfg.elm.n_out, jnp.int32)
    mask = jnp.asarray(np.array([True, False, True]), bool)  # width hi-lo

    # Reference: the solo session's own jitted learn dispatch on the
    # slice, written back — the exact dispatch a solo run would make.
    sub = fleet.slice_streams(state, lo, hi)
    sub_p_h = p.h[lo:hi]
    new_elm_s, new_prune_s = stream._learn_runner(cfg, False)(
        sub.elm, sub.prune, sub.drift, sub.meter,
        sub_p_h, labels[lo:hi], p.pred[lo:hi], p.confidence[lo:hi],
        mask, p.controller_on[lo:hi], p.theta[lo:hi],
    )
    want = state._replace(
        elm=jax.tree.map(lambda f, s: f.at[lo:hi].set(s), state.elm, new_elm_s),
        prune=jax.tree.map(lambda f, s: f.at[lo:hi].set(s), state.prune,
                           new_prune_s),
    )

    runner = fleet._patch_learn_runner(cfg, lo, hi, False)
    new_elm, new_prune = runner(
        state.elm, state.prune, state.drift, state.meter,
        sub_p_h, labels[lo:hi], p.pred[lo:hi], p.confidence[lo:hi],
        mask, p.controller_on[lo:hi], p.theta[lo:hi],
    )
    got = state._replace(elm=new_elm, prune=new_prune)
    _assert_state_equal(want, got, msg="patch-learn")
    # Rows outside the patch are bit-identical to the pre-learn state.
    for side in ((0, lo), (hi, total)):
        _assert_state_equal(
            fleet.slice_streams(state.elm, *side),
            fleet.slice_streams(got.elm, *side),
            msg=f"rows {side} must be untouched",
        )


# ---------------------------------------------------------------------------
# CohortSession contract checks
# ---------------------------------------------------------------------------


def test_cohort_rejects_mismatched_members():
    cfg_a, cfg_b = _cfg(n_hidden=16), _cfg(n_hidden=32)
    xs_a, ys_a = _stream_data(cfg_a, 4, 2, seed=1)
    xs_b, ys_b = _stream_data(cfg_b, 4, 2, seed=2)

    def sess(cfg, ys, mode="train_phase"):
        return stream.StreamSession(
            engine.init_fleet(cfg, 2), cfg,
            stream.LatencyTeacher(stream.array_labels(ys)), mode=mode,
        )

    with pytest.raises(ValueError):
        cohort.CohortSession([sess(cfg_a, ys_a), sess(cfg_b, ys_b)])
    with pytest.raises(ValueError):
        cohort.CohortSession(
            [sess(cfg_a, ys_a), sess(cfg_a, ys_a, mode="serve")]
        )
    with pytest.raises(ValueError):
        cohort.CohortSession([])  # a cohort can't be empty
