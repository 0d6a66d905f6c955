"""Streaming async-teacher runtime tests: zero-latency bit-for-bit parity
with run_fleet, out-of-order deferred labels, ring overflow, permanent
teacher outage, and the scalar-API confinement rule."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import drift as drift_mod
from repro.core import oselm, pruning
from repro.engine import stream


def _cfg(n_in=24, n_hidden=16, n_out=4, min_trained=16):
    return engine.EngineConfig(
        elm=oselm.OSELMConfig(
            n_in=n_in, n_hidden=n_hidden, n_out=n_out, variant="hash", ridge=1e-2
        ),
        prune=pruning.PruneConfig(min_trained=min_trained),
        drift=drift_mod.DriftConfig(warmup=16, k_sigma=3.0, enter_hits=2, exit_calm=16),
    )


def _stream_data(cfg, t, s, seed=0, shift_at=None):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    xs = np.array(jnp.tanh(jax.random.normal(kx, (t, s, cfg.elm.n_in))))
    if shift_at is not None:
        sev = np.linspace(2.0, 4.0, s)[None, :, None]
        xs[shift_at:] = np.clip(xs[shift_at:] * sev + 0.5 * sev, -4, 4)
    ys = np.asarray(jax.random.randint(ky, (t, s), 0, cfg.elm.n_out), np.int32)
    return jnp.asarray(xs), ys


@pytest.mark.parametrize("mode", ["algo1", "train_phase"])
def test_zero_latency_matches_run_fleet_bit_for_bit(mode):
    """stream.run with an instant teacher IS run_fleet: every output field
    and every leaf of the final state must match bit-for-bit (plan/learn
    are the exact two halves of fleet_step)."""
    cfg = _cfg()
    t_len, s_len = 90, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=1, shift_at=40)

    st_f, out_f = engine.run_fleet(
        engine.init_fleet(cfg, s_len), xs, jnp.asarray(ys), cfg, mode=mode
    )

    teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=0)
    st_s, out_s, stats = stream.run(
        engine.init_fleet(cfg, s_len), (xs[t] for t in range(t_len)), cfg,
        teacher, mode=mode,
    )

    for name in out_f._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(out_f, name)), np.asarray(getattr(out_s, name)),
            err_msg=f"output field {name!r} diverged",
        )
    for (path_a, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(st_f)[0],
        jax.tree_util.tree_flatten_with_path(st_s)[0],
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"state leaf {path_a} diverged"
        )
    assert stats.ticks == t_len
    assert stats.labels_applied == stats.queries_issued > 0
    assert stats.tickets_dropped == stats.tickets_lost == stats.replies_orphaned == 0
    assert stats.label_latency_p95 == 0.0


def test_zero_latency_matches_run_fleet_on_the_har_protocol():
    """The same parity at the paper's widths (n_in 561, N 128) on the HAR
    surrogate, with batch-initialized heads and the drift detector armed
    as the paper loop arms it — the comparison the chip smoke repeats."""
    import dataclasses

    from repro.configs import har_odl
    from repro.data import har

    cfg = dataclasses.replace(har_odl.full(), drift=drift_mod.DriftConfig(
        warmup=48, k_sigma=3.0, enter_hits=2, exit_calm=64))
    data = har.generate(seed=0)
    head = oselm.init_state_batch(cfg.elm, jnp.asarray(data.train_x),
                                  jax.nn.one_hot(data.train_y, cfg.elm.n_out))
    s = 4
    ticks, labels = har.drift_tick_stream(
        data, n_streams=s, calm=400, severities=np.linspace(1.0, 3.0, s))
    xs = np.stack(list(ticks))

    def fresh():
        return engine.broadcast_streams(
            engine.init_state(cfg)._replace(elm=head), s)

    st_f, out_f = engine.run_fleet(fresh(), jnp.asarray(xs),
                                   jnp.asarray(labels), cfg, mode="algo1")
    st_s, out_s, stats = stream.run(
        fresh(), iter(xs), cfg,
        stream.LatencyTeacher(stream.array_labels(labels), latency=0),
        mode="algo1")
    assert stats.labels_applied > 0
    for name in out_f._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(out_f, name)), np.asarray(getattr(out_s, name)),
            err_msg=f"output field {name!r} diverged")
    for a, b in zip(jax.tree.leaves(st_f), jax.tree.leaves(st_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_deferred_out_of_order_labels_train_on_query_time_features():
    """Jittered latency delivers answers out of order; every answered query
    must still train (count increments) and ``trained`` marks the tick the
    query was issued at, never a tick that was not queried."""
    cfg = _cfg(min_trained=1)
    t_len, s_len = 40, 4
    xs, ys = _stream_data(cfg, t_len, s_len, seed=2)

    teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=2, jitter=5, seed=3)
    st0 = engine.init_fleet(cfg, s_len)
    st, outs, stats = stream.run(
        st0, (xs[t] for t in range(t_len)), cfg, teacher, mode="train_phase",
    )

    assert stats.labels_applied > 0
    assert stats.labels_applied == int(np.asarray(st.elm.count).sum())
    assert stats.labels_applied == int(outs.trained.sum())
    # trained ⊆ queried, per tick (labels only ever apply to asked samples).
    assert not np.any(outs.trained & ~outs.queried)
    # The jitter actually exercised the out-of-order path.
    lat = np.asarray(stats.label_latency_ticks)
    assert lat.min() >= 2 and lat.max() > lat.min()
    assert stats.tickets_lost == 0 and len(teacher._inbox) == 0


def test_ring_overflow_drops_oldest_and_meters_it():
    """With capacity 2 and a teacher slower than the stream, only the two
    youngest tickets survive; evictions and orphaned replies are counted."""
    cfg = _cfg(min_trained=1_000_000)  # cold heads: every tick queries
    t_len, s_len = 6, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=4)

    teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=50)
    st, outs, stats = stream.run(
        engine.init_fleet(cfg, s_len), (xs[t] for t in range(t_len)), cfg,
        teacher, mode="train_phase", capacity=2,
    )

    assert stats.tickets_issued == t_len
    assert stats.tickets_dropped == t_len - 2
    assert stats.queries_dropped == (t_len - 2) * s_len
    # The drain waits out the latency: the 2 surviving tickets apply, the
    # 4 evicted tickets' late answers arrive as orphans.
    assert stats.labels_applied == 2 * s_len
    assert stats.replies_orphaned == t_len - 2
    assert stats.tickets_lost == 0
    np.testing.assert_array_equal(outs.trained.sum(axis=0), [2, 2, 2])
    np.testing.assert_array_equal(outs.trained[-2:], np.ones((2, s_len), bool))


def test_permanent_outage_leaves_heads_identical_to_never_queried():
    """A teacher that never answers must leave every head bit-identical to
    a run where the teacher was known-unavailable (no training on garbage),
    while the queries it swallowed are still metered as lost."""
    cfg = _cfg(min_trained=1)
    t_len, s_len = 30, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=5)

    dead = stream.LatencyTeacher(stream.array_labels(ys), latency=0, outage_after=0)
    st_out, outs_out, stats = stream.run(
        engine.init_fleet(cfg, s_len), (xs[t] for t in range(t_len)), cfg,
        dead, mode="train_phase",
    )

    st_ref, outs_ref = engine.run_fleet(
        engine.init_fleet(cfg, s_len), xs, jnp.asarray(ys), cfg,
        mode="train_phase",
        teacher_available=jnp.zeros((t_len, s_len), jnp.bool_),
    )

    for a, b in zip(jax.tree.leaves(st_out.elm), jax.tree.leaves(st_ref.elm)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(outs_out.pred, np.asarray(outs_ref.pred))
    assert stats.labels_applied == 0
    assert not outs_out.trained.any()
    assert stats.tickets_lost == stats.tickets_issued > 0
    assert stats.queries_issued > 0  # queries were issued (and metered) ...
    assert float(jnp.sum(st_out.meter.total)) > 0  # ... bytes left the edge


def test_deferred_ladder_judges_against_query_time_theta():
    """A disagreeing low-confidence query whose answer arrives after the
    ladder stepped down must still raise theta (paper §2.2: a query
    revealing disagreement steps UP) — the runtime passes the plan-time
    threshold into the deferred controller update."""
    cfg = pruning.PruneConfig()  # ladder (1.0, .64, .32, .16, .08)
    st = pruning.init_fleet(1)._replace(level=jnp.asarray([2]))  # theta now 0.32
    conf = jnp.asarray([0.5], jnp.float32)  # below theta=0.64 at query time
    q = jnp.asarray([True])
    disagree = jnp.asarray([False])
    # Judged at the current (post-step-down) theta the mismatch is masked...
    cur = pruning.update(st, q, disagree, conf, cfg)
    assert int(cur.level[0]) == 2
    # ...but judged at the query-time theta it steps the ladder back up.
    deferred = pruning.update(st, q, disagree, conf, cfg, theta=jnp.asarray([0.64]))
    assert int(deferred.level[0]) == 1


def test_runner_caches_are_bounded_with_counters():
    """The compiled-runner caches must be bounded (no leak per retired
    config in a long-lived server) and expose hit/miss counters."""
    info = stream.cache_stats()
    for name in ("chunk_runner", "plan_runner", "learn_runner"):
        assert info[name]["maxsize"] == engine.fleet.RUNNER_CACHE_SIZE
        assert {"hits", "misses", "size"} <= set(info[name])
    cfg = _cfg()
    xs, ys = _stream_data(cfg, 4, 2, seed=6)
    teacher = stream.LatencyTeacher(stream.array_labels(ys), latency=0)
    before = stream.cache_stats()["plan_runner"]
    stream.run(engine.init_fleet(cfg, 2), (xs[t] for t in range(4)), cfg,
               teacher, mode="train_phase")
    after = stream.cache_stats()["plan_runner"]
    # 4 ticks -> one miss (first compile) plus hits, all visible in counters.
    assert after["misses"] >= before["misses"]
    assert after["hits"] > before["hits"]


def test_scalar_api_confined_to_engine():
    """ISSUE 2 acceptance: no module outside core/odl_head.py (the alias)
    and repro/engine may import the scalar ODL API."""
    root = pathlib.Path(__file__).resolve().parent.parent
    allowed = {
        root / "src" / "repro" / "core" / "odl_head.py",
        # The core package re-exports its own alias submodule so the
        # original ``repro.core.odl_head`` import path keeps resolving.
        root / "src" / "repro" / "core" / "__init__.py",
    }
    offenders = []
    for base in ("src", "benchmarks", "examples"):
        for p in sorted((root / base).rglob("*.py")):
            if p in allowed or (root / "src" / "repro" / "engine") in p.parents:
                continue
            text = p.read_text()
            if "odl_head" in text or "engine.scalar" in text or "engine import scalar" in text:
                offenders.append(str(p.relative_to(root)))
    assert not offenders, f"scalar ODL API imported outside the alias: {offenders}"
