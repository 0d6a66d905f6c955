"""Fleet engine tests: batched Algorithm 1 vs the scalar shim, chunking,
kernel routing, and the serving gate/apply split."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import drift as drift_mod
from repro.core import odl_head, oselm, pruning
from repro.kernels import ops


def _cfg(n_in=24, n_hidden=16, n_out=4, min_trained=16, use_kernel=False):
    return odl_head.ODLCoreConfig(
        elm=oselm.OSELMConfig(
            n_in=n_in, n_hidden=n_hidden, n_out=n_out, variant="hash",
            ridge=1e-2, use_kernel=use_kernel,
        ),
        prune=pruning.PruneConfig(min_trained=min_trained),
        drift=drift_mod.DriftConfig(warmup=16, k_sigma=3.0, enter_hits=2, exit_calm=16),
    )


def _stream_data(cfg, t, s, seed=0, shift_at=None):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    xs = np.array(jnp.tanh(jax.random.normal(kx, (t, s, cfg.elm.n_in))))
    if shift_at is not None:
        # Per-stream severities so drift fires at different times per stream.
        sev = np.linspace(2.0, 4.0, s)[None, :, None]
        xs[shift_at:] = np.clip(xs[shift_at:] * sev + 0.5 * sev, -4, 4)
    ys = jax.random.randint(ky, (t, s), 0, cfg.elm.n_out)
    return jnp.asarray(xs), ys


@pytest.mark.parametrize("mode", ["algo1", "train_phase"])
def test_run_fleet_matches_independent_scalar_runs(mode):
    """(T, S) fleet == S independent scalar runs: control signals (theta
    trajectory, query decisions, drift mode, counts) must match bit-for-bit.

    beta/P are compared to 1e-3: the batched (S, n_in) matmuls round
    differently from the S = 1 shim's at f32 epsilon, and the RLS recursion
    amplifies that over T updates — the *decisions* stay identical, which is
    what the controller semantics require.
    """
    cfg = _cfg()
    t_len, s_len = 120, 3
    xs, ys = _stream_data(cfg, t_len, s_len, seed=1, shift_at=60)

    fstate, fouts = engine.run_fleet(
        engine.init_fleet(cfg, s_len), xs, ys, cfg, mode=mode, chunk=40
    )

    scalar_run = odl_head.run_stream if mode == "algo1" else odl_head.run_training_phase
    for s in range(s_len):
        st, outs = scalar_run(odl_head.init_state(cfg), xs[:, s], ys[:, s], cfg)
        # Control trajectory: bit-for-bit.
        np.testing.assert_array_equal(np.asarray(outs.theta), np.asarray(fouts.theta[:, s]))
        np.testing.assert_array_equal(
            np.asarray(outs.queried), np.asarray(fouts.queried[:, s])
        )
        np.testing.assert_array_equal(
            np.asarray(outs.mode_training), np.asarray(fouts.mode_training[:, s])
        )
        # Counters: exact.
        assert int(st.prune.queries) == int(fstate.prune.queries[s])
        assert int(st.prune.skips) == int(fstate.prune.skips[s])
        assert int(st.elm.count) == int(fstate.elm.count[s])
        assert float(st.meter.total) == float(fstate.meter.total[s])
        # Weights: float tolerance (see docstring).
        np.testing.assert_allclose(
            np.asarray(st.elm.beta), np.asarray(fstate.elm.beta[s]), atol=1e-3
        )


def test_teacher_outage_is_identity_per_stream():
    """Streams with an unavailable teacher must not train or charge comms."""
    cfg = _cfg()
    t_len, s_len = 12, 4
    xs, ys = _stream_data(cfg, t_len, s_len, seed=2)
    avail = jnp.zeros((t_len, s_len), jnp.bool_).at[:, ::2].set(True)

    st0 = engine.init_fleet(cfg, s_len)
    st, outs = engine.run_fleet(
        st0, xs, ys, cfg, mode="train_phase", teacher_available=avail
    )
    dead = np.arange(s_len)[1::2]
    np.testing.assert_allclose(
        np.asarray(st.elm.beta[dead]), np.asarray(st0.elm.beta[dead]), atol=1e-6
    )
    assert not bool(outs.queried[:, dead].any())
    assert float(jnp.sum(st.meter.total[dead])) == 0.0
    assert bool(outs.queried[:, ::2].any())  # live streams did query


def test_chunk_boundaries_do_not_recompile_or_change_results():
    """Chunked run == single-dispatch run, and every same-shape chunk reuses
    one compiled executable (the donation/no-recompile smoke test)."""
    cfg = _cfg(n_hidden=8, n_in=12)
    t_len, s_len = 48, 2
    xs, ys = _stream_data(cfg, t_len, s_len, seed=3)

    engine.fleet._chunk_runner.cache_clear()
    st_a, out_a = engine.run_fleet(
        engine.init_fleet(cfg, s_len), xs, ys, cfg, mode="train_phase", chunk=12
    )
    runner = engine.fleet._chunk_runner(cfg, "train_phase", False)
    assert runner._cache_size() == 1  # 4 chunk dispatches, one executable

    # A second run with the same chunk shape must not add compilations.
    engine.run_fleet(
        engine.init_fleet(cfg, s_len), xs, ys, cfg, mode="train_phase", chunk=12
    )
    assert runner._cache_size() == 1

    st_b, out_b = engine.run_fleet(
        engine.init_fleet(cfg, s_len), xs, ys, cfg, mode="train_phase"
    )
    np.testing.assert_allclose(
        np.asarray(st_a.elm.beta), np.asarray(st_b.elm.beta), atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(out_a.queried), np.asarray(out_b.queried))


def test_fleet_kernel_matches_jnp_path():
    """use_kernel=True (batched Pallas RLS) == einsum path, per stream."""
    cfg = _cfg(n_hidden=16)
    s_len = 5
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (s_len, cfg.elm.n_in))
    y = jax.nn.one_hot(jnp.arange(s_len) % cfg.elm.n_out, cfg.elm.n_out)
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0])

    st = engine.init_fleet(cfg, s_len).elm
    a = oselm.fleet_rank1_update(st, x, y, cfg.elm, mask=mask, use_kernel=False)
    b = oselm.fleet_rank1_update(st, x, y, cfg.elm, mask=mask, use_kernel=True)
    np.testing.assert_allclose(np.asarray(a.beta), np.asarray(b.beta), atol=1e-4)
    np.testing.assert_allclose(np.asarray(a.P), np.asarray(b.P), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(a.count), np.asarray(b.count))


def test_fleet_kernel_entry_matches_scalar_kernel():
    """oselm_rls_update_fleet == the scalar fused kernel run per stream,
    including ragged N (padding) and rank-k > 1."""
    s_len, n, k, m = 3, 20, 2, 4
    key = jax.random.PRNGKey(5)
    p0 = jnp.eye(n) * 0.5 + 0.01 * jax.random.normal(key, (s_len, n, n))
    p0 = 0.5 * (p0 + p0.transpose(0, 2, 1))
    beta = 0.1 * jax.random.normal(key, (s_len, n, m))
    h = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(6), (s_len, k, n)))
    y = jax.nn.one_hot(jnp.arange(s_len * k).reshape(s_len, k) % m, m)

    pf, bf = ops.oselm_rls_update_fleet(p0, beta, h, y)
    for s in range(s_len):
        ps, bs = ops.oselm_rls_update(p0[s], beta[s], h[s], y[s])
        np.testing.assert_allclose(np.asarray(pf[s]), np.asarray(ps), atol=1e-5)
        np.testing.assert_allclose(np.asarray(bf[s]), np.asarray(bs), atol=1e-5)


def test_gate_and_apply_labels_roundtrip():
    """Serving split: gate meters queries; apply_labels trains only the
    masked streams and leaves the rest untouched."""
    cfg = _cfg(min_trained=1_000_000)  # cold heads: everyone must query
    s_len = 4
    x = jnp.tanh(jax.random.normal(jax.random.PRNGKey(7), (s_len, cfg.elm.n_in)))
    labels = jnp.asarray([0, 1, 2, 3], jnp.int32)

    st0 = engine.init_fleet(cfg, s_len)
    st1, out = engine.gate(st0, x, cfg)
    assert bool(out.queried.all())
    np.testing.assert_allclose(
        np.asarray(st1.meter.up_bytes), np.full(s_len, cfg.elm.n_in * 4.0)
    )

    mask = jnp.asarray([True, True, False, False])
    st2 = engine.apply_labels(st1, out, labels, mask, cfg)
    np.testing.assert_array_equal(np.asarray(st2.elm.count), [1, 1, 0, 0])
    np.testing.assert_allclose(
        np.asarray(st2.elm.beta[2:]), np.asarray(st1.elm.beta[2:]), atol=1e-6
    )
    assert float(jnp.max(jnp.abs(st2.elm.beta[:2] - st1.elm.beta[:2]))) > 0


def test_broadcast_and_slice_roundtrip():
    cfg = _cfg()
    scalar = odl_head.init_state(cfg)
    fleet = engine.broadcast_streams(scalar, 3)
    back = engine.stream_slice(fleet, 1)
    for a, b in zip(jax.tree.leaves(scalar), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "s_len,n",
    [(1024, 0), (1024, 1), (4096, 328), (2048, 1023), (2048, 1025)],
    ids=["none", "one", "8pct", "under-half", "over-half"],
)
def test_learn_over_compacted_rows_is_full_width_learn_row_for_row(s_len, n):
    """A reply's learn over its ``learn_rows_index`` == the full-width
    learn, every leaf bit for bit, and rows outside the mask (padding
    included) unchanged; the index is sorted, unique, of the smallest
    capacity that holds the answered rows, and holds every one of them —
    or None (full width) past half the rows."""
    from repro.engine import stream

    cfg = _cfg()
    xs, ys = _stream_data(cfg, 7, s_len, seed=9)
    st, _ = engine.run_fleet(
        engine.init_fleet(cfg, s_len), xs[:6], ys[:6], cfg, mode="train_phase"
    )
    st, p = engine.plan(st, xs[6], cfg, mode="serve")
    rng = np.random.default_rng(n)
    mask = np.zeros(s_len, bool)
    mask[rng.choice(s_len, n, replace=False)] = True
    labels = rng.integers(0, cfg.elm.n_out, s_len).astype(np.int32)

    rows = stream.learn_rows_index(mask)
    if 2 * n > s_len:
        assert rows is None
    else:
        k = min(c for c in stream.learn_capacities(s_len) if c >= n)
        assert rows.dtype == np.int32 and len(rows) == k
        assert np.all(np.diff(rows) > 0)  # sorted and unique
        assert np.isin(np.flatnonzero(mask), rows).all()
        pad = rows[~mask[rows]]
        np.testing.assert_array_equal(pad, np.flatnonzero(~mask)[: k - n])

    runner = stream._learn_runner(cfg, False)
    args = (st.elm, st.prune, st.drift, st.meter, p.h, labels, p.pred,
            p.confidence, jnp.asarray(mask), p.controller_on, p.theta)
    full = runner(*args)
    compact = runner(*args) if rows is None else runner(*args, rows=rows)
    for (path, a), (_, b), (_, before) in zip(
        jax.tree_util.tree_flatten_with_path(full)[0],
        jax.tree_util.tree_flatten_with_path(compact)[0],
        jax.tree_util.tree_flatten_with_path((st.elm, st.prune))[0],
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))
        np.testing.assert_array_equal(
            np.asarray(b)[~mask], np.asarray(before)[~mask], err_msg=str(path)
        )
