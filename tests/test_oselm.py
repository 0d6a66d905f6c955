"""OS-ELM unit + property tests (paper §2.1, Fig. 2(b)/(d))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # degrade gracefully: only the @given tests skip
    from _hypothesis_stub import given, settings, st

from repro.core import oselm


def _data(key, n, n_in, n_out):
    kx, ky = jax.random.split(jax.random.PRNGKey(key))
    x = jax.random.normal(kx, (n, n_in))
    y = jax.nn.one_hot(jax.random.randint(ky, (n,), 0, n_out), n_out)
    return x, y


@pytest.mark.parametrize("variant", ["base", "hash"])
def test_sequential_equals_closed_form(variant):
    """RLS over the stream == ridge regression over the batch (Woodbury)."""
    cfg = oselm.OSELMConfig(n_in=24, n_hidden=16, n_out=4, variant=variant, ridge=1e-2)
    x, y = _data(0, 60, 24, 4)
    st_ = oselm.init_state(cfg)
    for i in range(0, 60, 6):
        st_ = oselm.sequential_update(st_, x[i : i + 6], y[i : i + 6], cfg)
    beta_cf = oselm.fit_closed_form(cfg, x, y)
    np.testing.assert_allclose(st_.beta, beta_cf, rtol=0, atol=5e-3)
    assert int(st_.count) == 60


def test_rank1_equals_rankk():
    """One rank-k update == k rank-1 updates (same P, beta)."""
    cfg = oselm.OSELMConfig(n_in=10, n_hidden=12, n_out=3, ridge=1e-1)
    x, y = _data(1, 8, 10, 3)
    st_k = oselm.sequential_update(oselm.init_state(cfg), x, y, cfg)
    st_1 = oselm.init_state(cfg)
    for i in range(8):
        st_1 = oselm.sequential_update(st_1, x[i], y[i], cfg)
    np.testing.assert_allclose(st_k.beta, st_1.beta, atol=2e-4)
    np.testing.assert_allclose(st_k.P, st_1.P, atol=2e-4)


def test_masked_row_is_identity():
    """A masked (pruned) row must leave (P, beta, count) exactly unchanged."""
    cfg = oselm.OSELMConfig(n_in=10, n_hidden=8, n_out=3)
    x, y = _data(2, 4, 10, 3)
    st0 = oselm.sequential_update(oselm.init_state(cfg), x[:2], y[:2], cfg)
    mask = jnp.array([0.0, 0.0])
    st1 = oselm.sequential_update(st0, x[2:], y[2:], cfg, mask=mask)
    np.testing.assert_allclose(st1.P, st0.P, atol=1e-6)
    np.testing.assert_allclose(st1.beta, st0.beta, atol=1e-6)
    assert int(st1.count) == int(st0.count)


def test_fleet_masked_stream_keeps_batch_initialized_P_bit_for_bit():
    """A batch-initialized P is symmetric only to rounding; the fleet
    update's symmetrization must not touch it on a masked stream (else
    run_fleet, which updates every stream every tick, drifts from
    stream.run, which updates only the answered ones)."""
    cfg = oselm.OSELMConfig(n_in=24, n_hidden=32, n_out=4, ridge=1e-2)
    x0, y0 = _data(5, 200, 24, 4)
    head = oselm.init_state_batch(cfg, x0, y0)
    assert not np.array_equal(np.asarray(head.P), np.asarray(head.P).T)
    fleet = jax.tree.map(lambda a: jnp.stack([a, a]), head)
    x, y = _data(6, 2, 24, 4)
    new = oselm.fleet_rank1_update_h(fleet, oselm.hidden(x, cfg), y, cfg,
                                     mask=jnp.array([1.0, 0.0]))
    for name in ("P", "beta", "count"):
        np.testing.assert_array_equal(np.asarray(getattr(new, name))[1],
                                      np.asarray(getattr(fleet, name))[1])
    assert not np.array_equal(np.asarray(new.P[0]), np.asarray(fleet.P[0]))


def test_partial_mask_equals_subset():
    """mask=[1,0,1] must equal updating with rows {0, 2} only."""
    cfg = oselm.OSELMConfig(n_in=10, n_hidden=8, n_out=3)
    x, y = _data(3, 3, 10, 3)
    st0 = oselm.init_state(cfg)
    st_m = oselm.sequential_update(st0, x, y, cfg, mask=jnp.array([1.0, 0.0, 1.0]))
    st_s = oselm.sequential_update(st0, x[jnp.array([0, 2])], y[jnp.array([0, 2])], cfg)
    np.testing.assert_allclose(st_m.beta, st_s.beta, atol=1e-4)
    np.testing.assert_allclose(st_m.P, st_s.P, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(
    n_hidden=st.integers(4, 32),
    k=st.integers(1, 8),
    seed=st.integers(0, 2**16),
)
def test_P_stays_symmetric_psd(n_hidden, k, seed):
    """Property: P is symmetric positive definite after any update sequence
    (it is the inverse of a ridge-regularized Gram matrix)."""
    cfg = oselm.OSELMConfig(n_in=12, n_hidden=n_hidden, n_out=3, ridge=1e-1)
    x, y = _data(seed, k, 12, 3)
    st_ = oselm.sequential_update(oselm.init_state(cfg), x, y, cfg)
    p = np.asarray(st_.P)
    np.testing.assert_allclose(p, p.T, atol=1e-4)
    eig = np.linalg.eigvalsh(p)
    assert eig.min() > 0, f"P lost positive definiteness: min eig {eig.min()}"


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_update_monotone_gram(seed):
    """Property: P^{-1} grows by H^T H, so P shrinks (in PSD order):
    v^T P' v <= v^T P v for any direction v."""
    cfg = oselm.OSELMConfig(n_in=12, n_hidden=8, n_out=3, ridge=1e-1)
    x, y = _data(seed, 4, 12, 3)
    st0 = oselm.init_state(cfg)
    st1 = oselm.sequential_update(st0, x, y, cfg)
    rng = np.random.default_rng(seed)
    v = rng.normal(size=(8,)).astype(np.float32)
    q0 = float(v @ np.asarray(st0.P) @ v)
    q1 = float(v @ np.asarray(st1.P) @ v)
    assert q1 <= q0 + 1e-4


def test_init_state_batch_matches_closed_form():
    cfg = oselm.OSELMConfig(n_in=16, n_hidden=12, n_out=4, ridge=1e-2)
    x, y = _data(7, 40, 16, 4)
    st_ = oselm.init_state_batch(cfg, x, y)
    beta_cf = oselm.fit_closed_form(cfg, x, y)
    np.testing.assert_allclose(st_.beta, beta_cf, atol=2e-3)


def test_init_batch_then_sequential_equals_full_closed_form():
    """Paper's exact protocol: batch init on half, sequential on the rest."""
    cfg = oselm.OSELMConfig(n_in=16, n_hidden=12, n_out=4, ridge=1e-2)
    x, y = _data(8, 50, 16, 4)
    st_ = oselm.init_state_batch(cfg, x[:25], y[:25])
    for i in range(25, 50, 5):
        st_ = oselm.sequential_update(st_, x[i : i + 5], y[i : i + 5], cfg)
    beta_cf = oselm.fit_closed_form(cfg, x, y)
    np.testing.assert_allclose(st_.beta, beta_cf, atol=5e-3)


def test_learns_separable_problem():
    """End behaviour: OS-ELM reaches high accuracy on a separable problem."""
    key = jax.random.PRNGKey(0)
    centers = jax.random.normal(key, (4, 20)) * 2.0
    labels = jnp.tile(jnp.arange(4), 50)
    x = centers[labels] + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (200, 20))
    y = jax.nn.one_hot(labels, 4)
    cfg = oselm.OSELMConfig(n_in=20, n_hidden=64, n_out=4)
    st_ = oselm.init_state(cfg)
    for i in range(0, 200, 10):
        st_ = oselm.sequential_update(st_, x[i : i + 10], y[i : i + 10], cfg)
    preds, _ = oselm.predict(st_, x, cfg)
    assert float(jnp.mean((preds == labels).astype(jnp.float32))) > 0.95


def test_fleet_vmap_consistency():
    """Fleet update == per-stream updates."""
    cfg = oselm.OSELMConfig(n_in=10, n_hidden=8, n_out=3)
    fleet = oselm.init_fleet(cfg, 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 10))
    y = jax.nn.one_hot(jnp.array([0, 1, 2, 0]), 3)
    fleet2 = oselm.fleet_update(fleet, x, y, cfg)
    for s in range(4):
        st_s = oselm.sequential_update(
            jax.tree.map(lambda a: a[s], fleet), x[s], y[s], cfg
        )
        np.testing.assert_allclose(
            jax.tree.map(lambda a: a[s], fleet2).beta, st_s.beta, atol=1e-3
        )


def test_hash_variant_needs_no_alpha_storage():
    """ODLHash predicts identically from config alone (alpha is implicit)."""
    cfg = oselm.OSELMConfig(n_in=10, n_hidden=8, n_out=3, variant="hash")
    assert oselm.make_alpha(cfg) is None
    x, y = _data(5, 6, 10, 3)
    st_ = oselm.sequential_update(oselm.init_state(cfg), x, y, cfg)
    p1, _ = oselm.predict(st_, x, cfg)
    p2, _ = oselm.predict(st_, x, cfg)
    np.testing.assert_array_equal(p1, p2)
