"""The plain references against the program at tiny sizes on the CPU, and
the control: the reference computed one precision step below what the
configuration states, put in the program's place, must fail the check."""

import json

import jax
import jax.numpy as jnp
import numpy as np

import tiny_cells

from bench import harness, heads_ref, sources


def test_hash_weights_are_the_programs():
    from repro.core import xorshift

    want = np.asarray(xorshift.alpha_hash(0x2D2A, 561, 128))
    got = heads_ref.alpha_hash(0x2D2A, 561, 128, 3)
    assert np.array_equal(got.astype(np.float32), want)


def test_head_reference_steps_like_the_program():
    """One plan and one learn of the program's fleet engine against the
    reference, on the same batch-initialized heads."""
    from repro.engine import fleet

    doc = json.loads((tiny_cells.BENCH / "configs" / "har-odl.json").read_text())
    doc["head"]["n_hidden"] = 32
    ecfg = sources.engine_config(doc, 561)
    rng = np.random.default_rng(0)
    x0 = np.tanh(rng.normal(size=(256, 561))).astype(np.float32)
    y0 = rng.integers(0, 6, 256)
    x = np.tanh(rng.normal(size=(8, 561))).astype(np.float32)
    labels = rng.integers(0, 6, 8).astype(np.int32)
    state = sources.batch_head_state(ecfg, x0, y0, 8, "highest")
    with jax.default_matmul_precision("highest"):
        state, p = fleet.plan(state, jnp.asarray(x), ecfg, mode="serve")
        mask = jnp.ones(8, bool)
        state = fleet.learn(state, p.h, jnp.asarray(labels), p.pred, p.confidence, mask,
                            p.controller_on, ecfg, theta=p.theta)
    rows = heads_ref.Rows.batch_init(heads_ref.Head.from_config(doc, 561), x0, y0, 8)
    ctx = rows.plan(x)
    rows.learn(ctx, labels, np.ones(8, bool))
    assert np.array_equal(ctx["queried"], np.asarray(p.queried))
    assert heads_ref.rel_err(np.asarray(state.elm.beta), rows.beta).max() < 1e-4
    assert heads_ref.rel_err(np.asarray(state.elm.P), rows.P).max() < 1e-4
    assert np.array_equal(np.asarray(state.prune.level), rows.level)


def test_backbone_reference_matches_the_programs_prefill(tmp_path):
    """The float32 reference against the program's bfloat16 forward pass,
    and the float8 control farther from the reference by a wide margin."""
    from repro.models import model as model_lib

    bench, bdir = tiny_cells.make(tmp_path)
    spec = harness.resolve(bench, "danube.decode32", bdir)
    cell = spec["source"](spec["config"], spec["mix"], 5, spec["ref"])
    cell.setup_program(lambda m: None)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 512)
    hidden, _ = model_lib.prefill(cell.params, tokens, cell.mcfg)
    b = spec["config"]["backbone"]
    want = np.asarray(spec["ref"].features(cell.params, tokens, b))
    ctrl = np.asarray(spec["ref"].features(cell.params, tokens, b, control=True))

    def gap(got):
        got = np.asarray(got, np.float32)
        return np.median(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1))

    assert gap(hidden) < 2e-2
    assert gap(ctrl) > 3 * gap(hidden)


def test_the_control_fails_the_check(tmp_path):
    bench, bdir = tiny_cells.make(tmp_path)
    for workload in ("danube.decode32", "har.drift64k"):
        out = harness.run_cell(bench, workload, 31, 0.5, False, require_tpu=False,
                               control=True, bench_dir=bdir)
        assert out["correct"] is False, (workload, out["check"])
