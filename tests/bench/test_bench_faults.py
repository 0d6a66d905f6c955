"""Whole runs of the tiny cells on the CPU with the timed path broken
underneath: each fault a cell can have must turn ``correct`` false.

The faults are planted in the program's own functions, which the compiled
runners pick up when they are next traced (their caches are cleared
first): a learn step that returns its state unchanged, a learn that leaves
half of the batch out, a query decision altered where the plan makes it,
and decode features altered where the backbone makes them.  The cells run
on one chip, so there is no exchange between chips to leave out.
"""

import pytest

import tiny_cells

from bench import harness


@pytest.fixture
def fresh_runners():
    from repro.engine import fleet, stream

    caches = (stream._plan_runner, stream._learn_runner, stream._learn_plan_runner,
              stream._plan_avail_runner, stream._learn_plan_avail_runner,
              fleet._patch_learn_runner, fleet._chunk_runner)
    for c in caches:
        c.cache_clear()
    yield
    for c in caches:
        c.cache_clear()


def _run(tmp_path, workload):
    bench, bdir = tiny_cells.make(tmp_path)
    return harness.run_cell(bench, workload, 21, 0.5, False, require_tpu=False,
                            bench_dir=bdir)


def _learn_unchanged(state, *args, **kw):
    return state


def _learn_half(fleet):
    real = fleet.learn

    def learn(state, h, labels, pred, conf, mask, controller_on, cfg, theta=None):
        keep = mask & (mask.cumsum() <= (mask.sum() + 1) // 2)
        return real(state, h, labels, pred, conf, keep, controller_on, cfg, theta=theta)

    return learn


def _plan_altered(fleet):
    real = fleet.plan

    def plan(state, x, cfg, **kw):
        new_state, p = real(state, x, cfg, **kw)
        return new_state, p._replace(queried=p.queried.at[::4].set(~p.queried[::4]))

    return plan


@pytest.mark.parametrize("workload", ["danube.decode32", "har.drift64k"])
@pytest.mark.parametrize("fault", ["learn_unchanged", "learn_half", "query_altered"])
def test_a_broken_head_path_is_not_correct(tmp_path, monkeypatch, fresh_runners,
                                           workload, fault):
    from repro.engine import fleet

    patch = {"learn_unchanged": ("learn", _learn_unchanged),
             "learn_half": ("learn", _learn_half(fleet)),
             "query_altered": ("plan", _plan_altered(fleet))}[fault]
    monkeypatch.setattr(fleet, *patch)
    out = _run(tmp_path, workload)
    assert out["correct"] is False, out["check"]


def test_altered_decode_features_are_not_correct(tmp_path, monkeypatch, fresh_runners):
    from repro.models import model as model_lib

    real = model_lib.decode_step

    def decode_step(params, state, token, cfg):
        logits, feats, state = real(params, state, token, cfg)
        return logits, feats.at[:, 0].multiply(1.5), state

    monkeypatch.setattr(model_lib, "decode_step", decode_step)
    out = _run(tmp_path, "danube.decode32")
    assert out["correct"] is False
    assert out["check"]["feat_err_max"]["value"] > out["check"]["feat_err_max"]["limit"]


def test_the_unbroken_path_is_correct(tmp_path, fresh_runners):
    for workload in ("danube.decode32", "har.drift64k"):
        out = _run(tmp_path / workload, workload)
        assert out["correct"] is True, out["check"]
