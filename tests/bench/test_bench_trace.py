"""The reduction from a device trace to the per-layer metrics, on a small
trace recorded on a TPU v5e (0.4 s of the ``danube.decode32`` cell, kept
in ``data/``), and the operation and byte counts against hand counts."""

import json
import pathlib

import numpy as np
import pytest

import tiny_cells

from bench import costs, harness, trace as trace_mod

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / "trace_danube.json"
HEADS = ("run_plan", "run_learn", "run_learn_plan", "run_patch")


@pytest.fixture(scope="module")
def doc():
    return json.loads(FIXTURE.read_text())


def _metric(name):
    return harness.load_module(tiny_cells.BENCH / "metrics" / f"{name}.py", f"t_{name}")


def test_busy_time_and_idle_share_match_a_timeline(doc):
    r = trace_mod.Reading(doc)
    lo, hi = doc["window"]
    timeline = np.zeros(int((hi - lo) // 1000) + 1, bool)  # 1 us bins
    for s, e in next(iter(doc["devices"].values()))["ops"]:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            timeline[int((a - lo) // 1000): int(np.ceil((b - lo) / 1000))] = True
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    assert r.busy_s == pytest.approx(timeline.sum() / 1e6, rel=2e-3)
    m = harness.Traced(r, {"decode": [], "head": []}, 1, "bench_decode", {}, 1)
    idle = _metric("device_idle_pct").read(m)
    assert idle == pytest.approx(100 * (1 - timeline.sum() / 1e6 / r.window_s), abs=0.2)
    assert 0 < idle < 100


def test_decode_and_head_time_per_tick(doc):
    r = trace_mod.Reading(doc)
    lo, hi = doc["window"]
    mods = next(iter(doc["devices"].values()))["modules"]
    dec = [e - s for n, s, e in mods if n == "bench_decode" and lo <= s < hi]
    head = [e - s for n, s, e in mods if n in HEADS and lo <= s < hi]
    assert dec and head
    m = harness.Traced(r, {"decode": [], "head": []}, len(dec), "bench_decode", {}, 1)
    assert _metric("decode_ms").read(m) == pytest.approx(sum(dec) / len(dec) / 1e6)
    assert _metric("head_ms").read(m) == pytest.approx(sum(head) / len(dec) / 1e6)
    assert r.top_modules()[0][0] == "bench_decode"
    gaps = r.idle_gaps()
    assert len(gaps) == 10 and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_a_metric_with_nothing_to_read_returns_nothing(doc):
    r = trace_mod.Reading(doc)
    m = harness.Traced(r, {"decode": [], "head": []}, 0, None,
                       {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}, 1)
    assert _metric("decode_roofline").read(m) is None
    assert _metric("head_ms").read(m) is None
    assert _metric("step_mfu").read(m) is None
    assert _metric("tick_gap_p95_ms").read(m) is None


def test_tick_gap_tail_is_read_off_the_device_clock(doc):
    """The 95th percentile of the gaps between consecutive starts of the
    tick source, against the gaps taken by hand from the trace."""
    r = trace_mod.Reading(doc)
    lo, hi = doc["window"]
    mods = next(iter(doc["devices"].values()))["modules"]
    starts = sorted(s for n, s, _ in mods if n == "bench_decode" and lo <= s < hi)
    gaps = np.diff(starts) / 1e6
    assert len(gaps) >= 5
    m = harness.Traced(r, {"decode": [], "head": []}, len(starts), "bench_decode", {}, 1)
    p95 = _metric("tick_gap_p95_ms").read(m)
    assert np.percentile(gaps, 50) <= p95 <= gaps.max()
    assert p95 == pytest.approx(np.percentile(gaps, 95))


def test_backbone_counts_match_the_programs_parameter_shapes():
    import jax

    from repro.configs.base import ModelConfig
    from repro.models import layers
    from repro.models import model as model_lib

    b = json.loads((tiny_cells.BENCH / "configs" / "h2o-danube-1.8b.json").read_text())["backbone"]
    mcfg = ModelConfig(arch_id="x", family="dense", n_layers=b["n_layers"],
                       d_model=b["d_model"], n_heads=b["n_heads"], n_kv_heads=b["n_kv_heads"],
                       d_ff=b["d_ff"], vocab_size=b["vocab_size"],
                       sliding_window=b["sliding_window"])
    shapes = jax.eval_shape(lambda: layers.init_params(model_lib.build_schema(mcfg),
                                                       jax.random.PRNGKey(0)))
    per = {"/".join(k.key for k in path): int(np.prod(s.shape))
           for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    p = costs.backbone_params(b)
    matmul = sum(v for k, v in per.items() if k.startswith("layers/") and "ln" not in k)
    assert p["layer"] * b["n_layers"] == matmul
    assert p["lm_head"] == per["lm_head"]
    total = sum(v for k, v in per.items() if k != "embed")
    weights = b["n_layers"] * (p["layer"] + p["norms"]) + p["lm_head"] + p["final_norm"]
    assert weights == total


def test_counts_against_hand_counts_at_one_shape():
    b = {"n_layers": 24, "d_model": 2560, "n_heads": 32, "n_kv_heads": 8, "d_ff": 6912,
         "vocab_size": 32000, "sliding_window": 4096}
    # Per layer: q 2560x2560, k and v 2560x640 each, o 2560x2560, mlp 3x2560x6912.
    layer = 2560 * 2560 * 2 + 2560 * 640 * 2 + 3 * 2560 * 6912
    assert costs.backbone_params(b)["layer"] == layer == 69_468_160
    flops, nbytes = costs.decode_step(b, 32, 199)  # 200 positions attended
    assert flops == 2 * 32 * (24 * 69_468_160 + 81_920_000) + 24 * 4 * 32 * 32 * 200 * 80
    weights = (24 * (69_468_160 + 5120) + 81_920_000 + 2560) * 4
    kv = 24 * 32 * 199 * 8 * 80 * 2 * 2 + 24 * 32 * 8 * 80 * 2 * 2
    assert nbytes == weights + kv + 32 * 2560 * 4
    assert costs.head_plan(10, 561, 128, 6) == (10 * (2 * 561 * 128 + 2 * 128 * 6),
                                                10 * (561 + 768) * 4)
    assert costs.head_learn(10, 128, 6) == (10 * (4 * 16384 + 4 * 768),
                                            10 * 2 * (16384 + 768) * 4)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs.min_time(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert costs.min_time(1.0, 819e9, peaks) == pytest.approx(1.0)
