"""Tiny copies of the cells over DeepSeek-V2-Lite's expert share
(``dsv2lite.decode512``, kind ``decode_moe``) and the four-chip sharded
fleet (``har.mesh4.drift256k``, kind ``mesh``) on the CPU: each resolves
its files by name, runs with ``correct`` true, and the control fails; the
mesh cell on four virtual CPU devices in a subprocess."""

import json
import os
import subprocess
import sys

import pytest

import tiny_cells

from bench import costs_moe, harness, trace as trace_mod

ROOT = tiny_cells.ROOT
FIXTURE = ROOT / "tests" / "bench" / "data" / "trace_danube.json"


def make(tmp):
    """``tiny_cells.make`` plus the two cells: a 3-layer backbone of width
    64 (one dense layer, 4 of 16 experts held, top 6) over 8 streams, and
    256 HAR users in 4 shards."""
    bench, bdir = tiny_cells.make(tmp)
    doc = json.loads((tiny_cells.BENCH / "configs" / "deepseek-v2-lite.json").read_text())
    doc["backbone"].update(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=24,
                           d_ff=96, vocab_size=512, n_experts=16, d_ff_expert=48,
                           n_shared_experts=1, experts_held=4, expert_first=4, kv_lora_rank=24,
                           qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
    doc["head"].update(n_hidden=16)
    doc["head"]["prune"]["min_trained"] = 16
    doc["head"]["drift"].update(warmup=8)
    # Limits at these sizes, set like the real ones: above the program's
    # largest reading on the CPU (features 0.044, logits 0.036, routes
    # 0.028, heads 4e-6, no flips) and below the control's smallest
    # (features 0.24, logits 0.23, routes 0.27, heads 0.11).
    doc["limits"].update(feat_err_max=0.1, logit_err_max=0.1, route_flip_share=0.1,
                         head_flip_share=0.01, theta_gap_share=0.1)
    (bdir / "configs" / "deepseek-v2-lite.json").write_text(json.dumps(doc))
    (bdir / "configs" / "deepseek-v2-lite.ref.py").write_text(
        (tiny_cells.BENCH / "configs" / "deepseek-v2-lite.ref.py").read_text())
    mix = json.loads((tiny_cells.BENCH / "traffic" / "decode512.json").read_text())
    mix.update(streams=8, prompt_len=8, max_len=24, warm_ticks=12, share_ticks=16, quantum=4)
    mix["check"] = {"feature_rows": 4, "feature_block": 2, "logit_rows": 2}
    (bdir / "traffic" / "decode512.json").write_text(json.dumps(mix))
    mix = json.loads((tiny_cells.BENCH / "traffic" / "mesh4drift256k.json").read_text())
    mix.update(streams=256, warm_ticks=12, share_ticks=16, bout_mean=6, dwell_mean=16)
    mix["check"] = {"head_rows": 16}
    (bdir / "traffic" / "mesh4drift256k.json").write_text(json.dumps(mix))
    return bench, bdir


def test_the_new_cells_resolve_their_files_by_name(tmp_path):
    bench, bdir = make(tmp_path)
    spec = harness.resolve(bench, "dsv2lite.decode512", bdir)
    assert spec["source"].__module__ == "bench_source_decode_moe"
    assert callable(spec["ref"].features) and callable(spec["ref"].logits)
    assert {"moe_decode_ms", "moe_decode_roofline", "step_mfu"} <= set(spec["readers"])
    assert not {"decode_ms", "decode_roofline"} & set(spec["readers"])
    spec = harness.resolve(bench, "har.mesh4.drift256k", bdir)
    assert spec["source"].__module__ == "bench_source_mesh" and spec["ref"] is None
    assert spec["cell"]["chips"] == 4 and "moe_decode_ms" not in spec["readers"]


@pytest.mark.parametrize("control", [False, True], ids=["program", "control"])
def test_dsv2lite_tiny_cell_is_correct_and_its_control_is_not(tmp_path, control):
    bench, bdir = make(tmp_path)
    out = harness.run_cell(bench, "dsv2lite.decode512", 2**33 + 5, 0.5, False,
                           require_tpu=False, control=control, bench_dir=bdir)
    assert out["attempted"] > 0
    assert {"feat_err_max", "logit_err_max", "route_flip_share"} <= set(out["check"])
    assert out["correct"] is (not control), out["check"]


def test_dsv2lite_traced_run_reads_the_moe_decode_metrics(tmp_path, monkeypatch):
    """A traced run, its trace replaced by one recorded on the chip: the
    new per-layer metrics read the decode executable, the danube ones do
    not appear."""
    bench, bdir = make(tmp_path)
    fixture = json.loads(FIXTURE.read_text())
    monkeypatch.setattr(trace_mod, "compact", lambda path: fixture)
    monkeypatch.setattr(trace_mod, "find_xplane", lambda d: d)
    out = harness.run_cell(bench, "dsv2lite.decode512", 3, 0.5, True, require_tpu=False,
                           bench_dir=bdir)
    assert {"moe_decode_ms", "moe_decode_roofline", "step_mfu", "head_ms"} <= set(out["metrics"])
    assert not {"decode_ms", "decode_roofline"} & set(out["metrics"])


MESH_RUN = """
import json, pathlib, sys
sys.path[:0] = [{tests!r}]
import test_bench_new_cells as t
from bench import harness
bench, bdir = t.make(pathlib.Path({tmp!r}))
out = {{c: harness.run_cell(bench, "har.mesh4.drift256k", 2**33 + 9, 0.5, False,
                            require_tpu=False, control=c, bench_dir=bdir)
       for c in (False, True)}}
print(json.dumps({{str(c): {{"correct": o["correct"], "attempted": o["attempted"],
                           "device": o["device"], "check": o["check"]}}
                  for c, o in out.items()}}))
"""


def test_mesh_tiny_cell_on_four_devices(tmp_path):
    """The sharded fleet over four virtual CPU devices: correct, every
    shard's heads on its own device, and the control fails."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    code = MESH_RUN.format(tests=str(ROOT / "tests" / "bench"), tmp=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["False"]["device"]["count"] == 4
    assert out["False"]["attempted"] > 0
    assert out["False"]["correct"] is True, out["False"]["check"]
    assert out["True"]["correct"] is False, out["True"]["check"]


def test_moe_decode_costs_match_the_reckoning_from_the_shapes():
    """At 13 layers (the dense layer and 12 MoE layers) and the mean
    position 320, a decode tick of 512 streams needs 5.98 GB of weights
    besides the embedding table and 2.45 GB of latent cache: 10.3 ms at
    819 GB/s; the held experts' operations count 384 token-choices a
    layer."""
    b = json.loads((tiny_cells.BENCH / "configs" / "deepseek-v2-lite.json").read_text())
    b = dict(b["backbone"], n_layers=13)
    p = costs_moe.layer_params(b)
    assert round(p["attn"] / 1e6, 1) == 13.8 and round(p["held"] / 1e6, 1) == 69.2
    assert round(p["shared"] / 1e6, 1) == 17.3 and round(p["lm_head"] / 1e6, 1) == 209.7
    assert costs_moe.held_choices(b, 512) == 384
    flops, nbytes = costs_moe.decode_step(b, 512, 320)
    cache = 13 * 512 * 320 * 576 * 2
    assert round(cache / 1e9, 2) == 2.45
    assert round(nbytes / 819e9 * 1e3, 1) == 10.3
    assert 5.95e9 < nbytes - cache - 512 * 2048 * 4 - 13 * 512 * 576 * 2 < 6.0e9
    assert flops > 2 * 512 * (2048 * 102400 + 13 * 2048 * 16 * 192)
