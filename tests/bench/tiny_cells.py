"""Tiny copies of the benchmark's cells for tests on the CPU.

``make(tmp)`` writes a benchmark directory under ``tmp`` whose configs and
mixes keep the real files' keys at sizes a test run can hold (a 2-layer
backbone of width 64, heads of width 16, a few streams), with the real
references and metric readers beside them, and returns the parsed
``BENCHMARK.json`` for it together with the directory.
"""

from __future__ import annotations

import copy
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCH = ROOT / "bench"


def _load(path):
    return json.loads(path.read_text())


def make(tmp: pathlib.Path) -> tuple:
    real = _load(ROOT / "BENCHMARK.json")
    out = tmp / "bench"
    (out / "configs").mkdir(parents=True)
    (out / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", out / "metrics")
    shutil.copytree(BENCH / "sources", out / "sources",
                    ignore=shutil.ignore_patterns("__pycache__"))

    danube = _load(BENCH / "configs" / "h2o-danube-1.8b.json")
    danube["backbone"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                              d_ff=128, vocab_size=512, sliding_window=16)
    danube["head"].update(n_hidden=16)
    danube["head"]["prune"]["min_trained"] = 16
    danube["head"]["drift"].update(warmup=8)
    (out / "configs" / "h2o-danube-1.8b.json").write_text(json.dumps(danube))
    shutil.copy(BENCH / "configs" / "h2o-danube-1.8b.ref.py",
                out / "configs" / "h2o-danube-1.8b.ref.py")

    har = _load(BENCH / "configs" / "har-odl.json")
    har["head"].update(n_hidden=16)
    har["head"]["prune"]["min_trained"] = 16
    har["surrogate"]["samples_per_subject_class"] = 8
    (out / "configs" / "har-odl.json").write_text(json.dumps(har))

    decode = _load(BENCH / "traffic" / "decode32.json")
    decode.update(streams=4, prompt_len=8, max_len=24, warm_ticks=12, share_ticks=16,
                  quantum=4)
    decode["check"] = {"feature_rows": 4, "feature_block": 2}
    (out / "traffic" / "decode32.json").write_text(json.dumps(decode))
    mix = _load(BENCH / "traffic" / "drift64k.json")
    mix.update(streams=64, warm_ticks=12, share_ticks=16, quantum=4, bout_mean=6,
               dwell_mean=16)
    mix["check"] = {"head_rows": 16}
    (out / "traffic" / "drift64k.json").write_text(json.dumps(mix))
    # Limits at these sizes, set like the real ones: above the largest
    # reading of the program on the CPU (features 0.014, heads 6e-5, no
    # flips) and below the smallest of the control (features 0.105, heads
    # 0.065, flips 0.019, ladder gaps 0.19).
    for name, doc in (("h2o-danube-1.8b", danube), ("har-odl", har)):
        doc["limits"].update(head_flip_share=0.01, beta_err_max=0.02, p_err_max=0.02,
                             theta_gap_share=0.1)
        if "feat_err_max" in doc["limits"]:
            doc["limits"]["feat_err_max"] = 0.05
        (out / "configs" / f"{name}.json").write_text(json.dumps(doc))
    bench = copy.deepcopy(real)
    return bench, out
