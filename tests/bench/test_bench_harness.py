"""The benchmark harness on the CPU at tiny sizes: every cell resolves its
files by name, a new cell is a new file plus an entry, the traffic is
deterministic in the seed and the drift renewal process is stationary,
the result line has the contract's keys, and the measurement path refuses
a device that is not a TPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import tiny_cells

from bench import devices, harness, sources, trace as trace_mod

ROOT = tiny_cells.ROOT
FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / "trace_danube.json"


def quiet(msg):
    del msg


def test_every_cell_resolves_its_files_by_name():
    bench = harness.load_benchmark(ROOT)
    assert {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
            "per_layer"} == set(bench)
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        spec = harness.resolve(bench, w["name"])
        assert spec["config"]["name"] == w["config"]
        assert (ROOT / configs[w["config"]]["file"]).exists()
        assert issubclass(spec["source"], sources.Cell)
        assert spec["source"].tick_op
        assert spec["per_layer"], "every cell reports a per-layer metric"
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"}
        for m in spec["per_layer"]:
            assert callable(spec["readers"][m["name"]].read)
        if spec["config"].get("backbone"):
            assert callable(spec["ref"].features)


NEW_KIND = '''"""A source kind added as a file: the HAR kind with every tick's
drift severities drawn again."""

import pathlib

import numpy as np

from bench import harness

har = harness.load_module(pathlib.Path(__file__).with_name("har.py"), "bench_source_har")


class Source(har.Source):
    def setup_program(self, log):
        super().setup_program(log)
        produce, rng = self.feed.produce, np.random.default_rng([self.seed, 99])

        def redrawn(t):
            self.sev[:] = rng.uniform(*self.mix["severity"], len(self.sev))
            return produce(t)

        self.feed.produce = redrawn
'''


def test_a_new_config_kind_mix_and_metric_are_files_plus_entries(tmp_path):
    """A configuration, a source kind, a mix of two tenants and a metric,
    each added as a new file and an entry, run without a change to any
    file that was there."""
    bench, bdir = tiny_cells.make(tmp_path)
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}
    shutil.copy(bdir / "configs" / "har-odl.json", bdir / "configs" / "har-odl-b.json")
    (bdir / "sources" / "har_redrawn.py").write_text(NEW_KIND)
    mix = json.loads((bdir / "traffic" / "drift64k.json").read_text())
    mix.update(source="har_redrawn", streams=[24, 8], tenants=2)
    (bdir / "traffic" / "redrawn32.json").write_text(json.dumps(mix))
    (bdir / "metrics" / "ticks_traced.py").write_text(
        "def read(m):\n    return float(m.ticks) if m.ticks else None\n")
    bench["configs"].append({"name": "har-odl-b", "source": "x", "why": "x", "reduced": [],
                             "file": "bench/configs/har-odl-b.json"})
    bench["workloads"].append({"name": "har-b.redrawn32", "config": "har-odl-b",
                               "traffic": "redrawn32", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "ticks_traced", "unit": "ticks", "better": "higher",
                               "source": "device_trace", "layer": "device",
                               "moves": "stream_steps_per_s", "workloads": ["har-b.redrawn32"]})
    spec = harness.resolve(bench, "har-b.redrawn32", bdir)
    assert spec["source"].__module__ == "bench_source_har_redrawn"
    assert "ticks_traced" in spec["readers"]
    assert all(p.read_bytes() == b for p, b in before.items()), "an existing file changed"
    out = harness.run_cell(bench, "har-b.redrawn32", 5, 0.5, False, require_tpu=False,
                           bench_dir=bdir)
    assert out["attempted"] > 0 and out["correct"], out["check"]


def test_query_share_does_not_depend_on_the_windows_length(tmp_path):
    """The share counts a fixed range of ticks: a short window runs on past
    its close to reach them, and reads what a long one reads."""
    bench, bdir = tiny_cells.make(tmp_path)
    for workload in ("har.drift64k", "danube.decode32"):
        short, long_ = (harness.run_cell(bench, workload, 2**33 + 5, s, False,
                                         require_tpu=False, bench_dir=bdir)
                        for s in (0.01, 1.5))
        assert short["attempted"] < long_["attempted"]
        assert (short["metrics"]["query_share"]["value"]
                == long_["metrics"]["query_share"]["value"] > 0)


def _har_ticks(bdir, bench, seed, n, **mix):
    spec = harness.resolve(bench, "har.drift64k", bdir)
    cell = spec["source"](spec["config"], dict(spec["mix"], **mix), seed)
    cell.setup_program(quiet)
    ticks = cell.feed.reader(0)
    for _ in range(n):
        next(ticks)
    return cell


def test_traffic_is_deterministic_in_the_seed(tmp_path):
    bench, bdir = tiny_cells.make(tmp_path)
    a, b, c = (_har_ticks(bdir, bench, s, 20) for s in (2**33 + 1, 2**33 + 1, 2))
    assert all(np.array_equal(x, y) for x, y in zip(a.row_idx, b.row_idx))
    assert not all(np.array_equal(x, y) for x, y in zip(a.row_idx, c.row_idx))

    spec = harness.resolve(bench, "danube.decode32", bdir)
    feats = []
    for seed in (7, 7, 8):
        cell = spec["source"](spec["config"], spec["mix"], seed, spec["ref"])
        cell.setup_program(quiet)
        feats.append(np.stack([np.asarray(cell._produce(k)) for k in range(20)]))
    assert np.array_equal(feats[0], feats[1])
    assert not np.array_equal(feats[0], feats[2])


def test_drift_renewal_process_is_stationary(tmp_path):
    bench, bdir = tiny_cells.make(tmp_path)
    cell = _har_ticks(bdir, bench, 11, 256, streams=8192, dwell_mean=32)
    sw = np.asarray(cell.switches, float) / 8192
    first, second = sw[:128].mean(), sw[128:].mean()
    # Geometric dwells with residual lives drawn from the same law: the
    # switch rate is 1/dwell_mean from the first tick on, with no trend.
    assert abs(first - 1 / 32) < 0.1 / 32 and abs(second - 1 / 32) < 0.1 / 32

    spec = harness.resolve(bench, "har.drift64k", bdir)
    cell = spec["source"](spec["config"], dict(spec["mix"], streams=256), 11)
    cell.setup_program(quiet)
    cell.warm(quiet, lambda: 0)
    shares = []
    for _ in range(2):
        c0 = cell.counters()
        while cell.counters()["ticks"] < c0["ticks"] + 200:
            cell.round()
        c1 = cell.counters()
        shares.append((c1["queries"] - c0["queries"]) / (c1["steps"] - c0["steps"]))
    assert abs(shares[1] - shares[0]) < 0.15 * shares[0], shares


def test_result_line_has_the_contract_keys(tmp_path, monkeypatch):
    bench, bdir = tiny_cells.make(tmp_path)
    out = harness.run_cell(bench, "danube.decode32", 3, 0.5, False, require_tpu=False,
                           bench_dir=bdir)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "check"]
    assert set(out["metrics"]) == {"stream_steps_per_s", "query_share", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    assert all({"value", "limit"} == set(c) for c in out["check"].values())

    # A traced run, its trace replaced by one recorded on the chip.
    fixture = json.loads(FIXTURE.read_text())
    monkeypatch.setattr(trace_mod, "compact", lambda path: fixture)
    monkeypatch.setattr(trace_mod, "find_xplane", lambda d: d)
    out = harness.run_cell(bench, "danube.decode32", 3, 0.5, True, require_tpu=False,
                           bench_dir=bdir)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device",
                         "breakdown", "check"]
    assert {"device_idle_pct", "decode_ms", "head_ms", "tick_gap_p95_ms"} <= set(out["metrics"])
    assert not {"stream_steps_per_s", "query_share", "setup_s"} & set(out["metrics"])
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in out["breakdown"].values())

    # A kind with no decode work reads the same trace without a decode.
    out = harness.run_cell(bench, "har.drift64k", 3, 0.5, True, require_tpu=False,
                           bench_dir=bdir)
    assert {"device_idle_pct", "step_mfu", "head_ms"} <= set(out["metrics"])
    assert not {"decode_ms", "decode_roofline"} & set(out["metrics"])


def test_measurement_refuses_a_device_that_is_not_a_tpu():
    with pytest.raises(devices.NoChip):
        devices.device_line(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "danube.decode32",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
