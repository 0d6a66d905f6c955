"""One process per chip: no import claims a device, children that need no
device stay off it, workers are pinned one per chip, parents that start
device-hungry children stay off JAX, and the compile cache sits where it
is told."""

import os
import pathlib
import subprocess
import sys
import textwrap
import time

import pytest

from repro.engine import rpc
from repro.kernels import ops
from repro.launch import serve
from repro.runtime import accel, elastic

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _python(code: str, timeout: float = 300, **env) -> str:
    full_env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), **env)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=full_env, capture_output=True, text=True,
                       timeout=timeout, cwd=ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.parametrize("module", [
    "repro.engine.rpc", "repro.runtime.worker", "repro.engine.durable",
    "repro.launch.serve",
])
def test_import_initializes_no_backend(module):
    """Under a platform that does not exist, any backend initialization
    raises — so a clean import proves the module touches no device (a
    device array built at import once claimed the chip for every child)."""
    out = _python(f"""
        import {module}
        from repro.runtime import accel
        assert not accel.backend_initialized()
        print("clean")
    """, JAX_PLATFORMS="nosuchplatform")
    assert "clean" in out


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    out = _python(f"""
        import jax, jax.numpy as jnp
        from repro.runtime import accel
        placed = accel.enable_compile_cache()
        assert placed == {str(tmp_path)!r}, placed
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
        print(jax.config.jax_compilation_cache_dir)
    """, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert out.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was cached in the given dir"


def test_compile_cache_defaults_to_one_fixed_dir_in_the_checkout():
    env = {k: v for k, v in os.environ.items() if k != accel.CACHE_ENV}
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax; from repro.runtime import accel; "
         "print(accel.enable_compile_cache()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=dict(env, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, check=True)
    placed, configured = r.stdout.split()
    assert placed == configured == str(ROOT / ".jax_cache")


def test_label_server_child_runs_on_the_cpu(monkeypatch):
    seen = {}

    class Spawned(Exception):
        pass

    def fake_popen(cmd, **kw):
        seen.update(kw["env"])
        raise Spawned

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(rpc.subprocess, "Popen", fake_popen)
    with pytest.raises(Spawned):
        with rpc.loopback_server():
            pass
    assert seen["JAX_PLATFORMS"] == "cpu"


def test_tpu_host_pins_each_worker_to_its_own_chip(monkeypatch):
    monkeypatch.setattr(accel, "host_tpu_chips", lambda env=None: 4)
    for chip in (None, 4, -1):
        with pytest.raises(ValueError, match="4 TPU chip"):
            elastic.spawn_worker("w", chip=chip)
    env = accel.one_chip_env(2)
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_PROCESS_ADDRESSES"] == f"localhost:{env['TPU_PROCESS_PORT']}"


def test_serve_fleet_refuses_more_workers_than_chips(monkeypatch):
    monkeypatch.setattr(accel, "host_tpu_chips", lambda env=None: 2)
    with pytest.raises(ValueError, match="needs a TPU chip each"):
        serve.serve_fleet(workers=3, tenants=3)


def test_no_tpu_chips_counted_when_platforms_exclude_the_tpu():
    assert accel.host_tpu_chips({"JAX_PLATFORMS": "cpu"}) == 0


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", False),
])
def test_kernels_interpret_only_on_the_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    assert ops._interpret() is interpret


def test_serve_fleet_router_never_touches_jax():
    """The router parent of a worker fleet (with its live migration and
    drain) initializes no JAX backend: on a TPU host it would hold the
    chips its workers need."""
    out = _python("""
        from repro.launch import serve
        from repro.runtime import accel
        serve.main(["--workers", "2", "--tenants", "2", "--batch", "4",
                    "--tokens", "3000"])
        print("router backend initialized:", accel.backend_initialized())
    """, timeout=600, JAX_PLATFORMS="cpu")
    assert "tenant0 migrated" in out
    assert "router backend initialized: False" in out


def test_crash_smoke_parent_stays_off_jax_until_the_child_is_dead():
    r = subprocess.run(
        [sys.executable, "-m", "repro.engine.durable", "--crash-smoke",
         "--ticks", "120", "--snapshot-every", "10"],
        env=dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "crash smoke OK" in r.stdout


def test_worker_answers_control_commands_while_tenants_run():
    """A busy scheduler loop must not starve the control socket: status
    answers while tenants are mid-stream, not after they finish (a starved
    status once made the router pack every tenant on one worker and skip
    the migration it was asked for)."""
    from repro import engine
    from repro.core import drift as drift_mod
    from repro.core import oselm, pruning
    from repro.runtime import worker as worker_mod

    cfg = engine.EngineConfig(
        elm=oselm.OSELMConfig(n_in=16, n_hidden=16, n_out=4, variant="hash",
                              ridge=1e-2),
        prune=pruning.PruneConfig(min_trained=1_000_000),
        drift=drift_mod.DriftConfig(),
    )
    w = elastic.spawn_worker("busy")
    try:
        for i in range(2):
            w.admit(worker_mod.tenant_spec(
                f"t{i}", cfg, s=32, mode="serve", capacity=8,
                backpressure="drop_oldest",
                ticks=worker_mod.synth_ticks_spec(seed=i, t_total=20_000),
                teacher=worker_mod.latency_teacher_spec(
                    n_out=4, latency=2, jitter=1, loss=0.0, seed=i)))
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:  # past the first compiles
            if min((r["t"] for r in w.status()["live"]), default=0) > 50:
                break
        t0 = time.monotonic()
        live = w.status()["live"]
        waited = time.monotonic() - t0
        assert live and all(r["t"] < 20_000 for r in live)
        assert waited < 2.0, waited
    finally:
        w.close()
