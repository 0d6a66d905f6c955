"""Durable session driver: cadence snapshots, resume, crash-restart smoke.

Thin glue over ``engine/snapshot.py`` for the single-tenant case —
``run_durable`` is ``stream.run`` plus a periodic
``CheckpointManager``-published snapshot and a ``resume=True`` path that
restores the latest published snapshot and seeks the tick source to the
recorded cursor.  The multi-tenant equivalents (per-tenant snapshot
directories, ``run_supervised`` crash-restart supervision, live tenant
migration) live in ``engine/multiplex.py``.

This module is also the kill-and-resume proof, runnable standalone::

    PYTHONPATH=src python -m repro.engine.durable --crash-smoke

spawns a child multiplexing two lossy tenants with cadence snapshots,
SIGKILLs it mid-stream once snapshots are published, resumes from the
snapshot directory, and asserts that every tenant completes with the
query-accounting identity intact — the CI smoke for the whole durability
stack (ISSUE 4).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import signal
import subprocess
import sys
import tempfile
import time
from typing import Iterable, Optional

import numpy as np

from repro.engine import multiplex, snapshot, stream
from repro.engine.types import EngineConfig, EngineState
from repro.runtime import accel
from repro.runtime.checkpoint import CheckpointManager


def run_durable(
    state: Optional[EngineState],
    ticks: Iterable,
    cfg: EngineConfig,
    teacher: stream.Teacher,
    snapshot_dir: str,
    snapshot_every: int = 1000,
    resume: bool = False,
    keep: int = 3,
    mode: str = "algo1",
    capacity: int = 64,
    backpressure: str = "drop_oldest",
    collect: bool = True,
    drain: bool = True,
    donate: Optional[bool] = None,
):
    """``stream.run`` with durability: every ``snapshot_every`` ticks the
    session is serialized and published atomically (keep-``keep``) under
    ``snapshot_dir``.  With ``resume=True`` and a published snapshot, the
    run restores it — the tick source must then be seekable
    (``snapshot.ResumableTicks``); a teacher that supports
    ``restore_snapshot`` (e.g. ``LatencyTeacher``) resumes bit-for-bit,
    any other teacher gets the in-flight ring re-asked.

    Returns ``(final state, outputs, stats)`` exactly like ``stream.run``.
    """
    manager = CheckpointManager(snapshot_dir, keep=keep)
    sess = None
    if resume and manager.latest_step() is not None:
        _, tree = manager.restore()
        sess = stream.StreamSession.restore(tree, teacher, cfg=cfg)
        snapshot.seek_ticks(ticks, snapshot.ticks_consumed(tree))
    if sess is None:
        if state is None:
            raise ValueError("no state and no snapshot to resume from")
        sess = stream.StreamSession(
            state, cfg, teacher, mode=mode, capacity=capacity,
            backpressure=backpressure, collect=collect, donate=donate,
        )
    last_snap = sess.t
    it = iter(ticks)
    if not sess.started():
        x0 = next(it, None)
        if x0 is not None:
            sess.start(x0)
    # A started session always has a planned tick pending (``_p``) until the
    # source is exhausted — the same double-buffered drive as ``stream.run``,
    # except it also works for a session restored mid-stream.
    try:
        while sess._p is not None:
            nxt = next(it, None)
            sess.advance(nxt)
            if snapshot_every > 0 and sess.t - last_snap >= snapshot_every:
                manager.save_async(sess.t, sess.snapshot())
                last_snap = sess.t
    finally:
        # Settle any in-flight background write before returning OR before a
        # crash propagates — a restarted attempt must never race an orphaned
        # writer thread for the same step directory.
        manager.wait()
    return sess.finish(drain=drain)


# ---------------------------------------------------------------------------
# Kill-and-resume smoke (CI): two lossy tenants, SIGKILL, resume, reconcile
# ---------------------------------------------------------------------------

_SMOKE_TENANTS = 2
_SMOKE_S = 8
_N_IN, _N_HIDDEN, _N_OUT = 16, 16, 4


def _smoke_cfg() -> EngineConfig:
    from repro.core import drift as drift_mod
    from repro.core import oselm, pruning

    return EngineConfig(
        elm=oselm.OSELMConfig(
            n_in=_N_IN, n_hidden=_N_HIDDEN, n_out=_N_OUT, variant="hash", ridge=1e-2
        ),
        prune=pruning.PruneConfig(min_trained=1_000_000),  # cold: every tick asks
        drift=drift_mod.DriftConfig(),
    )


def _smoke_data(t_len: int, seed: int):
    rng = np.random.default_rng(seed)
    xs = np.tanh(rng.normal(size=(t_len, _SMOKE_S, _N_IN))).astype(np.float32)
    ys = rng.integers(0, _N_OUT, size=(t_len, _SMOKE_S)).astype(np.int32)
    return xs, ys


def _smoke_tenants(t_len: int, tick_sleep_s: float):
    """Fresh tenant list — deterministic across processes (seeded data and
    teachers; resumed teachers restore their RNG from the snapshot)."""
    from repro.engine import fleet

    cfg = _smoke_cfg()
    tenants = []
    for i in range(_SMOKE_TENANTS):
        xs, ys = _smoke_data(t_len, seed=100 + i)

        def factory(start, xs=xs):
            for t in range(start, len(xs)):
                if tick_sleep_s > 0:
                    time.sleep(tick_sleep_s)
                yield xs[t]

        tenants.append(
            multiplex.Tenant(
                name=f"tenant{i}",
                state=fleet.init_fleet(cfg, _SMOKE_S),
                ticks=snapshot.ResumableTicks(factory),
                cfg=cfg,
                teacher=stream.LatencyTeacher(
                    stream.array_labels(ys), latency=2, jitter=2,
                    loss_prob=0.2, partial_prob=0.1, seed=7 + i,
                ),
                mode="train_phase",
                capacity=4,
                backpressure=("drop_oldest", "coalesce")[i % 2],
                collect=False,
            )
        )
    return tenants


def _smoke_run(snapshot_dir: str, ticks: int, snapshot_every: int,
               tick_sleep_s: float, resume: bool) -> dict:
    results, agg = multiplex.run(
        _smoke_tenants(ticks, tick_sleep_s),
        snapshot_dir=snapshot_dir,
        snapshot_every=snapshot_every,
        resume=resume,
    )
    report = {}
    for name, r in sorted(results.items()):
        s = r.stats
        report[name] = {
            "ticks": s.ticks,
            "queries_issued": s.queries_issued,
            "labels_applied": s.labels_applied,
            "queries_lost": s.queries_lost,
            "queries_dropped": s.queries_dropped,
            "queries_coalesced": s.queries_coalesced,
            "tickets_reasked": s.tickets_reasked,
            "reconciled": s.reconciled,
        }
    return report


def _crash_smoke(ticks: int, snapshot_every: int) -> int:
    """Phase 1: child runs slowly with cadence snapshots; parent SIGKILLs it
    once every tenant has a published snapshot.  Phase 2: resume in-process
    from the snapshot directory, run to completion, assert reconciliation.

    The parent stays off JAX until the child is dead: the child needs the
    device, and on a TPU host a parent holding it would starve the child."""
    if accel.backend_initialized():
        raise RuntimeError("crash smoke: this process already holds a JAX "
                           "backend, so the child could not claim the device")
    src_root = str(pathlib.Path(__file__).resolve().parents[2])
    env = dict(os.environ)
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory(prefix="durable_smoke_") as d:
        child = subprocess.Popen(
            [sys.executable, "-m", "repro.engine.durable", "--smoke-child",
             "--dir", d, "--ticks", str(ticks),
             "--snapshot-every", str(snapshot_every), "--tick-sleep-ms", "5"],
            env=env,
        )
        try:
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                published = [
                    name
                    for name in os.listdir(d)
                    if CheckpointManager(os.path.join(d, name)).latest_step()
                    is not None
                ]
                if len(published) >= _SMOKE_TENANTS:
                    break
                if child.poll() is not None:
                    raise RuntimeError(
                        "smoke child exited before any snapshot was published "
                        f"(rc={child.returncode}) — nothing to kill"
                    )
                time.sleep(0.05)
            else:
                raise RuntimeError("timed out waiting for snapshots")
            child.send_signal(signal.SIGKILL)  # crash mid-stream, mid-anything
            child.wait(timeout=30)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait(timeout=30)
        if accel.backend_initialized():
            raise RuntimeError("crash smoke: the parent touched JAX while "
                               "the child was alive")
        report = _smoke_run(d, ticks, snapshot_every, tick_sleep_s=0.0, resume=True)
        # odlint: disable=ODL005 -- CI crash-smoke CLI prints its report
        print(json.dumps(report, indent=2))
        for name, r in report.items():
            assert r["reconciled"], f"{name}: accounting broken after resume: {r}"
            assert r["ticks"] == ticks, f"{name}: resumed run incomplete: {r}"
            assert r["labels_applied"] > 0, f"{name}: resumed run never trained"
    # odlint: disable=ODL005 -- CI crash-smoke CLI status line
    print(f"crash smoke OK: {_SMOKE_TENANTS} tenants killed mid-stream, "
          f"resumed from snapshots, accounting reconciled")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--crash-smoke", action="store_true",
                    help="SIGKILL a snapshotting child mid-stream, resume, "
                    "assert the accounting identity reconciles")
    ap.add_argument("--smoke-child", action="store_true",
                    help="(internal) run the lossy multi-tenant workload")
    ap.add_argument("--dir", default=None, help="snapshot directory")
    ap.add_argument("--ticks", type=int, default=400)
    ap.add_argument("--snapshot-every", type=int, default=25)
    ap.add_argument("--tick-sleep-ms", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)
    accel.enable_compile_cache()
    if args.crash_smoke:
        return _crash_smoke(args.ticks, args.snapshot_every)
    if args.smoke_child:
        assert args.dir, "--smoke-child needs --dir"
        report = _smoke_run(
            args.dir, args.ticks, args.snapshot_every,
            tick_sleep_s=args.tick_sleep_ms / 1000.0, resume=args.resume,
        )
        # odlint: disable=ODL005 -- smoke-child CLI: parent parses stdout
        print(json.dumps(report, indent=2))
        return 0
    ap.error("choose --crash-smoke or --smoke-child")
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
