"""Source kind ``mesh``: the ``har`` kind's users on a four-chip host, one
tenant whose stream axis is sharded over the chips.

The program's ``stream.ShardedStreamSession`` (the path ``launch/serve.py
--mesh-fleet`` runs, ``stream.run_sharded``) holds ``shards`` shard-local
sessions, shard k's heads on ``jax.devices()[k]``; no chip holds the whole
fleet.  Each round hands every shard its rows of one full-width tick, as
``run_sharded`` does, so a tick counts once however many shards it feeds.
Each shard has its own copy of the surrogate on its chip and its own
gather (``bench_gather``), so no full-width tick is staged anywhere, and
its own teacher.  The heads start from one batch-initialised state,
broadcast on the host and placed shard by shard.

The check follows ``check.head_rows`` rows drawn across the shards, an
equal share from each.  The work of the traced ticks is split by chip:
``head`` is the first chip's share, which ``head_roofline`` sets against
the device time averaged over the chips; ``head_other_chips`` the rest,
which the whole step's share counts too.
"""

from __future__ import annotations

import gc
import pathlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs, har_surrogate, harness
from bench.sources import BenchTeacher, batch_head_state, engine_config

har = harness.load_module(pathlib.Path(__file__).with_name("har.py"), "bench_source_har")


class ShardResult(NamedTuple):
    state: object
    stats: object


class Source(har.Source):
    tick_op = "bench_gather"

    def setup_program(self, log):
        from repro.engine import stream

        m, doc = self.mix, self.doc
        n_shards, s = m["shards"], m["streams"]
        if s % n_shards:
            raise ValueError(f"{s} streams do not split into {n_shards} equal shards")
        devices = jax.devices()[:n_shards]
        if len(devices) < n_shards:
            raise ValueError(f"{n_shards} shards need {n_shards} devices; "
                             f"JAX sees {len(devices)}")
        self.n_in = doc["n_in"]
        self.ecfg = engine_config(doc, self.n_in)
        width = s // n_shards
        names = [f"shard{k}" for k in range(n_shards)]
        self.streams = {n: width for n in names}
        self.bounds = {n: (k * width, (k + 1) * width) for k, n in enumerate(names)}
        sur = har_surrogate.generate(**doc["surrogate"])
        self.pool = sur.pool.reshape(-1, self.n_in)
        self.k = sur.pool.shape[1]
        self.init_batch = (sur.init_x, sur.init_y)
        self._pools = [jax.device_put(self.pool, d) for d in devices]
        self._devices = devices

        def bench_gather(pool, idx, scale, shift):
            x = pool[idx]
            return jnp.clip(x * scale[:, None] + shift[:, None], -3.0, 3.0)

        self._gather = jax.jit(bench_gather)
        rng = self.rng
        self.subjects = np.asarray(m["subjects"])
        self.subj = rng.choice(self.subjects, s)
        self.cls = rng.integers(0, har_surrogate.N_CLASSES, s)
        self.bout = rng.geometric(1.0 / m["bout_mean"], s)
        self.dwell = rng.geometric(1.0 / m["dwell_mean"], s) if m["dwell_mean"] else None
        sev_range = m["severity"]
        self.sev = (rng.uniform(*sev_range, s) if sev_range else np.ones(s)).astype(np.float32)
        self.switches: list = []
        per = m["check"]["head_rows"] // n_shards
        self.rows = {n: np.sort(np.random.default_rng([self.seed, 29, k]).choice(
            width, per, replace=False)) for k, n in enumerate(names)}
        followed = np.concatenate([self.bounds[n][0] + self.rows[n] for n in names])
        self._followed_abs = followed
        self._followed = {n: np.searchsorted(followed, self.bounds[n][0] + self.rows[n])
                          for n in names}
        self.row_idx: list = []
        self.row_sev: list = []
        self.labels: dict = {}
        t = m["teacher"]
        self.teachers = {
            n: BenchTeacher(lambda tick, feats, n=n: self.labels[tick][slice(*self.bounds[n])],
                            t["latency"], t["jitter"], self.seed + 7919 * (k + 1), self.rows[n])
            for k, n in enumerate(names)}
        one = jax.device_get(batch_head_state(self.ecfg, sur.init_x, sur.init_y, 1,
                                              doc["head"]["init_precision"]))
        fleet_state = jax.tree.map(lambda a: np.broadcast_to(a, (s,) + a.shape[1:]), one)
        self.sharded = stream.ShardedStreamSession(
            fleet_state, self.ecfg, list(self.teachers.values()), n_shards=n_shards,
            mode=m["mode"], capacity=m["capacity"], backpressure=m["backpressure"],
            collect=False, devices=devices)
        self.sessions = dict(zip(names, self.sharded.sessions))
        self.t = 0
        self.stopped = False
        log(f"set-up: 1 tenant of {s} streams in {n_shards} shards of {width} on "
            f"{[d.id for d in devices]}, HAR surrogate pool {self.pool.shape[0]} x "
            f"{self.n_in} on each chip, subjects {m['subjects']}, dwell {m['dwell_mean']}, "
            f"bouts {m['bout_mean']}, severity {sev_range}")

    def _step_users(self, t):
        """Advance every user's trajectory by one tick (as the ``har`` kind
        does) and return the surrogate index of each user's sample."""
        m, rng, s = self.mix, self.rng, len(self.subj)
        self.bout -= 1
        ends = np.flatnonzero(self.bout <= 0)
        self.cls[ends] = rng.integers(0, har_surrogate.N_CLASSES, len(ends))
        self.bout[ends] = rng.geometric(1.0 / m["bout_mean"], len(ends))
        if self.dwell is not None:
            self.dwell -= 1
            sw = np.flatnonzero(self.dwell <= 0)
            self.subj[sw] = rng.choice(self.subjects, len(sw))
            if m["severity"]:
                self.sev[sw] = rng.uniform(*m["severity"], len(sw))
            self.dwell[sw] = rng.geometric(1.0 / m["dwell_mean"], len(sw))
            self.switches.append(len(sw))
        idx = ((self.subj * har_surrogate.N_CLASSES + self.cls) * self.k
               + rng.integers(0, self.k, s)).astype(np.int32)
        self.labels[t] = self.cls.astype(np.int32)
        self.labels.pop(t - 64, None)
        self.row_idx.append(idx[self._followed_abs])
        self.row_sev.append(self.sev[self._followed_abs].copy())
        return idx

    def _produce(self, t):
        """Tick t's features, one array per shard on its own chip."""
        idx = self._step_users(t)
        shift = np.float32(0.4 if self.mix["severity"] else 0.0)
        out = []
        with jax.profiler.TraceAnnotation("bench.tick_source"):
            for k, (lo, hi) in enumerate(self.bounds.values()):
                dev = self._devices[k]
                sev = self.sev[lo:hi].copy()  # self.sev changes in place at a switch
                out.append(self._gather(self._pools[k], jax.device_put(idx[lo:hi], dev),
                                        jax.device_put(sev, dev),
                                        jax.device_put(sev * shift, dev)))
        return out

    def round(self):
        """One full-width tick: every shard is handed its rows."""
        from repro.distributed import sharding

        if self.stopped:
            return
        xs = self._produce(self.t)
        with sharding.deactivate():
            for sess, x in zip(self.sessions.values(), xs):
                if sess.started():
                    sess.advance(x)
                else:
                    sess.start(x)
        self.t += 1

    def stop_sources(self):
        self.stopped = True

    def handed(self) -> dict:
        return {n: self.t for n in self.sessions}

    def counters(self) -> dict:
        out = {"ticks": self.t, "steps": self.t * sum(self.streams.values()),
               "queries": sum(t.queries() for t in self.teachers.values()),
               "dropped": 0, "lost": 0}
        for sess in self.sessions.values():
            out["dropped"] += sess.stats.queries_dropped
            out["lost"] += sess.stats.queries_lost
        return out

    def close(self):
        """Finish every shard's last tick, drain its teacher, settle its
        accounting; each shard's state stays on its chip."""
        from repro.distributed import sharding

        self.stop_sources()
        self.results = {}
        with sharding.deactivate():
            for n, sess in self.sessions.items():
                if sess.started():
                    sess.advance(None)
                state, _, stats = sess.finish(drain=True)
                self.results[n] = ShardResult(state, stats)

    def free_program(self):
        self.sessions = self.sharded = None
        self.results = {n: r._replace(state=None) for n, r in self.results.items()}
        gc.collect()

    def work(self, t0: int, t1: int) -> dict:
        """The heads' plan and learn work of ticks [t0, t1): the first
        chip's under ``head``, the other chips' under
        ``head_other_chips``."""
        h = self.doc["head"]
        work = {"head": [], "head_other_chips": []}
        for k, (n, teacher) in enumerate(self.teachers.items()):
            part = work["head" if k == 0 else "head_other_chips"]
            part += [costs.head_plan(self.streams[n], self.n_in, h["n_hidden"], h["n_out"])
                     for _ in range(t0, t1)]
            for ev in teacher.events:
                if ev[0] == "poll" and t0 <= ev[1] < t1:
                    rows = sum(teacher.asked[i][1] for i in ev[2])
                    part.append(costs.head_learn(rows, h["n_hidden"], h["n_out"]))
        return work
