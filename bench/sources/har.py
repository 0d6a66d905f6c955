"""Source kind ``har``: users' sensor streams from the HAR surrogate.

Each stream follows its own subject, drawn from the mix's ``subjects``,
through activity bouts of mean ``bout_mean`` ticks.  With ``dwell_mean``
set, each stream changes subject as a seeded renewal process: geometric
dwells, the first one drawn from the same law, so the switch rate is the
same from the first tick on.  With ``severity`` set, a stream's features
are drifted as the program's drift stream drifts them, ``clip(x * sev +
0.4 * sev, -3, 3)``, at a severity drawn per subject; without it they are
the subject's samples as they are.

The host draws only per-stream sample indices; the features are gathered
on the device from a device-resident copy of the surrogate, one gather a
tick for every tenant's streams together.  Ground truth plays the teacher
(the paper's protocol).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import har_surrogate
from bench.sources import Cell, SharedFeed, batch_head_state, engine_config, tenant_streams


class Source(Cell):
    tick_op = "bench_gather"

    def setup_program(self, log):
        m, doc = self.mix, self.doc
        self.n_in = doc["n_in"]
        self.ecfg = engine_config(doc, self.n_in)
        sizes = tenant_streams(m)
        names = [f"tenant{i}" for i in range(len(sizes))]
        self.streams = dict(zip(names, sizes))
        bounds = np.cumsum([0] + sizes)
        self.bounds = {n: (int(bounds[i]), int(bounds[i + 1])) for i, n in enumerate(names)}
        s = int(bounds[-1])
        sur = har_surrogate.generate(**doc["surrogate"])
        self.pool = sur.pool.reshape(-1, self.n_in)
        self.k = sur.pool.shape[1]
        self.init_batch = (sur.init_x, sur.init_y)
        pool_dev = jnp.asarray(self.pool)

        def bench_gather(pool, idx, scale, shift):
            x = pool[idx]
            return jnp.clip(x * scale[:, None] + shift[:, None], -3.0, 3.0)

        gather = jax.jit(bench_gather)
        rng = self.rng
        subjects = np.asarray(m["subjects"])
        sev_range = m["severity"]
        self.subj = rng.choice(subjects, s)
        self.cls = rng.integers(0, har_surrogate.N_CLASSES, s)
        self.bout = rng.geometric(1.0 / m["bout_mean"], s)
        self.dwell = (rng.geometric(1.0 / m["dwell_mean"], s) if m["dwell_mean"]
                      else None)
        self.sev = (rng.uniform(*sev_range, s) if sev_range else np.ones(s)).astype(np.float32)
        shift = np.float32(0.4 if sev_range else 0.0)
        self.switches: list = []
        # The rows the check follows: a sample of each tenant's streams.
        self.rows = {}
        for i, n in enumerate(names):
            lo, hi = self.bounds[n]
            k = min(m["check"]["head_rows"], hi - lo)
            self.rows[n] = np.sort(np.random.default_rng([self.seed, 29, i]).choice(
                hi - lo, k, replace=False))
        followed = np.concatenate([self.bounds[n][0] + self.rows[n] for n in names])
        self.row_idx: list = []
        self.row_sev: list = []
        self.labels: dict = {}

        def produce(t):
            self.bout -= 1
            ends = np.flatnonzero(self.bout <= 0)
            self.cls[ends] = rng.integers(0, har_surrogate.N_CLASSES, len(ends))
            self.bout[ends] = rng.geometric(1.0 / m["bout_mean"], len(ends))
            if self.dwell is not None:
                self.dwell -= 1
                sw = np.flatnonzero(self.dwell <= 0)
                self.subj[sw] = rng.choice(subjects, len(sw))
                if sev_range:
                    self.sev[sw] = rng.uniform(*sev_range, len(sw))
                self.dwell[sw] = rng.geometric(1.0 / m["dwell_mean"], len(sw))
                self.switches.append(len(sw))
            idx = ((self.subj * har_surrogate.N_CLASSES + self.cls) * self.k
                   + rng.integers(0, self.k, s)).astype(np.int32)
            self.labels[t] = self.cls.astype(np.int32)
            self.labels.pop(t - 64, None)
            self.row_idx.append(idx[followed])
            self.row_sev.append(self.sev[followed].copy())
            # JAX may read a host array after the call returns (on the CPU
            # it may not copy it at all), and self.sev changes in place at
            # the next switch: hand it a copy.
            sev = self.sev.copy()
            with jax.profiler.TraceAnnotation("bench.tick_source"):
                return gather(pool_dev, jnp.asarray(idx), jnp.asarray(sev),
                              jnp.asarray(sev * shift))

        def view(i, x):
            lo, hi = self.bounds[names[i]]
            return x if (lo, hi) == (0, s) else x[lo:hi]

        self.feed = SharedFeed(produce, len(names), view)
        self._followed = {n: np.searchsorted(followed, self.bounds[n][0] + self.rows[n])
                          for n in names}
        states = {n: batch_head_state(self.ecfg, sur.init_x, sur.init_y, self.streams[n],
                                      doc["head"]["init_precision"]) for n in names}
        labelers = {n: (lambda tick, feats, n=n: self.labels[tick][slice(*self.bounds[n])])
                    for n in names}
        self._build_mux(states, {n: self.feed.reader(i) for i, n in enumerate(names)},
                        labelers, self.rows)
        del states
        log(f"set-up: {len(names)} tenant(s), {s} streams, HAR surrogate pool "
            f"{self.pool.shape[0]} x {self.n_in} on the device, subjects "
            f"{m['subjects']}, dwell {m['dwell_mean']}, bouts {m['bout_mean']}, "
            f"severity {sev_range}")

    def stop_sources(self):
        self.feed.stop()

    def handed(self) -> dict:
        return dict(zip(self.teachers, self.feed.cursors))

    def x_rows(self, name, t):
        """Tick t's features of the rows followed in tenant ``name``, as the
        gather makes them."""
        j = self._followed[name]
        idx, sev = self.row_idx[t][j], self.row_sev[t][j]
        shift = np.float32(0.4 if self.mix["severity"] else 0.0)
        x = self.pool[idx] * sev[:, None] + (sev * shift)[:, None]
        return np.clip(x, np.float32(-3.0), np.float32(3.0))

    def check(self, control: bool, log) -> dict:
        """Heads against the plain OS-ELM replay on a seeded sample of each
        tenant's streams, and the program's query accounting."""
        finals = {n: self.head_rows_state(n, self.rows[n]) for n in self.teachers}
        identity = sum(not r.stats.reconciled for r in self.results.values())
        n_ticks = {n: r.stats.ticks for n, r in self.results.items()}
        self.free_program()
        x_at = {n: (lambda t, n=n: self.x_rows(n, t)) for n in self.teachers}
        out = self.replay_heads(finals, n_ticks, {n: self.init_batch for n in self.teachers},
                                x_at, self.rows, control)
        out["identity_breaks"] = identity
        return out
