"""Tick sources: what drives the program's serving entry in a cell.

A traffic mix (``traffic/<mix>.json``) names its source kind in
``"source"``; the kind is the file ``sources/<kind>.py``, which defines
``Source``, a subclass of ``Cell`` below.  A new kind is a new file.  The
kinds here:

* ``decode`` — a backbone decode batch feeding the heads of every tenant;
* ``har`` — users' sensor streams from the HAR surrogate, in one tenant or
  several.

Every kind runs the program's own serving path: ``multiplex.Multiplexer``
over ``multiplex.Tenant``s stepped with ``round()``, the entry
``launch/serve`` drives.  The benchmark owns only the tick sources and the
teachers; the teachers record what they were asked and what they answered,
which is what the reference replays (``heads_ref.replay``).
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs
from bench import heads_ref


# Set-up ends only after this many ticks in a row compiled no program.
QUIET_TICKS = 48


def base_key(seed: int):
    """A JAX key for any non-negative seed (PRNGKey keeps only 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


class BenchTeacher:
    """The program's ``Teacher`` protocol with a tick-granular latency and
    jitter, labels from ``label_fn`` at ask time, and a log of its traffic
    restricted to the recorded ``rows``."""

    def __init__(self, label_fn, latency: int, jitter: int, seed: int, rows):
        self.label_fn, self.latency, self.jitter = label_fn, latency, jitter
        self.rows = np.asarray(rows)
        self._rng = np.random.default_rng(seed)
        self._next = 0
        self._inbox: list = []
        self.events: list = []  # see heads_ref.replay
        self.asked = {}  # ticket -> (tick, streams asked over all rows)

    def ask(self, feats, mask, tick):
        ticket = self._next
        self._next += 1
        due = tick + self.latency + int(self._rng.integers(0, self.jitter + 1))
        mask = np.array(mask, bool)
        labels = np.asarray(self.label_fn(tick, feats), np.int32)
        self._inbox.append((due, ticket, mask, labels))
        self.events.append(("ask", tick, ticket, mask[self.rows], labels[self.rows]))
        self.asked[ticket] = (tick, int(mask.sum()))
        return ticket

    def poll(self, tick):
        from repro.engine import stream

        ready = [e for e in self._inbox if e[0] <= tick]
        if not ready:
            return []
        self._inbox = [e for e in self._inbox if e[0] > tick]
        ready.sort(key=lambda e: (e[0], e[1]))
        self.events.append(("poll", tick, [e[1] for e in ready]))
        return [stream.TeacherReply(ticket=t, labels=lab, answered=m)
                for _, t, m, lab in ready]

    def in_flight(self):
        return len(self._inbox)

    def queries(self, lo: int = 0, hi: int | None = None) -> int:
        """Stream-queries received for ticks in [lo, hi)."""
        return sum(n for t, n in self.asked.values() if t >= lo and (hi is None or t < hi))


class SharedFeed:
    """One tick source read by several tenants: tick k is produced once,
    when the first tenant asks for it, and dropped once all have read it.
    ``view(i, x)`` gives reader i its part of a tick.  After ``stop()`` no
    new tick is produced, so every tenant ends on the same tick."""

    def __init__(self, produce, n_readers: int, view=None):
        self.produce = produce
        self.view = view or (lambda i, x: x)
        self.buf: dict = {}
        self.cursors = [0] * n_readers
        self.stopped = False

    def stop(self):
        self.stopped = True

    def reader(self, i: int):
        while True:
            k = self.cursors[i]
            if k not in self.buf:
                if self.stopped:
                    return
                self.buf[k] = self.produce(k)
            x = self.buf[k]
            self.cursors[i] += 1
            if all(c > k for c in self.cursors):
                del self.buf[k]
            yield self.view(i, x)


def engine_config(doc: dict, n_in: int):
    """The program's ``EngineConfig`` for the configuration's head."""
    from repro import engine
    from repro.core import drift, oselm, pruning

    h, p, d = doc["head"], doc["head"]["prune"], doc["head"]["drift"]
    elm = oselm.OSELMConfig(n_in=n_in, n_hidden=h["n_hidden"], n_out=h["n_out"],
                            variant=h["variant"], seed=h["hash_seed"],
                            ridge=h["ridge"], alpha_scale=h["alpha_scale"])
    return engine.EngineConfig(
        elm=elm,
        prune=pruning.PruneConfig(ladder=tuple(p["ladder"]), x_consec=p["x_consec"],
                                  min_trained=p["min_trained"]),
        drift=drift.DriftConfig(ewma_decay=d["ewma_decay"], k_sigma=d["k_sigma"],
                                warmup=d["warmup"], enter_hits=d["enter_hits"],
                                exit_calm=d["exit_calm"]))


def batch_head_state(ecfg, x0, y0, n_streams: int, precision: str):
    """The program's batch OS-ELM init on (x0, y0), broadcast to every
    stream."""
    from repro import engine
    from repro.core import oselm

    with jax.default_matmul_precision(precision):
        elm = oselm.init_state_batch(ecfg.elm, jnp.asarray(x0, jnp.float32),
                                     jax.nn.one_hot(jnp.asarray(y0), ecfg.elm.n_out))
    return engine.broadcast_streams(engine.init_state(ecfg)._replace(elm=elm), n_streams)


def tenant_streams(mix: dict) -> list:
    """Streams of each tenant: ``"streams"`` is one count for every one of
    ``"tenants"``, or a list with one count per tenant."""
    s = mix["streams"]
    return list(s) if isinstance(s, list) else [s] * mix["tenants"]


class Cell:
    """What the harness drives: set-up, rounds of the multiplexer, the
    close of the window, and the comparison with the reference.

    A kind sets ``n_in`` and ``streams`` (tenant -> streams) in
    ``setup_program`` and defines ``stop_sources``, ``handed`` (tenant ->
    ticks handed to it) and ``check``; ``tick_op`` names the jitted
    function that makes one tick on the device."""

    tick_op = None

    def __init__(self, doc: dict, mix: dict, seed: int, ref_module=None):
        self.doc, self.mix, self.seed = doc, mix, seed
        self.ref_module = ref_module
        self.rng = np.random.default_rng(seed)
        self.mux = None
        self.teachers: dict = {}
        self.streams: dict = {}
        self.results = None

    # -- the multiplexer ------------------------------------------------------

    def _build_mux(self, states: dict, feeds: dict, labelers: dict, rows: dict):
        from repro.engine import multiplex

        m, t = self.mix, self.mix["teacher"]
        tenants = []
        for i, name in enumerate(states):
            self.teachers[name] = BenchTeacher(labelers[name], t["latency"],
                                               t["jitter"], self.seed + 7919 * (i + 1),
                                               rows[name])
            tenants.append(multiplex.Tenant(
                name=name, state=states[name], ticks=feeds[name], cfg=self.ecfg,
                teacher=self.teachers[name], mode=m["mode"], capacity=m["capacity"],
                backpressure=m["backpressure"], collect=False))
        self.mux = multiplex.Multiplexer(tenants, sched=m["sched"],
                                         quantum=m["quantum"], fuse=m["fuse"])
        for ten in tenants:  # the sessions own copies; drop the originals
            ten.state = None

    def round(self):
        self.mux.round()

    def counters(self) -> dict:
        """Ticks handed to the tenants, stream-steps (ticks times streams)
        and stream-queries the teachers received, as the benchmark counts
        them; queries the program dropped or lost, as it counts them."""
        handed = self.handed()
        out = {"ticks": max(handed.values()),
               "steps": sum(k * self.streams[n] for n, k in handed.items()),
               "queries": sum(t.queries() for t in self.teachers.values()),
               "dropped": 0, "lost": 0}
        for name in self.teachers:
            st = self.mux.session(name).stats
            out["dropped"] += st.queries_dropped
            out["lost"] += st.queries_lost
        return out

    def share(self, lo: int, hi: int) -> float:
        """Stream-queries over stream-steps on ticks [lo, hi) of every
        tenant.  The ask decisions depend on the tick and not on the time,
        so this is a function of the seed alone."""
        queries = sum(t.queries(lo, hi) for t in self.teachers.values())
        return queries / ((hi - lo) * sum(self.streams.values()))

    def warm(self, log, compiles):
        """Rounds until every tenant has ticked ``warm_ticks`` times and
        the last ``QUIET_TICKS`` ticks compiled or loaded no program
        (``compiles()`` counts both); logs the query share of each stretch
        of ticks."""
        # The program makes an all-true learn mask on the device the first
        # time one ticket covers every stream of a session or of a fused
        # cohort, which some seeds reach only after many ticks: make that
        # small program for every width here.
        for n in set(self.streams.values()) | {sum(self.streams.values())}:
            jnp.ones((n,), jnp.bool_).block_until_ready()
        want, last = self.mix["warm_ticks"], self.counters()
        quiet_from, seen = 0, compiles()
        while True:
            self.round()
            now = self.counters()
            if compiles() != seen:
                seen, quiet_from = compiles(), now["ticks"]
            done = now["ticks"] >= max(want, quiet_from + QUIET_TICKS)
            if now["ticks"] - last["ticks"] >= 32 or done:
                share = (now["queries"] - last["queries"]) / max(now["steps"] - last["steps"], 1)
                log(f"warm: ticks {now['ticks']}, query share of the last "
                    f"{now['ticks'] - last['ticks']} ticks {100 * share:.2f}%")
                last = now
            if done:
                return

    def run_to(self, tick: int):
        """Rounds until every tenant has been handed ``tick`` ticks."""
        while min(self.handed().values()) < tick:
            self.round()

    def close(self):
        """End the tick sources and let the multiplexer finish: every tenant
        runs its last tick and drains its teacher."""
        self.stop_sources()
        self.results, _ = self.mux.run()

    # -- what the traced ticks needed ----------------------------------------

    def work(self, t0: int, t1: int) -> dict:
        """(flops, bytes) of each piece of work of ticks [t0, t1), by kind
        of work: here the heads' plan of every tick and the learn of every
        reply (``costs``)."""
        h = self.doc["head"]
        rows = sum(self.streams.values())
        work = {"head": [costs.head_plan(rows, self.n_in, h["n_hidden"], h["n_out"])
                         for _ in range(t0, t1)]}
        for teacher in self.teachers.values():
            for ev in teacher.events:
                if ev[0] == "poll" and t0 <= ev[1] < t1:
                    n = sum(teacher.asked[k][1] for k in ev[2])
                    work["head"].append(costs.head_learn(n, h["n_hidden"], h["n_out"]))
        return work

    # -- the check ------------------------------------------------------------

    def head_rows_state(self, name, rows):
        st = self.results[name].state
        idx = jnp.asarray(rows)
        return {"beta": np.asarray(st.elm.beta[idx], np.float64),
                "P": np.asarray(st.elm.P[idx], np.float64),
                "level": np.asarray(st.prune.level[idx])}

    def free_program(self):
        """Drop every device buffer of the program (the check has taken
        what it needs to the host)."""
        self.mux = None
        self.results = {n: r._replace(state=None) for n, r in self.results.items()}
        gc.collect()

    def replay_heads(self, finals: dict, n_ticks: dict, x0y0: dict, x_at: dict,
                     rows: dict, control: bool) -> dict:
        """Each tenant's sampled rows through the reference replay, from the
        reference's own batch init on ``x0y0[name]``; with ``control`` the
        reference rounded to bfloat16 stands in for the program."""
        head = heads_ref.Head.from_config(self.doc, self.n_in)
        refs, replays, got = {}, {}, {}
        for n, teacher in self.teachers.items():
            x0, y0 = x0y0[n]
            refs[n] = heads_ref.Rows.batch_init(head, x0, y0, len(rows[n]))
            replays[n] = heads_ref.replay(refs[n], teacher.events, x_at[n], n_ticks[n],
                                          self.mix["capacity"])
            got[n] = finals[n]
            if control:
                ctrl = heads_ref.Rows.batch_init(head, x0, y0, len(rows[n]), heads_ref.bf16)
                replays[n] = heads_ref.replay(ctrl, teacher.events, x_at[n], n_ticks[n],
                                              self.mix["capacity"])
                got[n] = {"beta": ctrl.beta, "P": ctrl.P, "level": ctrl.level}
        return self.head_numbers(got, refs, replays)

    def head_numbers(self, finals: dict, refs: dict, replays: dict) -> dict:
        flips = sum(r["flips"] for r in replays.values())
        decisions = sum(r["decisions"] for r in replays.values())
        # np.max, not max: a NaN must reach the check and fail it.
        beta = float(np.max([heads_ref.rel_err(finals[n]["beta"], refs[n].beta).max()
                             for n in finals]))
        p = float(np.max([heads_ref.rel_err(finals[n]["P"], refs[n].P).max()
                          for n in finals]))
        gap = sum(int((finals[n]["level"] != refs[n].level).sum()) for n in finals)
        rows = sum(len(refs[n].level) for n in finals)
        return {"head_flip_share": flips / max(decisions, 1), "beta_err_max": beta,
                "p_err_max": p, "theta_gap_share": gap / max(rows, 1)}
