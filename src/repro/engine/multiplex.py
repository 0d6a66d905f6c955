"""Multi-tenant stream multiplexer: N independent fleets in one process.

The paper's deployment story is many edge devices sharing one teacher-side
host: each tenant is an independent fleet — its own ``EngineConfig``,
``EngineState``, tick source, ``Teacher``, pending-query ring, and
backpressure policy — but they all run in a single process, sharing the
engine's bounded compiled-runner LRUs (``stream._plan_runner`` /
``_learn_runner`` / ``_learn_plan_runner`` and ``fleet._chunk_runner``).
Tenants with the same ``(cfg, mode, donate)`` therefore share one compiled
executable: adding a tenant with a config already being served costs no
compile and no extra executable memory.

Every tenant ticks inside a scheduling *unit*: a group of the one tick
engine (``engine/cohort.py``).  Cohort fusion (``fuse=True``, the default)
packs tenants that also share a stream width into one unit of several
whose ``EngineState`` pytrees stack along the leading stream axis, so one
stacked dispatch per tick advances the whole cohort instead of one
dispatch per tenant — eliminating the tick-switch cache penalty entirely
at high tenant counts.  ``fuse=False`` keeps every tenant in a unit of its
own.  Each tenant keeps its own ring / teacher / backpressure / stats /
tick cursor, and per-tenant results are bit-for-bit identical either way
(locked by ``tests/test_cohort.py``).  A tenant whose tick source runs out
leaves its cohort for a unit of its own and drains there.

Scheduling (``sched``):

* ``"rr"`` (default) — round-robin with a ``quantum``-tick time slice:
  each unit advances by up to ``quantum`` plan/ask/poll/learn cycles
  before the scheduler moves on (switching every tick would evict the
  tenant's state from cache on every switch).
* ``"drr"`` — deficit round robin in *stream-step* (cost) units: every
  round each live tenant's deficit grows by the same credit
  (``quantum × min S``) and one tick debits that tenant's own S, so a
  tenant's share of device time is equal regardless of its size — an
  S=512 tenant runs ~1 tick for every 32 ticks of an S=16 tenant instead
  of head-of-line blocking it for ``quantum`` huge ticks.  Unspent credit
  carries over, so big tenants lose no throughput, only burstiness.

Because a session's per-tenant op sequence does not depend on what the
scheduler interleaves around it, a multiplexed tenant reproduces its solo
``stream.run`` bit-for-bit under either scheduler at any quantum (locked
by ``tests/test_multiplex.py``).  Tenants whose tick source is exhausted
are drained in bounded slices and finished; the multiplexer ends when
every tenant has finished.

Durability (``engine/snapshot.py``): pass ``snapshot_dir`` +
``snapshot_every`` and each tenant's session is serialized every
``snapshot_every`` ticks to ``<snapshot_dir>/<tenant>/step_*`` through
``runtime.checkpoint.CheckpointManager`` (atomic publish, keep-k, crashed
``.tmp`` fallback).  ``resume=True`` restores each tenant from its latest
published snapshot and seeks its (seekable) tick source to the recorded
cursor.  ``run_supervised`` wraps the whole thing in
``runtime.fault.run_with_restarts``: crash → restore → continue, bounded.

Live migration: ``Multiplexer.extract(name)`` quiesces a tenant (bounded
drain of in-flight replies), snapshots it, and removes it from this
scheduler; ``admit(tenant, snapshot=...)`` (or the ``snapshots=``
constructor arg) restores it into *another* multiplexer — in-flight
tickets that did not drain are re-asked through the new teacher connection
and metered (``tickets_reasked``), so the query-accounting identity
reconciles across the move.

Usage::

    results, agg = multiplex.run([
        multiplex.Tenant("edge-a", state_a, ticks_a, cfg_a, teacher_a),
        multiplex.Tenant("edge-b", state_b, ticks_b, cfg_b, teacher_b,
                         backpressure="coalesce"),
    ], sched="drr", snapshot_dir="/var/ckpt", snapshot_every=1000)

Teacher transport: ``shared_rpc_teachers`` builds per-tenant
``stream.Teacher`` handles over **shared** batched RPC connections —
tenants with the same ``(host, port)`` endpoint ride one
``rpc.BatchedRpcClient`` (one socket, one HMAC handshake per connection,
asks from all its tenants coalesced into single binary frames within the
flush window), so N tenants cost one round-trip stream per teacher host
instead of N.

``launch/serve.py`` drives this with ``--tenants`` / ``--backpressure`` /
``--sched`` / ``--snapshot-dir`` / ``--resume`` / ``--migrate``;
``benchmarks/multiplex_bench.py`` measures aggregate throughput and
``benchmarks/snapshot_bench.py`` the snapshot overhead.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import time
from typing import Iterable, NamedTuple, Optional

from repro.engine import snapshot as snapshot_mod
from repro.engine import stream
from repro.engine.types import EngineConfig, EngineState, FleetStepOutput
from repro.runtime import fault
from repro.runtime import telemetry as _telemetry
from repro.runtime.checkpoint import CheckpointManager

SCHEDULERS = ("rr", "drr")


def shape_key(cfg: EngineConfig, mode: str, donate: Optional[bool], s: int) -> str:
    """Stable cross-process id of a tenant's compiled-shape class.

    This is exactly the cohort fuse key ``(cfg, mode, donate, S)`` as a
    short hash: two tenants with equal keys share compiled executables here
    and can fuse into one cohort.  The elastic router
    (``runtime/elastic.py``) packs same-key tenants onto the same worker so
    that sharing actually happens — the key must therefore be computable on
    both sides of the wire, hence a digest of the JSON config rather than a
    Python hash.
    """
    blob = json.dumps(
        {
            "cfg": snapshot_mod.config_to_dict(cfg),
            "mode": mode,
            "donate": bool(True if donate is None else donate),
            "s": int(s),
        },
        sort_keys=True,
    )
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


@dataclasses.dataclass
class Tenant:
    """One fleet behind the multiplexer.

    ``name`` keys the result dict (must be unique).  Everything else is
    exactly what ``stream.run`` takes — per tenant: its own config, state,
    tick source, teacher, ring capacity, and backpressure policy
    (``stream.BACKPRESSURE_POLICIES``).  ``state`` may be None when the
    tenant is admitted from a snapshot (the snapshot carries the state).
    """

    name: str
    state: Optional[EngineState]
    ticks: Iterable  # yields (S, n_in) feature arrays, one per tick
    cfg: Optional[EngineConfig]
    teacher: stream.Teacher
    mode: str = "algo1"
    capacity: int = 64
    backpressure: str = "drop_oldest"
    collect: bool = True
    donate: Optional[bool] = None


def shared_rpc_teachers(
    endpoints,
    timeout_s: float = 5.0,
    connect_timeout_s: float = 5.0,
    secret: Optional[str] = None,
    batch_window_s: Optional[float] = None,
    batch_max: Optional[int] = None,
    compress: bool = False,
):
    """Per-tenant teachers over shared batched RPC connections.

    ``endpoints[i]`` is tenant i's ``(host, port)``; tenants with the same
    endpoint share **one** ``rpc.BatchedRpcClient`` — one socket per
    teacher host, one HMAC handshake per connection (not per tenant), and
    every tenant's asks coalesced into batched frames within the flush
    window.  Returns ``(teachers, clients)``: ``teachers[i]`` is tenant
    i's ``stream.Teacher`` handle, ``clients`` the deduplicated
    connections (close them — not the handles — when the run is done).
    """
    from repro.engine import rpc  # deferred: keep `python -m repro.engine.rpc` clean

    if batch_window_s is None:
        batch_window_s = rpc.DEFAULT_BATCH_WINDOW_S
    if batch_max is None:
        batch_max = rpc.DEFAULT_BATCH_MAX
    clients: dict = {}
    teachers = []
    try:
        for i, (host, port) in enumerate(endpoints):
            key = (host, int(port))
            client = clients.get(key)
            if client is None:
                client = clients[key] = rpc.BatchedRpcClient(
                    host, int(port), timeout_s=timeout_s,
                    connect_timeout_s=connect_timeout_s, secret=secret,
                    batch_window_s=batch_window_s, batch_max=batch_max,
                    compress=compress,
                )
            teachers.append(client.tenant(name=f"tenant{i}"))
    except BaseException:
        # A later endpoint's dial/handshake failed: the clients already
        # built (sockets + reader/flusher threads) would otherwise leak
        # for the life of the process.
        for client in clients.values():
            with contextlib.suppress(Exception):
                client.close()
        raise
    return teachers, list(clients.values())


class TenantResult(NamedTuple):
    name: str
    state: EngineState
    outputs: Optional[FleetStepOutput]
    stats: stream.StreamStats


@dataclasses.dataclass
class MultiplexStats:
    """Aggregate view over one multiplexed run.

    ``wall_s`` is the scheduler's wall time (shared by all tenants — each
    tenant's own ``StreamStats.wall_s`` spans the whole multiplexed run,
    so per-tenant ``steps_per_s`` is *not* additive; use
    ``steps_per_s`` here for aggregate throughput).
    """

    n_tenants: int = 0
    rounds: int = 0
    stream_steps: int = 0
    ticks: int = 0
    snapshots: int = 0
    wall_s: float = 0.0

    @property
    def steps_per_s(self) -> float:
        return self.stream_steps / self.wall_s if self.wall_s > 0 else 0.0

    def summary(self) -> dict:
        return {
            "n_tenants": self.n_tenants,
            "rounds": self.rounds,
            "ticks": self.ticks,
            "stream_steps": self.stream_steps,
            "snapshots": self.snapshots,
            "steps_per_s": self.steps_per_s,
            "wall_s": self.wall_s,
            "caches": stream.cache_stats(),
        }


class _Slot:
    """Scheduler-side bookkeeping for one tenant."""

    # Drain polls allowed per scheduler slice: a drain poll is far cheaper
    # than a real tick (no device dispatch), but a laggy teacher must not
    # head-of-line block live tenants, so a draining tenant gets a bounded
    # budget per round and resumes next round.
    DRAIN_TICKS_PER_SLICE = 64
    DRAIN_IDLE_SLEEP_S = 50e-6

    def __init__(
        self,
        tenant: Tenant,
        manager: Optional[CheckpointManager] = None,
        snapshot_every: int = 0,
        resume: bool = False,
        snapshot_tree: Optional[dict] = None,
        pending: str = "auto",
        positioned: bool = False,
    ):
        self.tenant = tenant
        self.manager = manager
        self.snapshot_every = snapshot_every
        from_manager = False
        if snapshot_tree is None and resume and manager is not None:
            if manager.latest_step() is not None:
                _, snapshot_tree = manager.restore()
                from_manager = True
        if snapshot_tree is not None:
            self.session = stream.StreamSession.restore(
                snapshot_tree, tenant.teacher, cfg=tenant.cfg, pending=pending
            )
            consumed = snapshot_mod.ticks_consumed(snapshot_tree)
            if from_manager or getattr(tenant.ticks, "seek", None) is not None:
                # Crash-restart: the fresh source is definitely at tick 0 —
                # it MUST be seekable (seek_ticks raises otherwise; silently
                # replaying ticks 0..k-1 into a t=k session would corrupt
                # training).
                snapshot_mod.seek_ticks(tenant.ticks, consumed)
            elif not positioned:
                # An explicit migration snapshot may hand over the
                # partially-consumed iterator itself (what ``extract``
                # returns) — but only with an explicit opt-in: silently
                # treating a fresh tick-0 iterator as positioned at tick k
                # would replay ticks into a t=k session.
                raise ValueError(
                    f"tenant {tenant.name!r}: restoring a snapshot needs a "
                    "seekable tick source (snapshot.ResumableTicks), or "
                    "pass positioned=True when handing over the "
                    "partially-consumed iterator returned by extract()"
                )
        else:
            if tenant.state is None or tenant.cfg is None:
                raise ValueError(
                    f"tenant {tenant.name!r} has no state/cfg and no snapshot "
                    "to restore from"
                )
            self.session = stream.StreamSession(
                tenant.state,
                tenant.cfg,
                tenant.teacher,
                mode=tenant.mode,
                capacity=tenant.capacity,
                backpressure=tenant.backpressure,
                collect=tenant.collect,
                donate=tenant.donate,
            )
        # Telemetry series for this tenant key on its name (worker/serve
        # layers add their own labels at scrape time).
        self.session.telemetry_labels = {"tenant": tenant.name}
        # Tick cost for the deficit scheduler = this tenant's stream count.
        self.s = self.session.width
        self.unit: Optional["_Unit"] = None  # the unit this tenant ticks in
        self.snapshots_taken = 0
        self._last_snap_t = self.session.t
        # Source exhausted; a session restored from a snapshot taken after
        # its stream ended has nothing left to plan, only the drain.
        self.draining = self.session.started() and self.session._p is None
        self._drain_ticks = 0  # cumulative, capped at stream.MAX_DRAIN_TICKS
        self.result: Optional[TenantResult] = None

    def drain_slice(self, drain: bool) -> bool:
        """One bounded slice of this exhausted tenant's drain, so other
        tenants keep ticking while it waits out its teacher; settles the
        tenant once done (at once when ``drain`` is off).  The cumulative
        cap keeps a broken always-in-flight teacher from pinning the
        scheduler forever (same bound a solo run's drain has).  Returns
        True while it still wants scheduling."""
        if drain:
            self._drain_ticks += self.DRAIN_TICKS_PER_SLICE
            if self._drain_ticks <= stream.MAX_DRAIN_TICKS and self.session.drain_replies(
                max_ticks=self.DRAIN_TICKS_PER_SLICE,
                idle_sleep_s=self.DRAIN_IDLE_SLEEP_S,
            ):
                return True
        self._finish()
        return False

    @property
    def it(self):
        it = getattr(self, "_it", None)
        if it is None:
            it = self._it = iter(self.tenant.ticks)
        return it

    def pull(self):
        """This tenant's next tick from its source (None when exhausted),
        recorded as a ``mux.source`` span when telemetry is on."""
        tel = _telemetry.TELEMETRY
        if tel is None:
            return next(self.it, None)
        tok = tel.tracer.begin("mux.source")
        try:
            return next(self.it, None)
        finally:
            tel.tracer.end(tok, tenant=self.tenant.name)

    def maybe_snapshot(self, force: bool = False) -> bool:
        """Cadence snapshot: capture now, write on the manager's background
        thread (atomic publish — a crash mid-write falls back to the
        previous good step)."""
        if self.manager is None or self.result is not None:
            return False
        due = (
            self.snapshot_every > 0
            and self.session.t - self._last_snap_t >= self.snapshot_every
        )
        if not (due or force) or not self.session.started():
            return False
        # A fused member's session.state is stale while the cohort holds
        # the authoritative stacked rows — write them back first.
        self.unit.cohort.refresh(self.session)
        self.manager.save_async(self.session.t, self.session.snapshot())
        self._last_snap_t = self.session.t
        self.snapshots_taken += 1
        return True

    def _finish(self) -> None:
        # Any draining already happened incrementally in drain_slice().
        if self.manager is not None:
            self.manager.wait()  # never finish with a snapshot mid-write
        state, outs, stats = self.session.finish(drain=False)
        self.result = TenantResult(
            name=self.tenant.name, state=state, outputs=outs, stats=stats
        )


class _Unit:
    """Scheduler-side unit: the slots that tick together in one group of
    the tick engine (``engine/cohort.py``) — one slot, or a fused cohort.

    ``s`` — the DRR tick cost — is the members' shared width, so each
    fused member receives exactly the credit/debit schedule it would in a
    unit of its own (cohorts only form between same-width tenants); the
    group's tick just does all members' device work at once.
    """

    def __init__(self, slot: _Slot):
        self.slots = [slot]
        slot.unit = self
        self.cohort = slot.session.group()
        self.s = slot.s
        self.deficit = 0.0
        self.last_ticks = 0  # real ticks advanced in the last step() call

    @property
    def draining(self) -> bool:
        # Only a unit of one holds an exhausted tenant.
        return self.slots[0].draining

    def fuse_key(self):
        """``(cfg, mode, donate, S)`` of a unit that can fuse, else None."""
        if self.draining:
            return None
        sess = self.slots[0].session
        return (sess.cfg, sess.mode, sess._donate, self.s)

    def attach(self, slots: list[_Slot]) -> None:
        self.cohort.attach(*(slot.session for slot in slots))
        self.slots += slots
        for slot in slots:
            slot.unit = self

    def release(self, slot: _Slot) -> None:
        """Take one member out (live migration): it leaves the group for
        one of its own."""
        if len(self.slots) > 1:
            self.cohort.detach(slot.session)
        self.slots.remove(slot)
        slot.unit = None

    def step(self, drain: bool, n_ticks: int) -> list["_Unit"]:
        """Advance by up to ``n_ticks`` ticks of the group, or one drain
        slice once its tenant's source ran out.  Returns the units live
        after it: this one while it wants scheduling, and a unit of its own
        for each member whose source ran out (which gets its first drain
        slice in this same call, as this unit's own tenant does)."""
        self.last_ticks = 0
        units = [self]
        if not self.draining:
            for _ in range(n_ticks):
                left, advanced = self.cohort.tick([s.pull() for s in self.slots])
                self.last_ticks += advanced
                for sess in left:
                    slot = next(s for s in self.slots if s.session is sess)
                    slot.draining = True
                    if sess not in self.cohort.members:
                        self.slots.remove(slot)
                        units.append(_Unit(slot))
                if self.draining:
                    break
            for u in units:
                for slot in u.slots:
                    slot.maybe_snapshot()
        return [u for u in units if not u.draining or u.slots[0].drain_slice(drain)]


DEFAULT_QUANTUM = 8


class Multiplexer:
    """The scheduler: drives N tenant sessions round-robin (or DRR) with
    optional per-tenant durability (see module docstring).

    ``round()`` runs one scheduler round and returns True while any tenant
    is live — drive it manually to interleave control (live migration),
    or call ``run()`` to completion.
    """

    def __init__(
        self,
        tenants: list[Tenant],
        drain: bool = True,
        quantum: int = DEFAULT_QUANTUM,
        sched: str = "rr",
        snapshot_dir: Optional[str] = None,
        snapshot_every: int = 0,
        snapshot_full_every: int = 1,
        resume: bool = False,
        snapshots: Optional[dict] = None,
        pending: str = "auto",
        fuse: bool = True,
    ):
        if quantum < 1:
            raise ValueError(f"quantum must be >= 1, got {quantum}")
        if sched not in SCHEDULERS:
            raise ValueError(f"unknown scheduler {sched!r}; choose {SCHEDULERS}")
        if resume and snapshot_dir is None:
            raise ValueError(
                "resume=True needs snapshot_dir — without it every tenant "
                "would silently start from scratch"
            )
        names = [t.name for t in tenants]
        if len(set(names)) != len(names):
            raise ValueError(f"tenant names must be unique, got {names}")
        self.drain = drain
        self.quantum = quantum
        self.sched = sched
        self.fuse = fuse
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = snapshot_every
        # Cadence saves ship only changed leaves, with a full snapshot every
        # k-th save (runtime/checkpoint.py); 1 = every save full.
        self.snapshot_full_every = snapshot_full_every
        self._resume = resume
        self._pending = pending
        self.agg = MultiplexStats(n_tenants=len(tenants))
        self._slots: list[_Slot] = []
        self._live: list[_Unit] = []  # scheduling units, in round order
        self._t0: Optional[float] = None
        for t in tenants:
            self.admit(t, snapshot=(snapshots or {}).get(t.name))
        self.agg.n_tenants = len(self._slots)

    # -- tenant management -------------------------------------------------

    def _manager_for(self, name: str) -> Optional[CheckpointManager]:
        if self.snapshot_dir is None:
            return None
        return CheckpointManager(
            os.path.join(self.snapshot_dir, name),
            full_every=self.snapshot_full_every,
        )

    def admit(self, tenant: Tenant, snapshot: Optional[dict] = None,
              positioned: bool = False) -> None:
        """Add a tenant — fresh, resumed from its snapshot directory, or
        restored from an explicit ``snapshot`` tree (live migration).
        ``positioned=True`` asserts that a non-seekable ``tenant.ticks`` is
        already at the snapshot's cursor (i.e. it is the iterator
        ``extract`` returned, not a fresh tick-0 source)."""
        if any(s.tenant.name == tenant.name for s in self._slots):
            raise ValueError(f"tenant name {tenant.name!r} already admitted")
        tel = _telemetry.TELEMETRY
        tok = tel.tracer.begin("migrate.admit") if tel is not None else None
        slot = _Slot(
            tenant,
            manager=self._manager_for(tenant.name),
            snapshot_every=self.snapshot_every,
            resume=self._resume,
            snapshot_tree=snapshot,
            pending=self._pending,
            positioned=positioned,
        )
        self._slots.append(slot)
        self._live.append(_Unit(slot))
        self.agg.n_tenants = len(self._slots)
        if tok is not None:
            tel.tracer.end(
                tok, tenant=tenant.name, restored=snapshot is not None
            )
            tel.registry.count("odl_mux_admits")

    def _slot(self, name: str) -> _Slot:
        for s in self._slots:
            if s.tenant.name == name:
                return s
        raise KeyError(f"no tenant named {name!r}")

    def session(self, name: str) -> stream.StreamSession:
        return self._slot(name).session

    def finished(self, name: str) -> bool:
        return self._slot(name).result is not None

    def live_tenants(self) -> list[str]:
        """Names of tenants still being scheduled (admission order)."""
        return [s.tenant.name for s in self._slots if s.result is None]

    def finished_results(self) -> dict[str, TenantResult]:
        """Per-tenant results of every *finished* tenant — unlike
        ``results()``, callable while others are still live (the worker
        serves long-lived fleets that never fully drain)."""
        return {
            s.tenant.name: s.result for s in self._slots if s.result is not None
        }

    def load_report(self) -> list[dict]:
        """Per-live-tenant load signals for the elastic router: tick cursor,
        tick-rate EMA, ring occupancy (current/high-water/capacity), the
        compiled-shape key placement packs by, and whether the tenant is
        currently riding a fused cohort.  Accurate while fused — everything
        reported here is per-tenant host state, which cohort ticking keeps
        current."""
        out = []
        for slot in self._slots:
            if slot.result is not None:
                continue
            sess = slot.session
            stats = sess.stats
            out.append({
                "name": slot.tenant.name,
                "t": sess.t,
                "s": slot.s,
                "shape_key": shape_key(sess.cfg, sess.mode, sess._donate, slot.s),
                "tick_rate_ema": stats.tick_rate_ema,
                "ring": len(sess.ring),
                "ring_hwm": stats.ring_occupancy_hwm,
                "ring_capacity": sess.ring.capacity,
                "queries_issued": stats.queries_issued,
                "labels_applied": stats.labels_applied,
                "draining": slot.draining,
                "fused": len(slot.unit.slots) > 1,
            })
        return out

    def sync_telemetry(self) -> None:
        """Mirror every tenant's ``StreamStats`` (live sessions and
        finished results alike) into the enabled registry — the pull half
        of the one-source-of-truth design.  Called by live scrapes
        (``runtime/worker.py`` ``metrics``) and end-of-run reports; no-op
        when telemetry is disabled, never on the per-tick path."""
        tel = _telemetry.TELEMETRY
        if tel is None:
            return
        for slot in self._slots:
            if slot.result is not None:
                _telemetry.sync_stream_stats(
                    tel.registry, slot.result.stats, pending=0,
                    tenant=slot.tenant.name,
                )
            else:
                slot.session.sync_telemetry()
        tel.registry.gauge("odl_mux_tenants", len(self._slots))

    def extract(self, name: str, quiesce_ticks: int = 4096):
        """Live-migrate a tenant out: snapshot the session and remove it
        from this scheduler.

        When the teacher cannot snapshot its own state, the session first
        quiesces (bounded drain of in-flight replies, salvaging answers
        that would die with the connection — still-unanswered tickets stay
        in the ring, travel in the snapshot, and are re-asked on restore).
        A snapshot-capable teacher (``snapshot_state``) skips the quiesce:
        its undelivered inbox rides the snapshot verbatim, so the restored
        run replays every reply at its original due tick.  Draining early
        would apply labels *before* the plans they interleave with in the
        uninterrupted run — those plans then see a different ``elm`` and
        can flip query decisions, breaking bit-for-bit migration.

        Returns ``(snapshot_tree, ticks)``: the serialized session and the
        tenant's *partially-consumed* tick iterator (positioned at the next
        unread tick — for a seekable source this is the source itself and
        ``admit`` re-seeks it; for a plain sequence/generator it is the
        live iterator, so migration never replays ticks).  Hand both to
        another multiplexer's ``admit`` (same process) or persist the tree
        through a ``CheckpointManager`` and reopen a seekable source at
        ``snapshot.ticks_consumed(tree)`` (another process).
        """
        tel = _telemetry.TELEMETRY
        tok = tel.tracer.begin("migrate.extract") if tel is not None else None
        slot = self._slot(name)
        if slot.result is not None:
            raise ValueError(f"tenant {name!r} already finished; nothing to migrate")
        # Leave the unit first (a fused member's stacked rows and pending
        # plan go back into its session), then the ordinary quiesce/snapshot
        # flow applies.
        unit = slot.unit
        unit.release(slot)
        if not unit.slots:
            self._live.remove(unit)
        snapshot_capable = (
            getattr(slot.session.teacher, "snapshot_state", None) is not None
        )
        if quiesce_ticks > 0 and not snapshot_capable:
            slot.session.quiesce(
                max_ticks=quiesce_ticks, idle_sleep_s=slot.DRAIN_IDLE_SLEEP_S
            )
        tree = slot.session.snapshot()
        if slot.manager is not None:
            slot.manager.wait()
            slot.manager.save(slot.session.t, tree)
        self._slots.remove(slot)
        self.agg.n_tenants = len(self._slots)
        if tok is not None:
            tel.tracer.end(tok, tenant=name, t=slot.session.t)
            tel.registry.count("odl_mux_extracts")
        return tree, slot.it

    # -- scheduling --------------------------------------------------------

    def round(self) -> bool:
        """One scheduler round over all live tenants.  Returns True while
        any tenant still wants scheduling."""
        try:
            return self._round()
        except BaseException:
            # Settle in-flight background snapshot writes before the crash
            # propagates: a supervised restart in this process must never
            # race an orphaned writer thread for the same step directory.
            for s in self._slots:
                if s.manager is not None:
                    with contextlib.suppress(Exception):
                        s.manager.wait()
            raise

    def _form_cohorts(self) -> None:
        """Pack live units by ``(cfg, mode, donate, S)`` into one unit per
        key.

        Runs at every round start, so tenants admitted mid-run (including
        live-migration snapshots restored with pending tickets) join a
        matching cohort at the next scheduling boundary.  Only units of
        one join, and a key has at most one unit of several (the host).
        The stream width S is part of the key: cohort members tick in
        lockstep, and fusing different widths would break the DRR
        scheduler's per-tenant fairness (each fused member must cost
        exactly what it would in a unit of its own)."""
        groups: dict = {}
        for u in self._live:
            key = u.fuse_key()
            if key is not None:
                groups.setdefault(key, []).append(u)
        for units in groups.values():
            if len(units) < 2:
                continue
            host = max(units, key=lambda u: len(u.slots))
            packed = len(host.slots) == 1
            joining = [u for u in units if u is not host]
            host.attach([u.slots[0] for u in joining])  # all units of one
            for u in joining:
                self._live.remove(u)
            tel = _telemetry.TELEMETRY
            if packed and tel is not None:
                tel.tracer.event(
                    "cohort.pack", members=len(host.slots), s=host.s,
                    tenants=",".join(s.tenant.name for s in host.slots),
                )
                tel.registry.count("odl_mux_cohorts_packed")

    def _round(self) -> bool:
        if self._t0 is None:
            self._t0 = time.perf_counter()
        if not self._live:
            return False
        tel = _telemetry.TELEMETRY
        if tel is None:
            return self._schedule(None, None)
        labels: dict = {}
        tok = tel.tracer.begin("mux.round")
        try:
            return self._schedule(tel, labels)
        finally:
            tel.tracer.end(tok, **labels)

    def _schedule(self, tel, labels: Optional[dict]) -> bool:
        """One round's scheduling (``_round``'s body); with telemetry on
        (``tel``) it meters the round and fills the ``mux.round`` span's
        ``labels``."""
        self.agg.rounds += 1
        if self.fuse:
            self._form_cohorts()
        units = list(self._live)  # pre-round units, for debit metering below
        if self.sched == "drr":
            # Credit is sized by the smallest *ticking* tenant: a tenant
            # that is only draining costs no device time and must not gate
            # everyone else's budget (a small drained tenant stuck waiting
            # out a slow teacher would otherwise collapse live tenants to
            # ~1 tick per S_big/S_small rounds).  A unit's cost is its
            # (shared) member width, so each fused member sees the same
            # credit/debit schedule as in a unit of its own.
            ticking = [u.s for u in self._live if not u.draining]
            credit = self.quantum * min(ticking) if ticking else 0
            nxt = []
            for u in self._live:
                u.deficit += credit
                n = int(u.deficit // u.s)
                stepped = u.step(self.drain, n)
                u.deficit -= u.last_ticks * u.s
                if u.draining:
                    u.deficit = 0.0  # drained slices don't consume credit
                nxt.extend(stepped)
            self._live = nxt
        else:
            nxt = []
            for u in self._live:
                nxt.extend(u.step(self.drain, self.quantum))
            self._live = nxt
        if tel is not None:
            # Scheduler-level meters: rounds, and the stream-step debits
            # this round actually charged (ticks × per-unit cost S — the
            # DRR deficit currency; for rr the same product measures the
            # round's device work).
            tel.registry.count("odl_mux_rounds")
            tel.registry.count(
                "odl_mux_quantum_debits",
                sum(u.last_ticks * u.s for u in units),
            )
            tel.registry.gauge("odl_mux_live_units", len(self._live))
            labels.update(units=len(units),
                          ticks=sum(u.last_ticks for u in units))
        return bool(self._live)

    def run(self) -> tuple[dict[str, TenantResult], MultiplexStats]:
        while self.round():
            pass
        return self.results()

    def results(self) -> tuple[dict[str, TenantResult], MultiplexStats]:
        """Finalize and collect per-tenant results + aggregate stats."""
        if self._live:
            raise RuntimeError("results() with tenants still live; drive round()")
        if self._t0 is not None:
            self.agg.wall_s = time.perf_counter() - self._t0
        self.agg.stream_steps = sum(s.result.stats.stream_steps for s in self._slots)
        self.agg.ticks = sum(s.result.stats.ticks for s in self._slots)
        # Snapshots *taken* this run (keep-k GC prunes the directories, so
        # counting surviving step dirs would undercount).
        self.agg.snapshots = sum(s.snapshots_taken for s in self._slots)
        return {s.tenant.name: s.result for s in self._slots}, self.agg


def run(
    tenants: list[Tenant],
    drain: bool = True,
    quantum: int = DEFAULT_QUANTUM,
    sched: str = "rr",
    snapshot_dir: Optional[str] = None,
    snapshot_every: int = 0,
    resume: bool = False,
    fuse: bool = True,
) -> tuple[dict[str, TenantResult], MultiplexStats]:
    """Multiplex every tenant's stream over this process to completion.

    ``quantum`` is the scheduler time slice: how many consecutive ticks one
    tenant runs before the scheduler moves on.  Switching tenants every
    tick (quantum=1) evicts the previous tenant's state (P alone is
    S·N²·4 bytes) from cache on every switch and costs ~15-45% aggregate
    throughput at S=512; a few ticks per slice amortize that while keeping
    per-tenant scheduling delay bounded.  ``sched="drr"`` measures the
    slice in stream-steps instead of ticks so small tenants are not
    starved by huge ones (see module docstring).  The per-tenant result is
    bit-for-bit identical for every quantum and scheduler — only
    wall-clock interleaving changes.

    ``fuse`` (default True) packs tenants with the same ``(cfg, mode,
    donate)`` and stream width into *cohorts* advanced by one stacked
    dispatch per tick instead of one per tenant (``engine/cohort.py``);
    ``fuse=False`` ticks every tenant in a unit of its own.  Per-tenant
    results are bit-for-bit identical either way (and to a solo run); only
    device dispatch count and wall-clock interleaving change.

    ``snapshot_dir`` + ``snapshot_every`` enable per-tenant durability;
    ``resume=True`` restores tenants from their latest published snapshot
    (tick sources must then be seekable — ``snapshot.ResumableTicks``).

    Returns ``(results, agg)``: ``results[name]`` is that tenant's
    ``(state, outputs, stats)`` — identical to what a solo ``stream.run``
    over the same inputs returns — and ``agg`` is the aggregate
    ``MultiplexStats`` (true wall time, total steps).
    """
    if not tenants:
        raise ValueError("multiplex.run needs at least one tenant")
    return Multiplexer(
        tenants,
        drain=drain,
        quantum=quantum,
        sched=sched,
        snapshot_dir=snapshot_dir,
        snapshot_every=snapshot_every,
        resume=resume,
        fuse=fuse,
    ).run()


def run_supervised(
    make_tenants,
    snapshot_dir: str,
    snapshot_every: int = 1000,
    max_restarts: int = 3,
    **kw,
):
    """Crash-restart supervision around a durable multiplexed run.

    ``make_tenants()`` must build a *fresh* tenant list (fresh teacher
    instances, seekable tick sources) on every attempt — the previous
    attempt's objects died with it.  Each attempt resumes every tenant
    from its latest published snapshot under ``snapshot_dir`` (or from
    scratch when none exists yet); ``runtime.fault.run_with_restarts``
    bounds the retry loop.
    """

    class _DirView:
        """Adapter: the per-tenant snapshot directory tree viewed as one
        checkpointed unit for the supervisor (restore is a no-op — each
        tenant restores itself from its own subdirectory)."""

        def latest_step(self):
            steps = [
                s
                for name in (
                    os.listdir(snapshot_dir) if os.path.isdir(snapshot_dir) else []
                )
                if os.path.isdir(os.path.join(snapshot_dir, name))
                for s in [CheckpointManager(os.path.join(snapshot_dir, name)).latest_step()]
                if s is not None
            ]
            return max(steps) if steps else None

        def restore(self):
            return self.latest_step(), None

    def run_attempt(state, start_step):
        del state, start_step  # per-tenant restore happens inside run()
        return run(
            make_tenants(),
            snapshot_dir=snapshot_dir,
            snapshot_every=snapshot_every,
            resume=True,
            **kw,
        )

    return fault.run_with_restarts(
        lambda: None, run_attempt, _DirView(), max_restarts=max_restarts
    )


# The multiplexer's compiled-executable sharing is observable here: tenant
# configs that hash equal hit the same LRU entries.
cache_stats = stream.cache_stats
