"""Streaming async-teacher runtime: Algorithm 1 from a tick iterator.

``run_fleet`` needs the whole stream materialized as one ``(T, S, n_in)``
array with same-tick labels — fine for offline repro, wrong for the paper's
actual deployment story, where each tick arrives once and teacher answers
come back with real latency.  This module is the runtime for that case::

    ticks ──▶ plan (device) ──▶ queried feats ──▶ Teacher.ask ──╮
      ▲                                                         │ latency,
      │  host ingests tick t+1 while the device runs tick t     │ jitter,
      ╰─ learn (device) ◀── PendingRing ◀──── Teacher.poll ◀────╯ loss

Pieces:

* ``Teacher`` protocol — ``ask(feats, mask, tick) -> ticket`` and
  ``poll(tick) -> [TeacherReply]`` (plus ``in_flight()`` so the runtime
  knows when draining is pointless).  ``LatencyTeacher`` implements it with
  a tick-granular latency / jitter / loss / partial-answer /
  permanent-outage model; ``array_labels`` adapts a materialized label
  array (the paper's protocol, where ground truth plays the teacher);
  ``engine.rpc.RpcTeacher`` implements the same protocol over a real TCP
  socket with wall-clock timeout → loss mapping.
* ``PendingRing`` — fixed-capacity buffer of in-flight tickets holding the
  plan-time features (``h``), prediction, and confidence until the answer
  arrives.  What happens when it saturates is a pluggable *backpressure
  policy* (``BACKPRESSURE_POLICIES``):

  - ``drop_oldest`` (default) — evict the oldest in-flight ticket, metered;
    its late answer is counted as orphaned.
  - ``drop_newest`` — refuse the new ask; the tick's queries are dropped.
  - ``block``      — defer the ask to a later tick: the plan context waits
    in a bounded host-side queue and is submitted as ring slots free up
    (FIFO, so ask order is preserved).
  - ``coalesce``   — a stream that re-queries while it already has a query
    in flight is merged into that in-flight ticket (no duplicate teacher
    traffic; the in-flight answer settles the decision it belongs to);
    only the uncovered remainder is asked, evicting the oldest on
    overflow.

* ``StreamSession`` — one stream's (one *tenant's*) runtime as an
  explicit state machine: ``start(x0)`` dispatches the first plan,
  ``advance(next_tick)`` finishes the current tick (ask → poll → learn,
  fused with the next tick's plan), ``finish()`` drains and returns
  ``(state, outputs, stats)``.  The tick's device work runs in the one
  tick engine, ``engine.cohort.CohortSession``, where a session on its
  own is a group of one.  ``run`` drives a single session;
  ``engine.multiplex`` interleaves many sessions — with per-tenant
  configs, teachers, rings, and backpressure — over one process, sharing
  the bounded compiled-runner LRUs below.
* ``run`` — the double-buffered tick loop: the next tick is pulled from the
  iterator and shipped to the device while the current tick's ``plan``
  computes; answered labels apply out of order through the engine's masked
  ``learn``.  Per-tick wall latency and ask→answer label latency are
  recorded in ``StreamStats`` (p50/p95).

Query accounting reconciles exactly: every stream-query the plan decided
to issue ends in exactly one of ``labels_applied`` (answer applied),
``queries_dropped`` (backpressure victim), ``queries_lost`` (teacher loss,
outage, timeout, or partial-answer residue), or ``queries_coalesced``
(merged into an in-flight ticket; zero unless the policy is
``coalesce``) — ``StreamStats.reconciled`` states the identity.

With a zero-latency teacher the runtime reproduces ``run_fleet`` outputs
and final state bit-for-bit (locked by ``tests/test_stream.py``): ``plan``
and ``learn`` are the exact two halves of ``fleet_step``.

Sessions are durable: ``StreamSession.snapshot()`` serializes the whole
runtime state (engine pytree, ring + plan-time contexts, policy state,
stats, tick cursor, teacher state when supported) and ``restore`` resumes
it bit-for-bit — see ``engine/snapshot.py`` and ``tests/test_snapshot.py``.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import json
import time
from typing import Callable, Iterable, NamedTuple, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding
from repro.engine import fleet
from repro.engine.types import EngineConfig, EngineState, FleetStepOutput
from repro.runtime import telemetry as _telemetry

# Safety bound on drain polling — a broken Teacher that reports in-flight
# tickets forever must not hang the runtime (serve.py uses it too).
MAX_DRAIN_TICKS = 1_000_000

# Sleep between empty drain polls while replies are still in flight.
# Tick-granular teachers (LatencyTeacher) resolve by tick count, so this
# costs at most a few ms per drain; wall-clock teachers (RpcTeacher) need
# the drain to wait out real network latency without busy-spinning a core
# — and without burning through MAX_DRAIN_TICKS before the reply (or its
# timeout) can land.
DRAIN_IDLE_SLEEP_S = 200e-6

# Latency distributions keep a sliding window: long-running servers must
# not grow per-tick history without bound (same class of fix as the
# bounded PendingRing and runner LRUs).  p50/p95 reflect recent ticks.
STATS_WINDOW = 4096

# Smoothing of StreamStats.tick_rate_ema — a load signal, not an accounting
# counter, so responsiveness beats precision.
TICK_RATE_EMA_ALPHA = 0.1

# Pluggable pending-ring saturation policies (see module docstring).
BACKPRESSURE_POLICIES = ("drop_oldest", "drop_newest", "block", "coalesce")


class TeacherReply(NamedTuple):
    """One answered ticket.  ``answered`` may be a subset of the asked mask
    (a teacher can answer some streams of a ticket and lose others)."""

    ticket: int
    labels: np.ndarray  # (S,) int32 — valid where ``answered``
    answered: np.ndarray  # (S,) bool


class Teacher(Protocol):
    """Asynchronous label oracle with tick-granular time."""

    def ask(self, feats, mask: np.ndarray, tick: int) -> int:
        """Submit one query batch (feats (S, n_in), mask (S,) bool marks the
        streams actually querying).  ``tick`` is the tick the query is
        *about* — the current tick, except for asks the ``block`` policy
        deferred, which keep their origin tick.  Returns a ticket id."""
        ...

    def poll(self, tick: int) -> list[TeacherReply]:
        """Labels that have arrived by ``tick`` (possibly out of order)."""
        ...

    def in_flight(self) -> int:
        """Tickets asked but not yet answered nor lost."""
        ...


# (tick, feats) -> (S,) int32 labels.  ``feats`` may be a device array; only
# pull it to host if the labels actually depend on it.
LabelFn = Callable[[int, object], np.ndarray]


def array_labels(labels) -> LabelFn:
    """Adapt a materialized (T, S) label array to a ``LabelFn`` — the
    paper's evaluation protocol, where ground truth plays the teacher."""
    arr = np.asarray(labels)

    def fn(tick, feats):
        del feats
        return np.asarray(arr[tick], np.int32)

    return fn


@dataclasses.dataclass
class LatencyTeacher:
    """Teacher with a configurable latency / jitter / loss / outage model.

    Each ``ask`` becomes one in-flight ticket answered ``latency`` ticks
    later, plus a uniform per-ticket jitter in [0, jitter] — so with jitter
    > 0 answers arrive out of order.  A ``loss_prob`` fraction of tickets
    is silently lost (never answered), ``partial_prob`` drops each asked
    *stream* from its reply independently (a partially answered ticket —
    the residue is metered as ``queries_lost``), and ``outage_after >= t``
    kills every ticket asked at or after tick t — the paper's permanent-
    outage fault case ("queries will be retried later or skipped").
    """

    label_fn: LabelFn
    latency: int = 0
    jitter: int = 0
    loss_prob: float = 0.0
    partial_prob: float = 0.0
    outage_after: Optional[int] = None
    seed: int = 0

    def __post_init__(self):
        self._rng = np.random.default_rng(self.seed)
        self._next_ticket = 0
        # (due_tick, ticket, mask, labels) — labels are computed at ask time
        # so they reflect the tick the query was about.
        self._inbox: list[tuple[int, int, np.ndarray, np.ndarray]] = []

    def ask(self, feats, mask, tick):
        ticket = self._next_ticket
        self._next_ticket += 1
        lost = (
            self.outage_after is not None and tick >= self.outage_after
        ) or (self.loss_prob > 0.0 and self._rng.uniform() < self.loss_prob)
        if not lost:
            due = tick + self.latency
            if self.jitter:
                due += int(self._rng.integers(0, self.jitter + 1))
            answered = np.asarray(mask, bool)
            if self.partial_prob > 0.0:
                keep = self._rng.uniform(size=answered.shape) >= self.partial_prob
                answered = answered & keep
            labels = np.asarray(self.label_fn(tick, feats), np.int32)
            self._inbox.append((due, ticket, answered, labels))
        return ticket

    def poll(self, tick):
        ready = [e for e in self._inbox if e[0] <= tick]
        if not ready:
            return []
        self._inbox = [e for e in self._inbox if e[0] > tick]
        ready.sort(key=lambda e: (e[0], e[1]))
        return [TeacherReply(ticket=t, labels=lab, answered=m) for _, t, m, lab in ready]

    def in_flight(self):
        return len(self._inbox)

    # -- snapshot support (engine/snapshot.py) -----------------------------

    def snapshot_state(self) -> dict:
        """Full teacher state as a numpy/JSON tree: RNG, ticket counter, and
        the undelivered inbox — restoring it makes a resumed run answer
        bit-for-bit like the uninterrupted one.  ``label_fn`` is NOT
        serialized; the restoring process reconstructs the teacher with the
        same label source before calling ``restore_snapshot``."""
        meta = {
            "kind": "latency",
            "next_ticket": self._next_ticket,
            "rng": self._rng.bit_generator.state,  # JSON-able (arbitrary ints)
        }
        return {
            "meta": np.asarray(json.dumps(meta, default=int)),
            "inbox": [
                {
                    "due": np.asarray(due, np.int64),
                    "ticket": np.asarray(ticket, np.int64),
                    "answered": np.asarray(answered, bool),
                    "labels": np.asarray(labels, np.int32),
                }
                for due, ticket, answered, labels in self._inbox
            ],
        }

    def restore_snapshot(self, tree: dict) -> None:
        meta = json.loads(np.asarray(tree["meta"]).item())
        self._next_ticket = int(meta["next_ticket"])
        self._rng.bit_generator.state = meta["rng"]
        self._inbox = [
            (
                int(np.asarray(e["due"])),
                int(np.asarray(e["ticket"])),
                np.asarray(e["answered"], bool),
                np.asarray(e["labels"], np.int32),
            )
            for e in tree["inbox"]
        ]


class PendingTicket(NamedTuple):
    """What must survive the teacher round-trip: the plan-time features and
    controller context of one asked tick.  ``x`` (the raw tick features)
    rides along so a snapshot restored against a *fresh* teacher connection
    can re-ask the in-flight queries (engine/snapshot.py); the ring is
    bounded, so this holds at most ``capacity`` extra (S, n_in) buffers."""

    tick: int
    queried: np.ndarray  # (S,) bool host copy of the asked mask
    plan: fleet.PlanOutput  # device arrays captured at query time
    x: object  # the tick's raw features (whatever the iterator yielded)


class PlanSlice:
    """Lazy row-window view of a cohort's full-width ``fleet.PlanOutput``.

    A group of several (``engine/cohort.py``) plans all its members in one
    stacked dispatch; each member session's current plan and ring entries
    then hold a ``PlanSlice`` instead of a member-width ``PlanOutput``.
    Attribute access slices the full plan lazily (device-side), and
    ``_asdict`` matches the NamedTuple protocol, so a group of one's learn,
    snapshot (``snapshot._plan_to_tree``), and patch-learn paths treat it
    exactly like a ``PlanOutput``.  ``materialize()`` turns it into a real
    member-width ``PlanOutput`` (detaching from the cohort).
    """

    __slots__ = ("full", "lo", "hi")

    def __init__(self, full: fleet.PlanOutput, lo: int, hi: int):
        self.full = full
        self.lo = lo
        self.hi = hi

    def __getattr__(self, name):
        # Only reached for names not in __slots__ — i.e. PlanOutput fields.
        return getattr(self.full, name)[self.lo : self.hi]

    def _asdict(self):
        return {
            k: getattr(self.full, k)[self.lo : self.hi]
            for k in fleet.PlanOutput._fields
        }

    def materialize(self) -> fleet.PlanOutput:
        return fleet.PlanOutput(**self._asdict())


class DeferredAsk(NamedTuple):
    """A ``block``-policy ask waiting for a free ring slot."""

    tick: int
    x: object  # the tick's features (whatever the iterator yielded)
    queried: np.ndarray  # (S,) bool
    plan: fleet.PlanOutput


class PendingRing:
    """Fixed-capacity ordered map ticket -> entry.

    ``push`` evicts and returns the oldest entry when full (the runtime
    meters the drop); ``pop`` of an unknown/evicted ticket returns None.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"ring capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._slots: "collections.OrderedDict[int, object]" = collections.OrderedDict()

    def __len__(self):
        return len(self._slots)

    def full(self) -> bool:
        return len(self._slots) >= self.capacity

    def push(self, ticket: int, entry):
        dropped = None
        if len(self._slots) >= self.capacity:
            dropped = self._slots.popitem(last=False)[1]
        self._slots[ticket] = entry
        return dropped

    def pop(self, ticket: int):
        return self._slots.pop(ticket, None)

    def entries(self):
        """Live entries, oldest first (read-only view for coverage scans)."""
        return self._slots.values()

    def tickets(self):
        """Live ticket ids, oldest first (snapshot serialization)."""
        return self._slots.keys()

    def drain(self):
        """Remove and return all entries (oldest first)."""
        out = list(self._slots.values())
        self._slots.clear()
        return out


def _percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else 0.0


@dataclasses.dataclass
class StreamStats:
    """Counters + latency distributions of one ``run`` (or serving loop).

    Query accounting (stream-queries, i.e. mask sums): every query the plan
    decided to issue lands in exactly one terminal bucket, so
    ``queries_issued == labels_applied + queries_dropped + queries_lost +
    queries_coalesced`` always holds (``reconciled``).  With any policy but
    ``coalesce`` the last term is zero and the identity is the three-term
    one from ISSUE 3.
    """

    ticks: int = 0
    stream_steps: int = 0
    tickets_issued: int = 0  # teacher.ask calls actually made
    queries_issued: int = 0  # stream-queries the plan decided to issue
    labels_applied: int = 0  # stream-labels applied through ``learn``
    tickets_dropped: int = 0  # evicted / refused / expired by backpressure
    queries_dropped: int = 0
    replies_orphaned: int = 0  # answered after their ticket was evicted
    tickets_lost: int = 0  # never answered (teacher loss / outage / timeout)
    queries_lost: int = 0  # incl. the residue of partially answered tickets
    tickets_coalesced: int = 0  # asks merged (at least partly) into in-flight
    queries_coalesced: int = 0  # stream-queries settled by an in-flight ticket
    asks_deferred: int = 0  # ``block``: asks that waited for a ring slot
    tickets_reasked: int = 0  # in-flight tickets re-submitted after a restore
    # Learn dispatch (``learn_rows_index``): replies learned over a
    # compacted row set or at full width, and the rows of this session
    # those learns read and wrote, padding included — so ``learn_rows /
    # labels_applied`` is the padding overhead.
    learns_compact: int = 0
    learns_full: int = 0
    learn_rows: int = 0
    wall_s: float = 0.0
    # Load signals for the elastic router (runtime/elastic.py): a wall-clock
    # EMA of the tick rate (ticks/s — NOT deterministic, excluded from
    # parity comparisons) and the pending ring's high-water occupancy (a
    # teacher that can't keep up shows here before queries start dropping).
    # Both travel in snapshots (engine/snapshot.py meta "stats") so a
    # migrated tenant keeps its wall-clock history — while the process-local
    # telemetry trace ring (runtime/telemetry.py) intentionally does NOT:
    # spans recorded on the source worker stay on the source, and parity
    # tests exclude both the EMA and the tracer accordingly
    # (tests/test_telemetry.py locks these restore semantics).
    tick_rate_ema: float = 0.0
    ring_occupancy_hwm: int = 0
    tick_ms: "collections.deque" = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=STATS_WINDOW)
    )
    label_latency_ticks: "collections.deque" = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=STATS_WINDOW)
    )

    @property
    def tick_p50_ms(self) -> float:
        return _percentile(self.tick_ms, 50)

    @property
    def tick_p95_ms(self) -> float:
        return _percentile(self.tick_ms, 95)

    @property
    def label_latency_p50(self) -> float:
        return _percentile(self.label_latency_ticks, 50)

    @property
    def label_latency_p95(self) -> float:
        return _percentile(self.label_latency_ticks, 95)

    @property
    def steps_per_s(self) -> float:
        return self.stream_steps / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def reconciled(self) -> bool:
        """The query-accounting identity (see class docstring)."""
        return self.queries_issued == (
            self.labels_applied
            + self.queries_dropped
            + self.queries_lost
            + self.queries_coalesced
        )

    def count_learn(self, rows: int, compact: bool) -> None:
        """Meter one reply's learn: ``rows`` of this session's rows read and
        written, over a compacted row set or at full width."""
        if compact:
            self.learns_compact += 1
        else:
            self.learns_full += 1
        self.learn_rows += rows

    def summary(self) -> dict:
        return {
            "ticks": self.ticks,
            "stream_steps": self.stream_steps,
            "steps_per_s": self.steps_per_s,
            "tickets_issued": self.tickets_issued,
            "queries_issued": self.queries_issued,
            "labels_applied": self.labels_applied,
            "tickets_dropped": self.tickets_dropped,
            "queries_dropped": self.queries_dropped,
            "replies_orphaned": self.replies_orphaned,
            "tickets_lost": self.tickets_lost,
            "queries_lost": self.queries_lost,
            "tickets_coalesced": self.tickets_coalesced,
            "queries_coalesced": self.queries_coalesced,
            "asks_deferred": self.asks_deferred,
            "tickets_reasked": self.tickets_reasked,
            "learns_compact": self.learns_compact,
            "learns_full": self.learns_full,
            "learn_rows": self.learn_rows,
            "queries_reconciled": self.reconciled,
            "tick_rate_ema": self.tick_rate_ema,
            "ring_occupancy_hwm": self.ring_occupancy_hwm,
            "tick_p50_ms": self.tick_p50_ms,
            "tick_p95_ms": self.tick_p95_ms,
            "label_latency_p50": self.label_latency_p50,
            "label_latency_p95": self.label_latency_p95,
            "caches": cache_stats(),
        }


# The per-tick runners take state leaves positionally and return only the
# leaves their half actually writes; the host reassembles the pytree with
# ``_replace`` (zero-copy).  Returning the full EngineState would make XLA
# materialize a fresh copy of every pass-through leaf each tick — P alone
# is S·N²·4 bytes, which at S=1024 dwarfs the tick's real compute.
#
# The lru_caches are keyed on (cfg, mode, donate), so *tenants* of the
# multiplexer (engine/multiplex.py) that share a config share the same
# compiled executable — the whole point of multiplexing fleets over one
# process instead of one process per fleet.

@functools.lru_cache(maxsize=fleet.RUNNER_CACHE_SIZE)
def _plan_runner(cfg: EngineConfig, mode: str, donate: bool):
    def run_plan(elm, prune, drift, meter, x):
        state = EngineState(elm=elm, prune=prune, drift=drift, meter=meter)
        new_state, p = fleet.plan(state, x, cfg, mode=mode)
        return (new_state.prune, new_state.drift, new_state.meter), p

    # elm passes through plan untouched (and stays live on the host side),
    # so only the replaced controller leaves are donation candidates.
    return jax.jit(run_plan, donate_argnums=(1, 2, 3) if donate else ())


# The learn runners take one more argument, ``rows``: a reply's
# ``learn_rows_index`` (``fleet.learn_rows`` then reads and writes those K
# rows of P and beta in place of all S), or nothing, which is the
# full-width executable as it always was.


@functools.lru_cache(maxsize=fleet.RUNNER_CACHE_SIZE)
def _learn_runner(cfg: EngineConfig, donate: bool):
    def run_learn(elm, prune, drift, meter, h, labels, pred, conf, mask, controller_on,
                  theta, rows=None):
        state = EngineState(elm=elm, prune=prune, drift=drift, meter=meter)
        new_state = fleet.learn_rows(
            state, rows, h, labels, pred, conf, mask, controller_on, cfg, theta=theta
        )
        return new_state.elm, new_state.prune

    return jax.jit(run_learn, donate_argnums=(0, 1) if donate else ())


@functools.lru_cache(maxsize=fleet.RUNNER_CACHE_SIZE)
def _learn_plan_runner(cfg: EngineConfig, mode: str, donate: bool):
    """Steady-state fused tick: apply one reply's labels, then plan the next
    tick, in a single dispatch.  Halves per-tick dispatch overhead and lets
    XLA fuse across the learn→plan boundary — the same fusion ``run_fleet``
    gets inside its scan — so the zero-latency stream keeps pace with it.
    """

    def run_learn_plan(
        elm, prune, drift, meter, h, labels, pred, conf, mask, controller_on, theta,
        x_next, rows=None
    ):
        state = EngineState(elm=elm, prune=prune, drift=drift, meter=meter)
        state = fleet.learn_rows(
            state, rows, h, labels, pred, conf, mask, controller_on, cfg, theta=theta
        )
        new_state, p = fleet.plan(state, x_next, cfg, mode=mode)
        return (new_state.elm, new_state.prune, new_state.drift, new_state.meter), p

    return jax.jit(run_learn_plan, donate_argnums=(0, 1, 2, 3) if donate else ())


@functools.lru_cache(maxsize=fleet.RUNNER_CACHE_SIZE)
def _plan_avail_runner(cfg: EngineConfig, mode: str, donate: bool):
    """``_plan_runner`` with an explicit ``teacher_available`` vector.

    Used by sessions carrying dead padding rows (``live < S`` in a sharded
    session's tail shard): padded rows plan with ``avail=False`` so they
    never query, never learn, and never touch the teacher — while every
    shard's dispatch keeps the same (padded) width and therefore shares one
    compiled executable."""

    def run_plan(elm, prune, drift, meter, x, avail):
        state = EngineState(elm=elm, prune=prune, drift=drift, meter=meter)
        new_state, p = fleet.plan(state, x, cfg, mode=mode, teacher_available=avail)
        return (new_state.prune, new_state.drift, new_state.meter), p

    return jax.jit(run_plan, donate_argnums=(1, 2, 3) if donate else ())


@functools.lru_cache(maxsize=fleet.RUNNER_CACHE_SIZE)
def _learn_plan_avail_runner(cfg: EngineConfig, mode: str, donate: bool):
    """``_learn_plan_runner`` with an explicit ``teacher_available`` vector
    for the planned next tick (see ``_plan_avail_runner``)."""

    def run_learn_plan(
        elm, prune, drift, meter, h, labels, pred, conf, mask, controller_on, theta,
        x_next, avail, rows=None
    ):
        state = EngineState(elm=elm, prune=prune, drift=drift, meter=meter)
        state = fleet.learn_rows(
            state, rows, h, labels, pred, conf, mask, controller_on, cfg, theta=theta
        )
        new_state, p = fleet.plan(
            state, x_next, cfg, mode=mode, teacher_available=avail
        )
        return (new_state.elm, new_state.prune, new_state.drift, new_state.meter), p

    return jax.jit(run_learn_plan, donate_argnums=(0, 1, 2, 3) if donate else ())


# Row capacities of a compacted learn: powers of two from this floor up to
# half the width.  A width below twice the floor (a backbone's decode
# batch) has none, and every learn there runs at full width.
LEARN_ROWS_FLOOR = 256


def learn_capacities(s: int) -> tuple:
    """The row capacities K of a compacted learn at width ``s``."""
    ks, k = [], LEARN_ROWS_FLOOR
    while 2 * k <= s:
        ks.append(k)
        k *= 2
    return tuple(ks)


def learn_rows_index(mask: np.ndarray) -> Optional[np.ndarray]:
    """The rows one reply's learn runs over, for its host-side apply mask.

    The smallest capacity K of ``learn_capacities`` that holds the ``n``
    answered rows, and the sorted, unique int32 index of length K of those
    rows padded with the first K - n rows outside the mask (which learn as
    the identity and are written back as they were).  None — learn at full
    width — when no capacity holds them: more than half the rows answered,
    or a width below twice the floor.
    """
    n = int(mask.sum())
    k = next((k for k in learn_capacities(mask.shape[0]) if k >= n), None)
    if k is None:
        return None
    sel = np.array(mask, bool)
    sel[np.flatnonzero(~sel)[: k - n]] = True
    return np.flatnonzero(sel).astype(np.int32)


def _spec(a) -> jax.ShapeDtypeStruct:
    """A runner argument's abstract value as jit keys its executables: the
    shape, the dtype, and the sharding of an array committed to a device."""
    sharding = a.sharding if getattr(a, "committed", False) else None
    return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sharding)


@functools.lru_cache(maxsize=fleet.RUNNER_CACHE_SIZE)
def _compile_ladder(fn, specs: tuple, s: int) -> None:
    """Compile the learn runner ``fn`` at every capacity of width ``s``
    (``learn_capacities``) for arguments ``specs``: jit then finds each
    executable when a reply first needs it, and no reply compiles."""
    for k in learn_capacities(s):
        fn.lower(*specs, rows=jax.ShapeDtypeStruct((k,), jnp.int32)).compile()


def learn_call(fn, args: tuple, rows: Optional[np.ndarray], s: int,
               laddered: set, ship: Callable):
    """One learn dispatch of a session of width ``s``: ``fn(*args)`` at
    full width when ``rows`` is None, else over the rows of the host index
    ``rows`` (``learn_rows_index``).  A session's first compacted call of
    ``fn`` at a width compiles the width's capacities (``_compile_ladder``;
    ``laddered`` records it), so that a capacity first met later in the
    run compiles nothing."""
    if rows is None:
        return fn(*args)
    if (fn, s) not in laddered:
        _compile_ladder(fn, jax.tree.map(_spec, args), s)
        laddered.add((fn, s))
    return fn(*args, rows=ship(rows))


def cache_stats() -> dict:
    """Hit/miss counters for every compiled-runner cache in the engine."""
    out = dict(fleet.runner_cache_info())
    for name, fn in (
        ("plan_runner", _plan_runner),
        ("learn_runner", _learn_runner),
        ("learn_plan_runner", _learn_plan_runner),
        ("plan_avail_runner", _plan_avail_runner),
        ("learn_plan_avail_runner", _learn_plan_avail_runner),
        ("compile_ladder", _compile_ladder),
    ):
        info = fn.cache_info()
        out[name] = {
            "hits": info.hits,
            "misses": info.misses,
            "size": info.currsize,
            "maxsize": info.maxsize,
        }
    return out


def _default_ship():
    # Off-CPU, ship the next tick to the device eagerly so the transfer
    # overlaps the in-flight dispatch; on CPU the eager path is pure Python
    # overhead (~0.5 ms/call) and pjit's native conversion is far cheaper.
    return (lambda a: a) if jax.default_backend() == "cpu" else jax.device_put


class _NoTracer:
    """Records nothing: a tick's phase spans go here when telemetry is off."""

    @staticmethod
    def begin(name: str) -> None:
        return None

    @staticmethod
    def end(token, **labels) -> None:
        return None


NO_TRACER = _NoTracer()


class StreamSession:
    """One stream's (one tenant's) async-teacher runtime as a state machine.

    Lifecycle::

        sess = StreamSession(state, cfg, teacher, ...)
        sess.start(x0)          # dispatch the first tick's plan
        sess.advance(x1)        # finish tick 0 (ask/poll/learn), plan tick 1
        ...
        sess.advance(None)      # finish the last tick (no next plan)
        state, outs, stats = sess.finish()   # drain + accounting + outputs

    The session holds the per-tenant half: its ring, teacher, backpressure
    policy, stats, output collection and tick cursor.  The device work of a
    tick runs in the tick engine, ``engine.cohort.CohortSession``:
    ``start`` / ``advance`` / ``finish`` drive the session's own group of
    one, and the multiplexer (``engine.multiplex``) may instead tick it in
    a group of several same-shaped tenants.  The per-tenant op sequence is
    the same either way, so a multiplexed tenant reproduces its solo
    ``run`` bit-for-bit.

    ``backpressure`` picks the ring-saturation policy (see module
    docstring / ``BACKPRESSURE_POLICIES``).
    """

    def __init__(
        self,
        state: EngineState,
        cfg: EngineConfig,
        teacher: Teacher,
        mode: str = "algo1",
        capacity: int = 64,
        backpressure: str = "drop_oldest",
        collect: bool = True,
        donate: Optional[bool] = None,
        stats: Optional[StreamStats] = None,
        ship: Optional[Callable] = None,
        live: Optional[int] = None,
    ):
        if backpressure not in BACKPRESSURE_POLICIES:
            raise ValueError(
                f"unknown backpressure policy {backpressure!r}; "
                f"choose one of {BACKPRESSURE_POLICIES}"
            )
        if donate is None:
            donate = True
        if donate:
            # Own the buffers we are about to donate tick after tick; the
            # caller's state must survive the run.
            state = jax.tree.map(jnp.copy, state)
        self._donate = donate
        self.state = state
        self.cfg = cfg
        self.teacher = teacher
        self.mode = mode
        self.backpressure = backpressure
        self.collect = collect
        self.stats = stats if stats is not None else StreamStats()
        self.ring = PendingRing(capacity)
        self.ship = ship if ship is not None else _default_ship()
        # ``live``: only the first ``live`` rows are real streams — the tail
        # is dead padding (a sharded session's S-rounding, see
        # ShardedStreamSession).  Dead rows plan with teacher_available=False
        # (never query/learn) and are excluded from ``stream_steps``.
        self.width = int(jax.tree.leaves(state)[0].shape[0])
        self.live = None if live is not None and live >= self.width else live
        self._cohort = None  # the group this session ticks in (engine/cohort.py)
        # ``block``: asks waiting for a ring slot (bounded like the ring;
        # overflow drops the oldest deferred ask, metered).
        self._deferred: "collections.deque[DeferredAsk]" = collections.deque()
        self._cols: dict[str, list] = {
            k: []
            for k in ("pred", "outputs", "queried", "theta", "confidence",
                      "mode_training")
        }
        self._trained_rows: list[np.ndarray] = []
        self._x = None  # current tick's features (plan dispatched, not asked)
        self._p = None  # current tick's PlanOutput
        self.t = 0
        self._t_start: Optional[float] = None
        self._finished = False
        # Telemetry label set for this session's registry series / spans
        # ({tenant, worker, shard, ...}); owners (multiplexer, sharded
        # session, worker) fill it in.  Purely observational — never read
        # on the compute path.
        self.telemetry_labels: dict = {}

    # -- lifecycle ---------------------------------------------------------

    def started(self) -> bool:
        return self._t_start is not None

    def group(self):
        """The session's own group of one (``engine.cohort``), made on
        first use; raises while the session ticks in a group of several."""
        from repro.engine import cohort

        if self._cohort is None:
            self._cohort = cohort.CohortSession([self])
        elif len(self._cohort.members) > 1:
            raise RuntimeError("a fused member ticks and drains with its cohort")
        return self._cohort

    def start(self, x0) -> None:
        """Dispatch the first tick's plan (nothing pending yet)."""
        assert not self.started(), "session already started"
        self.group().tick([x0])

    def advance(self, nxt) -> None:
        """Finish the current tick (ask → poll → learn) and plan ``nxt``.

        ``nxt`` is the next tick's features (shipped here) or None when the
        iterator is exhausted — the learn of any same-tick replies then runs
        unfused.  Mirrors one iteration of the double-buffered ``run`` loop.
        """
        assert self._p is not None, "advance() before start()"
        self.group().tick([nxt])

    def drain_replies(
        self,
        max_ticks: int = MAX_DRAIN_TICKS,
        idle_sleep_s: float = DRAIN_IDLE_SLEEP_S,
    ) -> bool:
        """Wait out in-flight replies after the tick source is exhausted.

        Polls while *either* the ring still holds tickets *or* the teacher
        still has replies in flight — a reply whose ticket was evicted must
        still be polled so ``replies_orphaned`` meters it (polling only
        while both held silently discarded those).  Deferred (``block``)
        asks keep flushing as slots free up.  Stops as soon as nothing more
        can ever arrive.

        Returns True when the ``max_ticks`` budget ran out with work
        possibly still in flight (the caller may resume — the multiplexer
        drains one bounded slice per scheduler round), False when the
        drain is complete.
        """
        drained = 0
        while len(self.ring) or self._deferred or self.teacher.in_flight() > 0:
            if drained >= max_ticks:
                return True
            replies = self._poll_and_apply()
            self._flush_deferred()
            self.t += 1
            drained += 1
            if self.teacher.in_flight() == 0 and not replies:
                # A threaded teacher (RpcTeacher) may resolve a ticket
                # *between* the poll above and the in_flight check — the
                # reply is already pollable even though in-flight just hit
                # zero.  Poll once more before concluding nothing can ever
                # arrive; only then are ring leftovers lost for good.
                if not self._poll_and_apply():
                    break
            elif not replies and idle_sleep_s > 0:
                time.sleep(idle_sleep_s)
        return False

    def quiesce(
        self,
        max_ticks: int = 4096,
        idle_sleep_s: float = DRAIN_IDLE_SLEEP_S,
    ) -> bool:
        """Migration quiesce: wait out in-flight replies *without* advancing
        the session's tick clock.  ``drain_replies`` is for an exhausted
        tick source and lets ``t`` run on; a live migration happens
        mid-stream, where ``t`` must keep matching the tick source after
        the move.  Polls at a virtual time horizon, applies every answer
        that arrives (so it does not have to travel in the snapshot), then
        restores ``t``.  Returns True when the ring fully quiesced —
        anything left is either lost (lossy teacher) or must be re-asked
        by the restore (``engine.snapshot``)."""
        t0 = self.t
        try:
            self.drain_replies(max_ticks=max_ticks, idle_sleep_s=idle_sleep_s)
        finally:
            self.t = t0
        return not len(self.ring)

    def pending_queries(self) -> int:
        """Stream-queries issued but not yet settled: asked tickets still
        in the ring plus ``block``-deferred asks.  With it the accounting
        identity closes at *any* instant — ``queries_issued ==
        labels_applied + queries_dropped + queries_lost +
        queries_coalesced + pending_queries()`` — which is what makes a
        live mid-run scrape (runtime/worker.py ``metrics``) checkable."""
        n = sum(int(ent.queried.sum()) for ent in self.ring.entries())
        n += sum(int(d.queried.sum()) for d in self._deferred)
        return n

    def sync_telemetry(self) -> None:
        """Mirror this session's ``StreamStats`` into the enabled registry
        (no-op when telemetry is disabled).  Called at ``finish()`` and by
        live scrapes; never on the per-tick path."""
        tel = _telemetry.TELEMETRY
        if tel is not None:
            _telemetry.sync_stream_stats(
                tel.registry, self.stats, pending=self.pending_queries(),
                **self.telemetry_labels
            )

    def _poll_and_apply(self) -> list[TeacherReply]:
        """A drain's poll: claim what arrived and learn it through the
        session's group of one."""
        replies = self.teacher.poll(self.t)
        claims = self._claims(replies)
        if claims:
            self.group().learn_replies(claims)
        return replies

    def finish(
        self, drain: bool = True
    ) -> tuple[EngineState, Optional[FleetStepOutput], StreamStats]:
        """Drain, settle terminal accounting, and build stacked outputs."""
        assert self._p is None, "finish() with a planned tick still pending"
        if self._finished:
            raise RuntimeError("session already finished")
        self._finished = True
        if drain:
            self.drain_replies()
        for ent in self.ring.drain():
            self.stats.tickets_lost += 1
            self.stats.queries_lost += int(ent.queried.sum())
        for d in self._deferred:
            # ``block`` asks that never got a slot: the queries never hit
            # the wire — backpressure dropped them.
            self.stats.tickets_dropped += 1
            self.stats.queries_dropped += int(d.queried.sum())
        self._deferred.clear()
        if self._t_start is not None:
            self.stats.wall_s += time.perf_counter() - self._t_start
        self.sync_telemetry()
        outs = None
        if self.collect and self._cols["pred"]:
            outs = FleetStepOutput(
                pred=np.stack(self._cols["pred"]),
                outputs=np.stack(self._cols["outputs"]),
                queried=np.stack(self._cols["queried"]),
                trained=np.stack(self._trained_rows),
                theta=np.stack(self._cols["theta"]),
                confidence=np.stack(self._cols["confidence"]),
                mode_training=np.stack(self._cols["mode_training"]),
            )
        return self.state, outs, self.stats

    # -- durability (engine/snapshot.py) -----------------------------------

    def snapshot(self) -> dict:
        """Full-fidelity serialization of this session: EngineState, ring
        contents with their plan-time context, backpressure-policy state
        (deferred asks; coalesce coverage is the ring masks), stats, the
        in-flight tick, the tick-source cursor, and — when the teacher
        supports it — the teacher's own state.  The returned tree is numpy
        leaves + one JSON meta leaf: hand it to
        ``runtime.checkpoint.CheckpointManager.save`` for atomic keep-k
        publication.  The session keeps running."""
        from repro.engine import snapshot as snapshot_mod

        return snapshot_mod.capture(self)

    @classmethod
    def restore(
        cls,
        tree: dict,
        teacher: Teacher,
        cfg=None,
        ship: Optional[Callable] = None,
        pending: str = "auto",
    ) -> "StreamSession":
        """Rebuild a session from ``snapshot()``'s tree (see
        ``engine.snapshot.restore`` for the pending-ticket policies).  The
        caller repositions the tick source at
        ``engine.snapshot.ticks_consumed(tree)`` and resumes driving
        ``advance``; under a deterministic snapshot-capable teacher the
        resumed run is bit-for-bit the uninterrupted one."""
        from repro.engine import snapshot as snapshot_mod

        return snapshot_mod.restore(tree, teacher, cfg=cfg, ship=ship, pending=pending)

    # -- internals ---------------------------------------------------------

    def _ask(self, x, queried: np.ndarray, p, t: int):
        """One actual teacher.ask + ring push (evicting oldest, metered)."""
        ticket = self.teacher.ask(x, queried, t)
        self.stats.tickets_issued += 1
        dropped = self.ring.push(ticket, PendingTicket(t, queried, p, x))
        self.stats.ring_occupancy_hwm = max(
            self.stats.ring_occupancy_hwm, len(self.ring)
        )
        if dropped is not None:
            self.stats.tickets_dropped += 1
            self.stats.queries_dropped += int(dropped.queried.sum())
            tel = _telemetry.TELEMETRY
            if tel is not None:
                tel.tracer.event(
                    "ring.evict", t=t, evicted_tick=dropped.tick,
                    queries=int(dropped.queried.sum()), **self.telemetry_labels
                )

    def _submit(self, x, queried: np.ndarray, p, t: int) -> None:
        """Route one tick's decided queries through the backpressure policy."""
        policy = self.backpressure
        if policy == "coalesce":
            # Streams already covered by an in-flight ticket are merged into
            # it: the in-flight answer settles the decision it belongs to,
            # and no duplicate query hits the wire.
            entries = list(self.ring.entries())  # oldest first
            cover = np.zeros_like(queried)
            for ent in entries:
                cover |= ent.queried
            rest = queried & ~cover
            if rest.any() and self.ring.full() and entries:
                # The residual ask below will evict the oldest in-flight
                # ticket, so its coverage can no longer settle anything:
                # streams only it covered must ride the new ticket, not be
                # credited as coalesced against a ticket that is about to
                # become an orphan.
                cover = np.zeros_like(queried)
                for ent in entries[1:]:
                    cover |= ent.queried
                rest = queried & ~cover
            merged = queried & cover
            n_m = int(merged.sum())
            if n_m:
                self.stats.tickets_coalesced += 1
                self.stats.queries_coalesced += n_m
            if rest.any():
                self._ask(x, rest, p, t)
            return
        if policy == "drop_newest" and self.ring.full():
            self.stats.tickets_dropped += 1
            self.stats.queries_dropped += int(queried.sum())
            return
        if policy == "block" and (self.ring.full() or self._deferred):
            # FIFO: never let a new ask jump a deferred one.
            self.stats.asks_deferred += 1
            self._deferred.append(DeferredAsk(t, x, queried, p))
            if len(self._deferred) > self.ring.capacity:
                d = self._deferred.popleft()
                self.stats.tickets_dropped += 1
                self.stats.queries_dropped += int(d.queried.sum())
            return
        self._ask(x, queried, p, t)

    def _flush_deferred(self) -> None:
        while self._deferred and not self.ring.full():
            d = self._deferred.popleft()
            # Ask with the ORIGIN tick — the tick the query is about — so
            # the ring entry marks the right `trained` row, label latency
            # meters end-to-end from the decision, and a ground-truth
            # teacher (array_labels) looks up the right tick's labels.
            self._ask(d.x, d.queried, d.plan, d.tick)

    def _claim(self, reply: TeacherReply):
        """Resolve a reply against the ring with all drop/orphan/loss
        metering and trained-row marking.  Returns ``(entry, mask, reply)``
        — the ring entry and the host-side apply mask — or None when
        nothing is applicable; the group (``engine/cohort.py``) scatters
        the mask into its learn."""
        stats = self.stats
        ent = self.ring.pop(reply.ticket)
        if ent is None:
            stats.replies_orphaned += 1
            return None
        asked = int(ent.queried.sum())
        mask = ent.queried & np.asarray(reply.answered, bool)
        n = int(mask.sum())
        if n == 0:
            # The teacher answered the ticket but covered none of its asked
            # streams — those queries are gone for good; meter the ticket
            # and every one of its queries as lost so the accounting
            # identity holds.
            stats.tickets_lost += 1
            stats.queries_lost += asked
            return None
        stats.labels_applied += n
        # Partial answer: the unanswered residue of this ticket will never
        # get labels — meter it now, at the only moment it is knowable.
        stats.queries_lost += asked - n
        stats.label_latency_ticks.append(self.t - ent.tick)
        if self.collect and ent.tick < len(self._trained_rows):
            self._trained_rows[ent.tick] |= mask
        return ent, mask, reply

    def _claims(self, replies: list[TeacherReply]) -> list:
        return [c for c in map(self._claim, replies) if c is not None]

    # -- the member's half of a tick (driven by engine/cohort.py) ----------

    def _intake(self, queried: np.ndarray, cols: Optional[dict], tr) -> int:
        """Take in the current tick: collect its host columns (``cols``),
        open its trained row, and route its decided queries through the
        backpressure policy.  Returns the queries decided."""
        if self.collect:
            for k, col in self._cols.items():
                col.append(cols[k])
            self._trained_rows.append(np.zeros(queried.shape, bool))
        n_q = int(queried.sum())
        if n_q:
            # Decision-time metering: the comm meter already charged these
            # queries inside plan; every one of them must end in exactly one
            # of applied / dropped / lost / coalesced.
            self.stats.queries_issued += n_q
            tok = tr.begin("tick.ask")
            self._submit(self._x, queried, self._p, self.t)
            tr.end(tok, queries=n_q)
        return n_q

    def _poll(self, tr) -> list:
        """Claim the replies that arrived by this tick (``_claim``'s
        triples, in arrival order), then submit deferred asks into the
        slots they freed."""
        tok = tr.begin("tick.poll")
        claims = self._claims(self.teacher.poll(self.t))
        self._flush_deferred()
        tr.end(tok, replies=len(claims))
        return claims

    def _end_tick(self, tick_s: float) -> None:
        """Account one finished tick of ``tick_s`` wall seconds."""
        st = self.stats
        st.ticks += 1
        st.stream_steps += self.width if self.live is None else self.live
        st.tick_ms.append(tick_s * 1e3)
        if tick_s > 0:
            rate = 1.0 / tick_s
            ema = st.tick_rate_ema
            st.tick_rate_ema = (
                rate if ema == 0.0 else ema + TICK_RATE_EMA_ALPHA * (rate - ema)
            )
        self.t += 1


def run(
    state: EngineState,
    ticks: Iterable,  # yields (S, n_in) feature arrays, one per tick
    cfg: EngineConfig,
    teacher: Teacher,
    mode: str = "algo1",
    capacity: int = 64,
    backpressure: str = "drop_oldest",
    collect: bool = True,
    drain: bool = True,
    donate: Optional[bool] = None,
    stats: Optional[StreamStats] = None,
) -> tuple[EngineState, Optional[FleetStepOutput], StreamStats]:
    """Drive the engine from a tick iterator with an asynchronous teacher.

    Per tick: dispatch ``plan`` (device), ingest + ship the *next* tick
    while it runs (double buffering), then submit the queried features to
    ``teacher.ask`` and apply any answers ``teacher.poll`` returns through
    ``learn`` — out of order, against the features captured at query time.
    Pending tickets live in a ``capacity``-slot ring; saturation behavior
    is the pluggable ``backpressure`` policy (``BACKPRESSURE_POLICIES``;
    default drop-oldest).  After the iterator is exhausted, answers still
    in flight are drained (``drain=True``) so no late label is silently
    discarded.

    Returns ``(final state, outputs, stats)``.  ``outputs`` mirrors
    ``run_fleet``'s stacked (T, S) ``FleetStepOutput`` (host arrays;
    ``trained`` marks label-application ticks) — or None when
    ``collect=False`` (long-running servers should not accumulate history)
    or the iterator was empty.

    ``donate`` (default True) lets every per-tick dispatch update P/beta
    and the controller leaves in place instead of allocating fresh buffers
    (P, S·N²·4 bytes, is the dominant one; a reply whose answered rows fit
    a capacity of ``learn_capacities`` reads and writes only those K rows
    of it, a full-width reply all S).  The runtime first takes ownership of
    ``state`` with a one-time copy, so the caller's pytree stays valid
    either way.
    """
    sess = StreamSession(
        state, cfg, teacher, mode=mode, capacity=capacity,
        backpressure=backpressure, collect=collect, donate=donate, stats=stats,
    )
    it = iter(ticks)
    nxt = next(it, None)
    if nxt is not None:
        sess.start(nxt)
        while nxt is not None:
            # Double buffering: pull tick t+1 from the iterator (and ship it
            # inside advance) while the device is busy with tick t's plan.
            nxt = next(it, None)
            sess.advance(nxt)
    return sess.finish(drain=drain)


# ---------------------------------------------------------------------------
# Mesh-sharded streaming: per-shard sessions with shard-local pending rings.
# ---------------------------------------------------------------------------


class ShardedStreamSession:
    """N shard-local ``StreamSession``s advanced in lockstep over row
    windows of one full-width tick source; each ticks as a group of one of
    the tick engine (``engine/cohort.py``), the shards in turn.

    Everything per-tick is shard-local: shard k's ``EngineState`` rows live
    on device k (the active mesh's devices, or wherever ``devices`` says);
    its plan/learn dispatches, pending ring, backpressure state, and
    teacher connection cover only rows ``[k*width, (k+1)*width)``; and a
    label learns back only into the shard that planned the query — steady-
    state label application is N independent masked shard-width learns,
    never a full-width gather/scatter.  Host ingestion hands each shard a
    row-slice view of the incoming tick (zero-copy for unpadded shards)
    instead of staging any full-width buffer.  Per-shard query accounting
    reconciles shard-locally (``stats_summary()["per_shard"]``), which is
    how tests lock the no-cross-shard-traffic property.

    ``teachers`` is one ``Teacher`` per shard, or a factory
    ``shard_idx -> Teacher``; a shard's replies route to its own ring by
    construction.  For a shared remote teacher host, hand every shard a
    tenant handle of one ``rpc.BatchedRpcClient`` — shard asks then
    coalesce into batched frames on one socket without breaking shard
    locality (the demux is per-handle).

    S is padded up to a multiple of ``n_shards`` with *metered dead rows*
    at the tail: dead rows plan with ``teacher_available=False`` (never
    query, never learn, excluded from ``stream_steps``), every shard's
    dispatch keeps the same padded width — so all shards share one
    compiled runner per (cfg, mode, donate) — and ``finish()`` strips the
    padding from the merged state/outputs.  Bit-for-bit parity with the
    unsharded ``run`` at equal S under a deterministic lossless teacher is
    locked by tests/test_mesh_fleet.py.
    """

    def __init__(
        self,
        state: EngineState,
        cfg: EngineConfig,
        teachers,
        n_shards: Optional[int] = None,
        mode: str = "algo1",
        capacity: int = 64,
        backpressure: str = "drop_oldest",
        collect: bool = True,
        donate: Optional[bool] = None,
        devices=None,
    ):
        if n_shards is None:
            n_shards = sharding.fleet_axis_size()
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if devices is None:
            mesh = sharding.mesh_or_none()
            if mesh is not None:
                devices = list(mesh.devices.flat)
        if devices is not None and len(devices) < n_shards:
            raise ValueError(f"{n_shards} shards > {len(devices)} devices")
        if callable(teachers):
            teachers = [teachers(k) for k in range(n_shards)]
        teachers = list(teachers)
        if len(teachers) != n_shards:
            raise ValueError(
                f"need one teacher per shard: {len(teachers)} != {n_shards}"
            )
        s = int(jax.tree.leaves(state)[0].shape[0])
        self.s_real = s
        self.n_shards = n_shards
        self.n_pad = (-s) % n_shards
        self.width = (s + self.n_pad) // n_shards
        self.bounds = [
            (k * self.width, (k + 1) * self.width) for k in range(n_shards)
        ]
        padded = fleet.pad_streams(state, cfg, self.n_pad)
        self.sessions: list[StreamSession] = []
        for k, (lo, hi) in enumerate(self.bounds):
            sub = fleet.slice_streams(padded, lo, hi)
            if devices is not None:
                sub = jax.device_put(sub, devices[k])
            live = min(self.width, max(0, s - lo))
            self.sessions.append(
                StreamSession(
                    sub, cfg, teachers[k], mode=mode, capacity=capacity,
                    backpressure=backpressure, collect=collect, donate=donate,
                    live=live,
                )
            )
            self.sessions[-1].telemetry_labels = {"shard": str(k)}
        self._zeros = None  # shared immutable tick slice for fully-dead shards

    def _shard_tick(self, x: np.ndarray, k: int):
        lo, hi = self.bounds[k]
        if hi <= self.s_real:
            return x[lo:hi]  # view, no copy
        shape = (self.width,) + x.shape[1:]
        if lo >= self.s_real:
            if self._zeros is None or self._zeros.shape != shape or self._zeros.dtype != x.dtype:
                self._zeros = np.zeros(shape, x.dtype)
            return self._zeros
        # Tail shard with live + dead rows: fresh buffer per tick — the
        # previous tick's staged rows may still be referenced by the
        # session (the ask happens on the *next* advance) and by ring
        # tickets, so an in-place staging buffer would corrupt them.
        buf = np.zeros(shape, x.dtype)
        buf[: self.s_real - lo] = x[lo:]
        return buf

    def started(self) -> bool:
        return self.sessions[0].started()

    # Per-shard dispatches are shard-LOCAL (each session's operands live
    # on one device), so they must not inherit a caller's multi-device
    # mesh scope — under it ``constrain_fleet`` would demand the full
    # device set.  ``sharding.deactivate()`` makes the constraint the
    # identity for the duration of the shard calls.

    def start(self, x0) -> None:
        x0 = np.asarray(x0)
        with sharding.deactivate():
            for k, sess in enumerate(self.sessions):
                sess.start(self._shard_tick(x0, k))

    def advance(self, nxt) -> None:
        nxt = None if nxt is None else np.asarray(nxt)
        with sharding.deactivate():
            for k, sess in enumerate(self.sessions):
                sess.advance(None if nxt is None else self._shard_tick(nxt, k))

    def finish(
        self, drain: bool = True
    ) -> tuple[EngineState, Optional[FleetStepOutput], list[StreamStats]]:
        """Drain every shard, merge states/outputs in row order (stripping
        the dead-row padding), and return the per-shard stats list
        (``aggregate_stats`` folds it into one summary)."""
        states, outs, stats = [], [], []
        with sharding.deactivate():
            for sess in self.sessions:
                st, o, sstats = sess.finish(drain=drain)
                states.append(jax.device_get(st))
                outs.append(o)
                stats.append(sstats)
        merged = fleet.stack_streams(states)
        if self.n_pad:
            merged = fleet.slice_streams(merged, 0, self.s_real)
        out = None
        if outs and all(o is not None for o in outs):
            out = jax.tree.map(lambda *a: np.concatenate(a, axis=1), *outs)
            if self.n_pad:
                out = jax.tree.map(lambda a: a[:, : self.s_real], out)
        return merged, out, stats

    def stats_summary(self) -> dict:
        return aggregate_stats(
            [s.stats for s in self.sessions], padded_streams=self.n_pad
        )


def aggregate_stats(stats_list: list, padded_streams: int = 0) -> dict:
    """Fold per-shard ``StreamStats`` into one fleet-wide summary.

    Counters sum; latency percentiles pool the shard windows; the
    accounting identity must hold *per shard* (a reply can only settle a
    query its own shard issued), so ``queries_reconciled`` is the AND —
    any cross-shard leak shows up as one shard over- and another
    under-counting."""
    counters = (
        "stream_steps", "tickets_issued", "queries_issued", "labels_applied",
        "tickets_dropped", "queries_dropped", "replies_orphaned",
        "tickets_lost", "queries_lost", "tickets_coalesced",
        "queries_coalesced", "asks_deferred", "tickets_reasked",
        "learns_compact", "learns_full", "learn_rows",
    )
    out = {k: sum(getattr(s, k) for s in stats_list) for k in counters}
    out["ticks"] = max((s.ticks for s in stats_list), default=0)
    out["wall_s"] = max((s.wall_s for s in stats_list), default=0.0)
    out["steps_per_s"] = (
        out["stream_steps"] / out["wall_s"] if out["wall_s"] > 0 else 0.0
    )
    tick_ms = [v for s in stats_list for v in s.tick_ms]
    lab = [v for s in stats_list for v in s.label_latency_ticks]
    out["tick_p50_ms"] = _percentile(tick_ms, 50)
    out["tick_p95_ms"] = _percentile(tick_ms, 95)
    out["label_latency_p50"] = _percentile(lab, 50)
    out["label_latency_p95"] = _percentile(lab, 95)
    out["queries_reconciled"] = all(s.reconciled for s in stats_list)
    out["padded_streams"] = padded_streams
    out["n_shards"] = len(stats_list)
    out["per_shard"] = [s.summary() for s in stats_list]
    return out


def run_sharded(
    state: EngineState,
    ticks: Iterable,  # yields full-width (S, n_in) feature arrays
    cfg: EngineConfig,
    teachers,  # one Teacher per shard, or factory shard_idx -> Teacher
    n_shards: Optional[int] = None,
    mode: str = "algo1",
    capacity: int = 64,
    backpressure: str = "drop_oldest",
    collect: bool = True,
    drain: bool = True,
    donate: Optional[bool] = None,
    devices=None,
) -> tuple[EngineState, Optional[FleetStepOutput], list[StreamStats]]:
    """``run`` over a mesh-sharded fleet: the stream axis splits into
    ``n_shards`` shard-local sessions (default: the active mesh's fleet
    axis), each with its own pending ring and teacher — see
    ``ShardedStreamSession``.  Returns ``(final state, outputs, per-shard
    stats)`` with state/outputs already merged back to full (unpadded)
    width."""
    sess = ShardedStreamSession(
        state, cfg, teachers, n_shards=n_shards, mode=mode, capacity=capacity,
        backpressure=backpressure, collect=collect, donate=donate,
        devices=devices,
    )
    it = iter(ticks)
    nxt = next(it, None)
    if nxt is not None:
        sess.start(nxt)
        while nxt is not None:
            nxt = next(it, None)
            sess.advance(nxt)
    return sess.finish(drain=drain)
