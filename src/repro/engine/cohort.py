"""The tick engine: advance a group of same-shaped tenants one tick at a time.

Every live tick runs here.  A ``CohortSession`` is a group of member
``StreamSession``s that share ``(cfg, mode, donate)``; each tick it makes
one set of device dispatches for all of them.  A session ticking on its own
(``stream.run``, a multiplexer tenant no one fuses with, each shard of a
``stream.ShardedStreamSession``) is a group of one; the multiplexer
(``engine/multiplex.py``) packs same-width tenants into a group of several
— a *cohort* — whose ``EngineState`` pytrees stack along the leading
stream axis (every per-stream op in ``engine/fleet.py`` is elementwise or
einsum-batched over S, so row r of a stacked dispatch is bit-for-bit row r
of the group-of-one dispatch).

What the group does, and what stays per-tenant:

* **Group** — the device work: plan, learn, the steady-state fused
  learn+plan, the queried-mask host sync, and (when tenants collect
  outputs) the per-tick column pulls.
* **Per-tenant** — everything a tenant observes: its ``PendingRing``,
  ``Teacher`` connection, backpressure policy, ``StreamStats`` counters,
  output collection, and tick cursor.  The demux happens at the host
  boundary: each member's rows of the plan drive its own intake, poll and
  claim, and tick accounting (``StreamSession._intake`` / ``_poll`` /
  ``_end_tick``), so every output, counter, and the query-accounting
  identity are the same in a group of one or of several.

A group of one keeps its member's own rows (``session.state`` is the
group's state, with no copy), hands it the plain ``fleet.PlanOutput`` of
its plan, and ships the features it is given as they are (a device array
stays on the device).  A group of several stacks its members' rows (their
``session.state`` is stale until ``refresh`` or ``detach`` writes them
back), pulls their features to the host and ships them in one transfer,
and hands each member a ``stream.PlanSlice`` of the stacked plan.  A
member with dead padding rows (``live``) ticks only in a group of one,
which plans with ``teacher_available`` (the ``_avail`` runners).

Replies learn in rounds — round j applies each member's j-th claimed
reply, keeping every member's own apply order:

* **aligned** — a reply whose ring entry holds a plan over the group's
  current rows (in a group of one, every reply).  The aligned replies of a
  round that share a plan combine into ONE learn: each member's
  mask/labels scatter into its row window and everyone else's rows ride
  along under ``mask=False``, which is an exact identity — or, where few
  rows answered, the learn runs over the answered rows alone
  (``stream.learn_rows_index``).
* **fused** — when the last round is a single aligned group and no member
  leaves this tick, its learn fuses with the next tick's plan into one
  dispatch (bitwise identical to the separate dispatches — the engine's
  ops compile reassociation-free, locked by tests).
* **patch** — stragglers in a group of several: a ticket asked before its
  tenant joined (live migration in) or before a resize.  Its member-width
  plan context learns through ``fleet._patch_learn_runner``, which
  updates just that member's row window of the stacked P/beta in place.

Members join (``attach``) and leave (``detach``) mid-stream; a member that
leaves is handed back to a group of its own with its current rows, so live
migration out of a cohort is the ordinary quiesce/snapshot flow, and a
restored snapshot admits straight into a matching cohort.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import fleet, stream
from repro.engine.types import EngineState
from repro.runtime import telemetry as _telemetry

_COL_KEYS = ("pred", "outputs", "queried", "theta", "confidence", "mode_training")


class CohortSession:
    """The tick engine over one group of member ``StreamSession``s.

    Members keep their own sessions (ring, teacher, stats, tick cursor).
    The group's ``state`` is its one member's ``session.state`` in a group
    of one, and the members' stacked rows in a group of several (whose
    members' ``session.state`` is then stale until ``detach`` /
    ``refresh`` writes their rows back).
    """

    def __init__(self, members: list[stream.StreamSession]):
        if not members:
            raise ValueError("cohort needs at least one member")
        head = members[0]
        self.cfg = head.cfg
        self.mode = head.mode
        self.donate = head._donate
        self.ship = head.ship
        self.members: list[stream.StreamSession] = []
        self.bounds: list[tuple[int, int]] = []
        self._stacked: Optional[EngineState] = None
        # Dead padding rows plan with ``teacher_available`` (the ``_avail``
        # runners take it after the next tick's features).
        self._tail: tuple = ()
        if head.live is None:
            self._plan_fn = stream._plan_runner(self.cfg, self.mode, self.donate)
            self._fused_fn = stream._learn_plan_runner(self.cfg, self.mode, self.donate)
        else:
            self._tail = (jnp.arange(head.width) < head.live,)
            self._plan_fn = stream._plan_avail_runner(self.cfg, self.mode, self.donate)
            self._fused_fn = stream._learn_plan_avail_runner(
                self.cfg, self.mode, self.donate
            )
        self._learn_fn = stream._learn_runner(self.cfg, self.donate)
        self._full_mask_dev = None  # cached device-side all-True apply mask
        self._laddered: set = set()  # (runner, width) compiled by learn_call
        self._check(head, alone=True)
        self._admit(head)
        if len(members) > 1:
            self.attach(*members[1:])

    @property
    def total(self) -> int:
        return self.bounds[-1][1]

    @property
    def state(self) -> EngineState:
        return self._stacked if len(self.members) > 1 else self.members[0].state

    @state.setter
    def state(self, st: EngineState) -> None:
        if len(self.members) > 1:
            self._stacked = st
        else:
            self.members[0].state = st

    # -- membership --------------------------------------------------------

    def attach(self, *sessions: stream.StreamSession) -> None:
        """Absorb sessions — fresh, ticking on their own, or restored
        mid-stream.

        Their current state rows are appended to the stacked state in ONE
        tree concat (one at a time would pay an intermediate full copy
        each); a pending member-width plan (mid-stream join) keeps working
        through the straggler patch-learn path until the next group plan
        re-aligns it.  The caller must supply each new member's next tick
        on the very next ``tick()``.
        """
        self._joinable(self.members[0])
        for sess in sessions:
            self._check(sess, alone=False)
        prev = self.state
        for sess in sessions:
            self._admit(sess)
        self._stacked = fleet.stack_streams(
            [prev, *(jax.tree.map(jnp.asarray, sess.state) for sess in sessions)]
        )

    def _check(self, sess: stream.StreamSession, alone: bool) -> None:
        """Raise unless ``sess`` can join this group (``alone``: as its
        only member)."""
        if (sess.cfg, sess.mode, sess._donate) != (self.cfg, self.mode, self.donate):
            raise ValueError(
                "cohort members must share (cfg, mode, donate); "
                f"got {(sess.cfg, sess.mode, sess._donate)!r}"
            )
        if sess._cohort is not None and len(sess._cohort.members) > 1:
            raise ValueError("session is a member of another cohort; detach it first")
        if not alone:
            self._joinable(sess)

    @staticmethod
    def _joinable(sess: stream.StreamSession) -> None:
        """Raise unless ``sess`` can tick beside other members."""
        if sess.live is not None:
            raise ValueError("a session with dead rows ticks only in a group of its own")
        if sess.started() and sess._p is None:
            raise ValueError("cannot attach a session with nothing left to plan")

    def _admit(self, sess: stream.StreamSession) -> None:
        """Claim a checked session's row window."""
        lo = self.bounds[-1][1] if self.bounds else 0
        self.members.append(sess)
        self.bounds.append((lo, lo + sess.width))
        sess._cohort = self

    def detach(self, sess: stream.StreamSession) -> stream.StreamSession:
        """Hand a member of a group of several back to a group of its own
        (live migration out).  Ring entries that still hold ``PlanSlice``
        views keep working — its group of one slices them lazily."""
        if len(self.members) < 2:
            raise ValueError("a group of one keeps its member")
        self._drop([self.members.index(sess)])
        # Pending plans become member-width, as a group of one keeps them.
        for m in (sess, *(self.members if len(self.members) == 1 else ())):
            if isinstance(m._p, stream.PlanSlice):
                m._p = m._p.materialize()
        return sess

    def refresh(self, sess: stream.StreamSession) -> None:
        """Write a member's current rows back into its (stale) session
        state without detaching — cadence snapshots of fused members.  A
        group of one's member is always current."""
        if len(self.members) > 1:
            i = self.members.index(sess)
            sess.state = fleet.slice_streams(self._stacked, *self.bounds[i])

    def _drop(self, gone: list[int]) -> None:
        """Hand members ``gone`` (indices, not all of them) back to groups
        of their own, each with its current rows.  The rest stay,
        restacked; one left alone gets its rows back the same way, and the
        group's state is its own from then on."""
        keep = [i for i in range(len(self.members)) if i not in gone]
        full = self._stacked
        for i in (gone if len(keep) > 1 else range(len(self.members))):
            self.members[i].state = fleet.slice_streams(full, *self.bounds[i])
        for i in gone:
            self.members[i]._cohort = None
        self._stacked = fleet.stack_streams(
            [fleet.slice_streams(full, *self.bounds[i]) for i in keep]
        ) if len(keep) > 1 else None
        self.members = [self.members[i] for i in keep]
        self.bounds, lo = [], 0
        for m in self.members:
            self.bounds.append((lo, lo + m.width))
            lo += m.width

    # -- the tick ----------------------------------------------------------

    # odlint: shard-local
    def tick(self, nxts: list) -> tuple[list, bool]:
        """Advance every member one tick.

        ``nxts[i]`` is member i's next tick features — its first tick when
        the member has not started, None when its source is exhausted (the
        member finishes this tick's asks/polls/learns and plans nothing
        more; in a group of several it then leaves for a group of its own,
        and the group keeps at least one member).  Returns ``(left,
        advanced)``: the members whose source ran out, and whether any
        member actually advanced a tick (False for a tick that only starts
        members, which records no spans).
        """
        tel = _telemetry.TELEMETRY
        if tel is None or not any(m.started() for m in self.members):
            return self._tick(nxts, stream.NO_TRACER, None)
        tr = tel.tracer
        if len(self.members) == 1:
            labels = {"t": self.members[0].t, **self.members[0].telemetry_labels}
            tok = tr.begin("stream.tick")
        else:
            labels = {"t": self.members[0].t}
            tok = tr.begin("cohort.tick")
        try:
            return self._tick(nxts, tr, labels)
        finally:
            tr.end(tok, **labels)

    def _tick(self, nxts: list, tr, labels: Optional[dict]) -> tuple[list, bool]:
        """``tick``'s body; it records the tick's phases as ``tick.*`` spans
        on the tracer ``tr`` and, with telemetry on, fills the tick span's
        ``labels``."""
        t0 = time.perf_counter()
        members = list(self.members)
        assert len(nxts) == len(members), "one next-tick entry per member"
        planning = [i for i, x in enumerate(nxts) if x is not None]
        xs, x_next = self._stage(nxts, planning, tr)

        full = self._aligned_full()
        queried_full = cols_full = None
        if full is not None:
            tok = tr.begin("tick.sync")
            queried_full = np.asarray(full.queried)
            tr.end(tok, what="mask")
            if any(m.collect and m.started() for m in members):
                # One host sync per column for the whole group.
                tok = tr.begin("tick.sync")
                cols_full = {k: np.asarray(getattr(full, k)) for k in _COL_KEYS}
                tr.end(tok, what="cols")

        # Each member's intake, asks and claimed replies, in its own order.
        applies: list[list] = []
        ticking = [i for i, m in enumerate(members) if m.started()]
        queries = 0
        for i, m in enumerate(members):
            if not m.started():
                applies.append([])
                continue
            lo, hi = self.bounds[i]
            if queried_full is not None:
                queried = queried_full[lo:hi]
                cols = (None if cols_full is None
                        else {k: v[lo:hi] for k, v in cols_full.items()})
            else:
                tok = tr.begin("tick.sync")
                queried = np.asarray(m._p.queried)
                tr.end(tok, what="mask")
                cols = None
                if m.collect:
                    tok = tr.begin("tick.sync")
                    cols = {k: np.asarray(getattr(m._p, k)) for k in _COL_KEYS}
                    tr.end(tok, what="cols")
            queries += m._intake(queried, cols, tr)
            applies.append(m._poll(tr))

        # Nobody leaving: the last learn may fuse with the next plan.
        fuse_x = x_next if len(planning) == len(members) else None
        p_next, learns = self._learn_rounds(applies, fuse_x, tr)

        leaving = [i for i, x in enumerate(nxts) if x is None]
        for i in leaving:
            members[i]._x, members[i]._p = None, None
        gone = []
        if leaving and len(members) > 1:
            gone = leaving if planning else leaving[1:]
            self._drop(gone)

        # Plan the next tick for everyone remaining (starts fresh members).
        if planning and p_next is None:
            tok = tr.begin("tick.dispatch")
            (prune, drift, meter), p_next = self._plan_fn(*self.state, x_next, *self._tail)
            tr.end(tok, fn="plan")
            self.state = self.state._replace(prune=prune, drift=drift, meter=meter)
        if p_next is not None:
            for idx, i in enumerate(planning):
                m = members[i]
                if not m.started():
                    m._t_start = t0
                m._x = xs[i]
                m._p = (p_next if len(self.members) == 1
                        else stream.PlanSlice(p_next, *self.bounds[idx]))
        tick_s = time.perf_counter() - t0
        for i in ticking:
            members[i]._end_tick(tick_s)
        if labels is not None:
            if len(members) == 1:
                labels.update(queries=queries, learns=learns)
            else:
                labels.update(members=len(members), s=self.total,
                              detached=len(gone), learns=learns)
        return [members[i] for i in leaving], bool(ticking)

    # odlint: shard-local
    def learn_replies(self, claims: list) -> None:
        """Learn a group of one's claimed replies (``StreamSession._poll``'s
        ``(entry, mask, reply)``) in order, each in its own dispatch — a
        drain's, after the member's source ran out."""
        assert len(self.members) == 1, "only a group of one drains"
        self._learn_rounds([claims], None, stream.NO_TRACER)

    # -- internals ---------------------------------------------------------

    def _stage(self, nxts: list, planning: list[int], tr) -> tuple[list, object]:
        """The members' next-tick features as each member keeps them
        (``xs``), and the group's shipped next-tick input (None when no
        member plans).  A group of one ships what it is given, so a device
        array never visits the host; a group of several pulls its members'
        features to the host and ships them in one concatenated transfer."""
        if not planning:
            return list(nxts), None
        if len(self.members) == 1:
            tok = tr.begin("tick.ship")
            x = self.ship(nxts[0])
            tr.end(tok)
            return [x], x
        tok = tr.begin("tick.sync")
        xs = [None if x is None else np.asarray(x) for x in nxts]
        tr.end(tok, what="feats")
        hosts = [xs[i] for i in planning]
        tok = tr.begin("tick.ship")
        x_next = self.ship(np.concatenate(hosts, axis=0) if len(hosts) > 1 else hosts[0])
        tr.end(tok)
        return xs, x_next

    def _full_of(self, i: int, p) -> Optional[fleet.PlanOutput]:
        """The plan over the group's current rows that member i's plan
        ``p`` reads — ``p`` itself in a group of one, its full plan for an
        aligned ``PlanSlice`` in a group of several — or None (a
        straggler)."""
        if len(self.members) == 1:
            return p
        if (isinstance(p, stream.PlanSlice) and p.full.queried.shape[0] == self.total
                and (p.lo, p.hi) == self.bounds[i]):
            return p.full
        return None

    def _aligned_full(self) -> Optional[fleet.PlanOutput]:
        """The one plan every started member's pending plan reads at the
        group's current rows — or None (first tick, or a member joined
        mid-stream with a member-width plan or a pre-resize slice)."""
        full = None
        for i, m in enumerate(self.members):
            if not m.started():
                continue
            f = self._full_of(i, m._p)
            if f is None or (full is not None and f is not full):
                return None
            full = f
        return full

    def _learn_rounds(self, applies: list, fuse_x, tr):
        """Dispatch every member's claimed replies (``applies[i]``) in
        rounds; the last round's learn fuses with the plan of ``fuse_x``
        when it is one aligned learn.  Returns ``(fused plan or None,
        learn dispatches)``."""
        n_rounds = max((len(a) for a in applies), default=0)
        p_next, learns = None, 0
        for j in range(n_rounds):
            groups: dict[int, tuple] = {}
            stragglers = []
            for i, member_applies in enumerate(applies):
                if j >= len(member_applies):
                    continue
                ent, mask, reply = member_applies[j]
                full = self._full_of(i, ent.plan)
                if full is None:
                    stragglers.append((i, ent, mask, reply))
                else:
                    groups.setdefault(id(full), (full, []))[1].append((i, mask, reply))
            fuse = (fuse_x is not None and j == n_rounds - 1 and len(groups) == 1
                    and not stragglers)
            learns += len(groups) + len(stragglers)
            for full, group in groups.values():
                args, rows = self._group_args(full, group)
                n_rows = self.total if rows is None else len(rows)
                tok = tr.begin("tick.dispatch")
                if fuse:
                    (elm, prune, drift, meter), p_next = self._learn_call(
                        self._fused_fn, (*self.state, *args, fuse_x, *self._tail), rows
                    )
                    tr.end(tok, fn="learn_plan", rows=n_rows)
                    self.state = EngineState(elm=elm, prune=prune, drift=drift, meter=meter)
                else:
                    elm, prune = self._learn_call(self._learn_fn, (*self.state, *args), rows)
                    tr.end(tok, fn="learn", rows=n_rows)
                    self.state = self.state._replace(elm=elm, prune=prune)
            for i, ent, mask, reply in stragglers:
                tok = tr.begin("tick.dispatch")
                self._patch_learn(i, ent, mask, reply)
                tr.end(tok, fn="patch")
        return p_next, learns

    def _group_args(self, fullp: fleet.PlanOutput, group: list):
        """Scatter one round's aligned member masks/labels into learn args
        over the group's rows against their shared plan; returns ``(args,
        rows)``, ``rows`` the learn's ``stream.learn_rows_index``.  Members
        outside the group ride along under mask=False — an exact identity —
        or, when the learn compacts, are left out of it.  Each member's
        stats meter the rows of its own window that the learn reads and
        writes."""
        total = self.total
        mask_full = np.zeros((total,), bool)
        labels_full = np.zeros((total,), np.int32)
        for i, mask, reply in group:
            lo, hi = self.bounds[i]
            mask_full[lo:hi] = mask
            labels_full[lo:hi] = np.asarray(reply.labels, np.int32)
        rows = stream.learn_rows_index(mask_full)
        for i, mask, reply in group:
            lo, hi = self.bounds[i]
            self.members[i].stats.count_learn(
                hi - lo if rows is None
                else int(np.searchsorted(rows, hi) - np.searchsorted(rows, lo)),
                rows is not None,
            )
        if mask_full.all():
            if self._full_mask_dev is None or self._full_mask_dev.shape[0] != total:
                self._full_mask_dev = jnp.ones((total,), jnp.bool_)
            mask_dev = self._full_mask_dev
        else:
            mask_dev = jnp.asarray(mask_full)
        return (
            fullp.h,
            self.ship(labels_full),
            fullp.pred,
            fullp.confidence,
            mask_dev,
            fullp.controller_on,
            fullp.theta,
        ), rows

    def _learn_call(self, fn, args: tuple, rows):
        return stream.learn_call(fn, args, rows, self.total, self._laddered, self.ship)

    def _patch_learn(self, i: int, ent, mask: np.ndarray, reply) -> None:
        """Straggler reply: learn one member's member-width plan context
        into its row window of the stacked state."""
        lo, hi = self.bounds[i]
        self.members[i].stats.count_learn(hi - lo, False)
        p = ent.plan
        fn = fleet._patch_learn_runner(self.cfg, lo, hi, self.donate)
        elm, prune = fn(
            *self.state, p.h, self.ship(np.asarray(reply.labels, np.int32)), p.pred,
            p.confidence, jnp.asarray(mask), p.controller_on, p.theta,
        )
        self.state = self.state._replace(elm=elm, prune=prune)
