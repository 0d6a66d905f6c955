"""Cohort fusion: advance N same-shaped tenants in one fused dispatch.

The multiplexer (``engine/multiplex.py``) round-robins tenants through
*separate* jitted calls, paying a measured 15-45% tick-switch cache
penalty at high tenant counts even when every tenant shares the same
compiled executable.  A **cohort** removes the switches entirely: tenants
with the same ``(cfg, mode, donate)`` and stream width stack their
``EngineState`` pytrees along the leading stream axis (the tenant axis
folded onto S — every per-stream op in ``engine/fleet.py`` is elementwise
or einsum-batched over S, so row r of a stacked dispatch is bit-for-bit
row r of the solo dispatch), and one fused ``plan`` / ``learn`` /
``learn+plan`` call per tick advances all of them.

What fuses, and what stays per-tenant:

* **Fused** — the device work: plan, learn, the steady-state fused
  learn+plan, the queried-mask host sync, and (when tenants collect
  outputs) the per-tick column pulls.
* **Per-tenant** — everything a tenant observes: its ``PendingRing``,
  ``Teacher`` connection, backpressure policy, ``StreamStats`` counters,
  output collection, and tick cursor.  The demux happens at the host
  boundary: each member's slice of the stacked plan drives its own
  ``_submit`` / ``_claim_entry`` exactly as solo, so the per-tenant op
  sequence — and therefore every output, counter, and the query-accounting
  identity — is bit-for-bit the solo run's.

Replies demultiplex back through three learn paths, chosen per reply:

* **aligned** — the common case: a reply whose ring entry is a
  ``stream.PlanSlice`` of a full-width plan at the member's current
  bounds.  All aligned replies of a round that share the same full plan
  combine into ONE learn: each member's mask/labels scatter into their
  row window and everyone else's rows ride along under ``mask=False``,
  which is an exact identity — or, where few rows answered, the learn
  runs over the answered rows alone (``stream.learn_rows_index``).
* **fused** — when the last round is a single aligned group and no member
  is joining or leaving, its learn fuses with the next tick's stacked plan
  into one dispatch (bitwise identical to the separate dispatches — the
  engine's ops compile reassociation-free, locked by tests).
* **patch** — stragglers: a ticket asked before its tenant joined the
  cohort (live migration in) or before a resize.  Its solo-width plan
  context learns through ``fleet._patch_learn_runner``, which updates just
  that member's row window of the stacked P/beta in place.

Members join (``attach``) and leave (``detach``) mid-stream: detach
writes the member's current rows (and a materialized solo plan) back into
its ``StreamSession``, which then runs solo — so live migration out of a
fused cohort is the ordinary quiesce/snapshot flow, and a restored
snapshot admits straight into a matching cohort slot.
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
    """Lockstep driver for N member ``StreamSession``s on one stacked state.

    Members keep their own sessions (ring, teacher, stats, tick cursor);
    while fused, a member's ``session.state`` is stale — the cohort's
    stacked ``state`` is authoritative, and ``detach`` / ``refresh`` write
    the member's rows back.
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
        self.state: Optional[EngineState] = None
        # Same LRU keys as the members' own runners: fusing adds no cache
        # entries, the jit specializes internally per stacked width.
        self._plan_fn = stream._plan_runner(self.cfg, self.mode, self.donate)
        self._learn_fn = stream._learn_runner(self.cfg, self.donate)
        self._fused_fn = stream._learn_plan_runner(self.cfg, self.mode, self.donate)
        self._full_mask_dev = None  # cached device-side all-True apply mask
        self._laddered: set = set()  # (runner, width) compiled by learn_call
        # Stack every founding member in ONE tree concat (attach-at-a-time
        # would pay N-1 intermediate full copies — measurable at N=16).
        for m in members:
            self._admit_bookkeeping(m)
        self.state = fleet.stack_streams(
            [jax.tree.map(jnp.asarray, m.state) for m in members]
        ) if len(members) > 1 else jax.tree.map(jnp.copy, head.state)

    @property
    def total(self) -> int:
        return self.bounds[-1][1] if self.bounds else 0

    # -- membership --------------------------------------------------------

    def attach(self, sess: stream.StreamSession) -> None:
        """Absorb a session — fresh, running solo, or restored mid-stream.

        Its current state rows are appended to the stacked state; a pending
        solo-width plan (mid-stream join) keeps working through the
        straggler patch-learn path until the next fused plan re-aligns it.
        The caller must supply this member's next tick on the very next
        ``tick()`` — its rows take part in every fused dispatch from then
        on, exactly like its solo session would have.
        """
        self._admit_bookkeeping(sess)
        if self.state is None:
            # Own the rows we are about to donate tick after tick (the
            # member's own buffers must stay valid until detach overwrites
            # its .state); every later attach/detach concat re-owns anyway.
            self.state = jax.tree.map(jnp.copy, sess.state)
        else:
            self.state = fleet.stack_streams(
                [self.state, jax.tree.map(jnp.asarray, sess.state)]
            )

    def _admit_bookkeeping(self, sess: stream.StreamSession) -> None:
        """Validate a joining session and claim its row window — everything
        ``attach`` does except touching the stacked state, so ``__init__``
        can stack all founders in one concat."""
        if (sess.cfg, sess.mode, sess._donate) != (self.cfg, self.mode, self.donate):
            raise ValueError(
                "cohort members must share (cfg, mode, donate); "
                f"got {(sess.cfg, sess.mode, sess._donate)!r}"
            )
        if sess.started() and sess._p is None:
            raise ValueError("cannot attach a session with nothing left to plan")
        s = int(np.shape(np.asarray(sess.state.elm.count))[0])
        lo = self.total
        self.members.append(sess)
        self.bounds.append((lo, lo + s))

    def detach(self, sess: stream.StreamSession) -> stream.StreamSession:
        """Hand a member back to solo operation: write its current rows
        (and a materialized solo plan, if one is pending) back into the
        session and drop them from the stacked state.  Ring entries that
        still hold ``PlanSlice`` views keep working — solo learns slice
        them lazily."""
        i = self.members.index(sess)
        lo, hi = self.bounds[i]
        sess.state = fleet.slice_streams(self.state, lo, hi)
        if isinstance(sess._p, stream.PlanSlice):
            sess._p = sess._p.materialize()
        self.members.pop(i)
        w = hi - lo
        self.bounds = self.bounds[:i] + [
            (a - w, b - w) for a, b in self.bounds[i + 1 :]
        ]
        self.state = (
            fleet.remove_streams(self.state, lo, hi) if self.members else None
        )
        return sess

    def refresh(self, sess: stream.StreamSession) -> None:
        """Write a member's current rows back into its (stale) session
        state without detaching — cadence snapshots of fused members."""
        lo, hi = self.bounds[self.members.index(sess)]
        sess.state = fleet.slice_streams(self.state, lo, hi)

    # -- the fused tick ----------------------------------------------------

    def tick(self, nxts: list) -> tuple[list, bool]:
        """Advance every member one tick with fused device dispatches.

        ``nxts[i]`` is member i's next tick features — its first tick when
        the member has not started, None when its source is exhausted (the
        member finishes this tick's asks/polls/learns like a solo
        ``advance(None)``, then detaches).  Returns ``(detached, advanced)``:
        the sessions handed back to solo operation, and whether any member
        actually advanced a tick (False for the all-start first tick).
        """
        tel = _telemetry.TELEMETRY
        if tel is None:
            return self._tick(nxts, None, None)
        labels = {"t": self.members[0].t if self.members else 0}
        tok = tel.tracer.begin("cohort.tick")
        try:
            return self._tick(nxts, tel, labels)
        finally:
            tel.tracer.end(tok, **labels)

    def _tick(self, nxts: list, tel, labels: Optional[dict]) -> tuple[list, bool]:
        """``tick``'s body; with telemetry on (``tel``) it records the
        tick's phases as ``tick.*`` spans and fills the tick span's
        ``labels``."""
        t0 = time.perf_counter()
        members = list(self.members)
        assert len(nxts) == len(members), "one next-tick entry per member"
        # Keep next-tick features on the host: one np.concatenate + ONE
        # transfer ships the whole cohort's tick (vs a device_put per member
        # plus a device-side concat — the old per-tick hot spot).  Members
        # hold their host array as ``_x``; ring tickets and snapshots only
        # ever read its values.
        tok = tel.tracer.begin("tick.sync") if tel is not None else None
        x_host = [None if x is None else np.asarray(x) for x in nxts]
        if tok is not None:
            tel.tracer.end(tok, what="feats")
        full = self._aligned_full()
        queried_full = None
        if full is not None:
            tok = tel.tracer.begin("tick.sync") if tel is not None else None
            queried_full = np.asarray(full.queried)
            if tok is not None:
                tel.tracer.end(tok, what="mask")
        cols_full = None
        if queried_full is not None and any(
            m.collect and m.started() for m in members
        ):
            # One host sync per column for the whole cohort instead of one
            # per member (values are identical either way — pure movement).
            tok = tel.tracer.begin("tick.sync") if tel is not None else None
            cols_full = {k: np.asarray(getattr(full, k)) for k in _COL_KEYS}
            if tok is not None:
                tel.tracer.end(tok, what="cols")

        # Per-member tick bookkeeping: collect, submit asks, claim replies.
        # Cross-member order is irrelevant (rows are independent); each
        # member's own op order matches its solo ``advance`` exactly.
        applies: list[list] = []
        ticking: list[int] = []
        for i, m in enumerate(members):
            if not m.started():
                applies.append([])
                continue
            ticking.append(i)
            lo, hi = self.bounds[i]
            p = m._p
            if queried_full is not None:
                queried_host = queried_full[lo:hi]
            else:
                tok = tel.tracer.begin("tick.sync") if tel is not None else None
                queried_host = np.asarray(p.queried)
                if tok is not None:
                    tel.tracer.end(tok, what="mask")
            if m.collect:
                if cols_full is not None:
                    for k in _COL_KEYS:
                        m._cols[k].append(cols_full[k][lo:hi])
                else:
                    tok = tel.tracer.begin("tick.sync") if tel is not None else None
                    for k in _COL_KEYS:
                        m._cols[k].append(np.asarray(getattr(p, k)))
                    if tok is not None:
                        tel.tracer.end(tok, what="cols")
                m._trained_rows.append(np.zeros(queried_host.shape, bool))
            n_q = int(queried_host.sum())
            if n_q:
                m.stats.queries_issued += n_q
                tok = tel.tracer.begin("tick.ask") if tel is not None else None
                m._submit(m._x, queried_host, p, m.t)
                if tok is not None:
                    tel.tracer.end(tok, queries=n_q)
            tok = tel.tracer.begin("tick.poll") if tel is not None else None
            member_applies = []
            for r in m.teacher.poll(m.t):
                claimed = m._claim_entry(r, m.t)
                if claimed is not None:
                    member_applies.append((claimed[0], claimed[1], r))
            m._flush_deferred(m.t)
            if tok is not None:
                tel.tracer.end(tok, replies=len(member_applies))
            applies.append(member_applies)

        planning = [i for i in range(len(members)) if nxts[i] is not None]
        resizing = len(planning) != len(members)
        p_next = None

        def x_next_stacked():
            tok = tel.tracer.begin("tick.ship") if tel is not None else None
            hosts = [x_host[i] for i in planning]
            x = self.ship(
                np.concatenate(hosts, axis=0) if len(hosts) > 1 else hosts[0]
            )
            if tok is not None:
                tel.tracer.end(tok)
            return x

        # Learns in rounds: round j applies each member's j-th claimed
        # reply, preserving every member's own apply order while letting
        # replies that share a full plan combine into one dispatch.
        n_rounds = max((len(a) for a in applies), default=0)
        learns = 0
        for j in range(n_rounds):
            groups: dict[int, list] = {}
            order: list[tuple[int, fleet.PlanOutput]] = []
            stragglers: list[tuple[int, object, np.ndarray, object]] = []
            for i, member_applies in enumerate(applies):
                if j >= len(member_applies):
                    continue
                ent, mask, reply = member_applies[j]
                p = ent.plan
                if (
                    isinstance(p, stream.PlanSlice)
                    and p.full.queried.shape[0] == self.total
                    and (p.lo, p.hi) == self.bounds[i]
                ):
                    key = id(p.full)
                    if key not in groups:
                        groups[key] = []
                        order.append((key, p.full))
                    groups[key].append((i, ent, mask, reply))
                else:
                    stragglers.append((i, ent, mask, reply))
            fuse = (
                j == n_rounds - 1
                and not resizing
                and len(order) == 1
                and not stragglers
            )
            learns += len(order) + len(stragglers)
            for key, fullp in order:
                args, rows = self._group_args(fullp, groups[key])
                n_rows = self.total if rows is None else len(rows)
                if fuse:
                    x_next = x_next_stacked()
                    tok = tel.tracer.begin("tick.dispatch") if tel is not None else None
                    (elm2, prune2, drift2, meter2), p_next = self._learn_call(
                        self._fused_fn,
                        (self.state.elm, self.state.prune, self.state.drift,
                         self.state.meter, *args, x_next),
                        rows,
                    )
                    if tok is not None:
                        tel.tracer.end(tok, fn="learn_plan", rows=n_rows)
                    self.state = EngineState(
                        elm=elm2, prune=prune2, drift=drift2, meter=meter2
                    )
                else:
                    tok = tel.tracer.begin("tick.dispatch") if tel is not None else None
                    new_elm, new_prune = self._learn_call(
                        self._learn_fn,
                        (self.state.elm, self.state.prune, self.state.drift,
                         self.state.meter, *args),
                        rows,
                    )
                    if tok is not None:
                        tel.tracer.end(tok, fn="learn", rows=n_rows)
                    self.state = self.state._replace(elm=new_elm, prune=new_prune)
            for i, ent, mask, reply in stragglers:
                tok = tel.tracer.begin("tick.dispatch") if tel is not None else None
                self._patch_learn(i, ent, mask, reply)
                if tok is not None:
                    tel.tracer.end(tok, fn="patch")

        # Tick accounting for members that advanced (solo `advance` parity;
        # the shared wall time lands in every advanced member's tick_ms).
        for i in ticking:
            m = members[i]
            m.stats.ticks += 1
            m.stats.stream_steps += int(np.shape(m._x)[0])
            m.t += 1

        # Detach exhausted members before the next plan re-slices bounds.
        detached = []
        leaving = [i for i in range(len(members)) if nxts[i] is None]
        if leaving and len(leaving) == len(self.members):
            # Equal-length streams all run dry on the same tick — the common
            # shutdown.  Write each member's rows back with one slice apiece
            # and drop the stacked state wholesale, instead of per-member
            # ``detach`` paying a shrinking remove_streams concat each time.
            for i in leaving:
                m = members[i]
                m._x, m._p = None, None
                m.state = fleet.slice_streams(self.state, *self.bounds[i])
                detached.append(m)
            self.members, self.bounds, self.state = [], [], None
        else:
            for i in leaving:
                m = members[i]
                m._x, m._p = None, None
                detached.append(self.detach(m))

        # Plan the next tick for everyone remaining (starts fresh members).
        if planning and p_next is None:
            x_next = x_next_stacked()
            tok = tel.tracer.begin("tick.dispatch") if tel is not None else None
            (prune2, drift2, meter2), p_next = self._plan_fn(
                self.state.elm, self.state.prune, self.state.drift,
                self.state.meter, x_next,
            )
            if tok is not None:
                tel.tracer.end(tok, fn="plan")
            self.state = self.state._replace(
                prune=prune2, drift=drift2, meter=meter2
            )
        if p_next is not None:
            for idx, i in enumerate(planning):
                m = members[i]
                lo, hi = self.bounds[idx]
                if not m.started():
                    m._t_start = t0
                m._x = x_host[i]
                m._p = stream.PlanSlice(p_next, lo, hi)
        wall_ms = (time.perf_counter() - t0) * 1e3
        rate = 1e3 / wall_ms if wall_ms > 0 else 0.0
        for i in ticking:
            st = members[i].stats
            st.tick_ms.append(wall_ms)
            if rate > 0:  # same load signal solo `advance` keeps
                st.tick_rate_ema = (
                    rate if st.tick_rate_ema == 0.0
                    else st.tick_rate_ema
                    + stream.TICK_RATE_EMA_ALPHA * (rate - st.tick_rate_ema)
                )
        if labels is not None:
            labels.update(members=len(members), s=self.total,
                          detached=len(detached), learns=learns)
        return detached, bool(ticking)

    # -- internals ---------------------------------------------------------

    def _aligned_full(self) -> Optional[fleet.PlanOutput]:
        """The one full-width plan every started member's pending plan
        slices at current bounds — or None (first tick, or a member joined
        mid-stream with a solo plan / pre-resize slice)."""
        full = None
        for i, m in enumerate(self.members):
            if not m.started():
                continue
            p = m._p
            if (
                not isinstance(p, stream.PlanSlice)
                or p.full.queried.shape[0] != self.total
                or (p.lo, p.hi) != self.bounds[i]
            ):
                return None
            if full is None:
                full = p.full
            elif p.full is not full:
                return None
        return full

    def _group_args(self, fullp: fleet.PlanOutput, group: list):
        """Scatter one round's aligned member masks/labels into full-width
        learn args against their shared full plan; returns ``(args,
        rows)``, ``rows`` the learn's ``stream.learn_rows_index``.  Members
        outside the group ride along under mask=False — an exact identity —
        or, when the learn compacts, are left out of it.  Each member's
        stats meter the rows of its own window that the learn reads and
        writes."""
        total = self.total
        mask_full = np.zeros((total,), bool)
        labels_full = np.zeros((total,), np.int32)
        for i, ent, mask, reply in group:
            lo, hi = self.bounds[i]
            mask_full[lo:hi] = mask
            labels_full[lo:hi] = np.asarray(reply.labels, np.int32)
        rows = stream.learn_rows_index(mask_full)
        for i, ent, mask, reply in group:
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
        """Straggler reply: learn one member's solo-width plan context into
        its row window of the stacked state."""
        m = self.members[i]
        lo, hi = self.bounds[i]
        m.stats.count_learn(hi - lo, False)
        args = m._build_learn_args(ent, reply, mask)
        fn = fleet._patch_learn_runner(self.cfg, lo, hi, self.donate)
        new_elm, new_prune = fn(
            self.state.elm, self.state.prune, self.state.drift,
            self.state.meter, *args,
        )
        self.state = self.state._replace(elm=new_elm, prune=new_prune)
