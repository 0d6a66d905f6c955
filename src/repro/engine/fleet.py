"""Batched fleet engine for Algorithm 1 (see package docstring).

Design rules:
  * every leaf of ``EngineState`` carries a leading stream axis S;
  * all per-stream controller math (pruning ladder, drift detector) is
    elementwise jnp, so the scalar transition functions in ``core/`` apply
    to (S,) arrays unchanged — no vmap anywhere on the hot path;
  * the only matmuls are one (S, n_in) @ alpha hidden projection and the
    einsum-batched rank-1 Woodbury update (optionally the fused Pallas
    kernel via ``cfg.elm.use_kernel``);
  * one tick is split at the teacher round-trip: ``plan`` (predict, drift,
    query decision, comm metering) and ``learn`` (masked rank-1 RLS + the
    auto-theta controller observing answered queries).  ``fleet_step`` is
    exactly ``learn(plan(...))`` with same-tick labels, so the streaming
    runtime (``engine/stream.py``), which runs the two halves as separate
    dispatches with real teacher latency in between, degrades bit-for-bit
    to ``run_fleet`` when the teacher answers instantly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import drift as drift_mod
from repro.core import labels as labels_mod
from repro.core import oselm, pruning
from repro.distributed import sharding
from repro.engine.types import (
    EngineConfig,
    EngineState,
    FleetStepOutput,
    init_state,
)

# How many compiled runners to keep alive per process.  A serving process
# cycles through a handful of (cfg, mode, donate) combinations; unbounded
# caching leaks one executable per combination forever (see ROADMAP PR-2).
RUNNER_CACHE_SIZE = 32


def init_fleet(cfg: EngineConfig, n_streams: int) -> EngineState:
    return broadcast_streams(init_state(cfg), n_streams)


def broadcast_streams(state: EngineState, n_streams: int) -> EngineState:
    """Replicate one (scalar, no-S-axis) state across n_streams streams."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_streams,) + a.shape), state
    )


def stream_slice(state: EngineState, s: int) -> EngineState:
    """Extract stream ``s`` as a scalar (axis-free) state."""
    return jax.tree.map(lambda a: a[s], state)


# -- stacked-state helpers (cohort fusion, engine/cohort.py) ----------------
#
# A cohort stacks N same-shaped tenants' EngineStates along the leading
# stream axis (tenant axis folded onto S) so one fused plan/learn dispatch
# advances all of them.  Every per-stream op in this module is elementwise
# or einsum-batched over S, so row r of a stacked dispatch is bit-for-bit
# row r of the corresponding solo dispatch — the property the cohort
# engine's solo-parity guarantee rests on (locked by tests/test_cohort.py).


def stack_streams(states: list[EngineState]) -> EngineState:
    """Concatenate fleets along the leading stream axis."""
    return jax.tree.map(lambda *ls: jnp.concatenate(ls, axis=0), *states)


def slice_streams(state: EngineState, lo: int, hi: int) -> EngineState:
    """Extract the ``[lo:hi]`` stream window (one cohort member's rows)."""
    return jax.tree.map(lambda a: a[lo:hi], state)


@functools.lru_cache(maxsize=RUNNER_CACHE_SIZE)
def _patch_learn_runner(cfg: EngineConfig, lo: int, hi: int, donate: bool):
    """Learn on one member's ``[lo:hi]`` row window of a stacked cohort
    state, in place: slice the window out, run the member-width ``learn``,
    and scatter the updated P/beta/ladder rows back with ``.at[lo:hi]`` —
    donation keeps the full-width buffers in place, so a straggler reply
    (a ticket asked before its tenant joined the cohort, or before a
    resize) costs one member-width update, not a full-width one.  Rows
    outside the window are untouched, so this is bit-for-bit the solo
    ``learn`` on those rows."""

    def run_patch(elm, prune, drift, meter, h, labels, pred, conf, mask,
                  controller_on, theta):
        sub = EngineState(
            elm=jax.tree.map(lambda a: a[lo:hi], elm),
            prune=jax.tree.map(lambda a: a[lo:hi], prune),
            drift=jax.tree.map(lambda a: a[lo:hi], drift),
            meter=jax.tree.map(lambda a: a[lo:hi], meter),
        )
        new_sub = learn(
            sub, h, labels, pred, conf, mask, controller_on, cfg, theta=theta
        )
        new_elm = jax.tree.map(
            lambda full, part: full.at[lo:hi].set(part), elm, new_sub.elm
        )
        new_prune = jax.tree.map(
            lambda full, part: full.at[lo:hi].set(part), prune, new_sub.prune
        )
        return new_elm, new_prune

    return jax.jit(run_patch, donate_argnums=(0, 1) if donate else ())


def _tree_where(cond: jnp.ndarray, a, b):
    """Per-stream select between two pytrees of (S,)-leading leaves."""
    return jax.tree.map(
        lambda x, y: jnp.where(cond.reshape(cond.shape + (1,) * (x.ndim - cond.ndim)), x, y),
        a,
        b,
    )


def _predict(state: EngineState, x: jnp.ndarray, cfg: EngineConfig):
    """Fleet predict: hidden projection once, per-stream readout via einsum."""
    h = oselm.hidden(x, cfg.elm)  # (S, N)
    o = jnp.einsum("sn,snm->sm", h, state.elm.beta)  # (S, m)
    return h, jnp.argmax(o, axis=-1), o


class PlanOutput(NamedTuple):
    """Everything the first half of a tick produces — including what must
    survive the teacher round-trip so ``learn`` can apply labels later."""

    h: jnp.ndarray  # (S, N) hidden activations at query time
    pred: jnp.ndarray  # (S,) int32 local prediction c
    outputs: jnp.ndarray  # (S, m) raw outputs O
    confidence: jnp.ndarray  # (S,) f32 p1 - p2
    queried: jnp.ndarray  # (S,) bool — streams shipping feats to the teacher
    controller_on: jnp.ndarray  # (S,) bool — ladder observes this tick
    theta: jnp.ndarray  # (S,) f32 threshold in force this tick
    mode_training: jnp.ndarray  # (S,) bool


def plan(
    state: EngineState,
    x: jnp.ndarray,  # (S, n_in)
    cfg: EngineConfig,
    mode: str = "algo1",
    teacher_available: Optional[jnp.ndarray] = None,  # (S,) bool
    drift_active: Optional[jnp.ndarray] = None,  # (S,) bool (train_phase only)
) -> tuple[EngineState, PlanOutput]:
    """Teacher-facing half of one tick: predict → confidence → drift →
    should_query, charge the comm meter for issued queries, and account the
    pruning ladder's SKIP events (streams the controller observes but that
    do not query — their success/streak transition needs no label).

    ``elm`` passes through untouched; the committed state advances drift,
    the per-phase counter reset on a drift rising edge, skip accounting,
    and the meter.  Queried streams' ladder transitions wait for ``learn``.

    Counter semantics under label loss: the meter charges bytes for every
    *issued* query here, while ``prune.queries`` counts only *answered*
    queries (incremented in ``learn``) — with a lossy teacher the two
    deliberately diverge (``StreamStats.queries_issued`` tracks the former;
    ``comm_volume_fraction`` reflects queries the controller observed).
    """
    if mode not in ("algo1", "train_phase", "serve"):
        raise ValueError(f"unknown engine mode {mode!r}")
    n_streams = x.shape[0]
    if teacher_available is None:
        teacher_available = jnp.ones((n_streams,), jnp.bool_)

    h, c, o = _predict(state, x, cfg)
    conf = pruning.confidence(o)

    if mode == "serve":
        # ``gate`` semantics for the streaming/multiplexed serving path:
        # the drift detector runs live and a drifting stream is forced to
        # query (the paper's pruning condition 2), the controller is always
        # armed, and there is no training-mode gating — exactly the
        # decision logic of ``gate``, so ``plan(mode='serve')`` + ``learn``
        # is bit-for-bit ``gate`` + ``apply_labels``.
        s = drift_mod.score(x, o, cfg.drift)
        new_drift = drift_mod.update(state.drift, s, cfg.drift)
        training = jnp.ones((n_streams,), jnp.bool_)
        prune_st = state.prune
        want_query = pruning.should_query(
            prune_st, o, state.elm.count, new_drift.active, cfg.prune
        )
        queried = want_query & teacher_available
        controller_on = teacher_available
    elif mode == "algo1":
        # IsDrift / IsTrainDone: per-stream detector with hysteresis.
        s = drift_mod.score(x, o, cfg.drift)  # (S,)
        new_drift = drift_mod.update(state.drift, s, cfg.drift)
        training = new_drift.active
        # Rising edge == IsDrift fired: re-arm the per-phase counter.
        entering = jnp.logical_and(training, jnp.logical_not(state.drift.active))
        prune_st = _tree_where(entering, pruning.reset_phase(state.prune), state.prune)
        want_query = pruning.should_query(
            prune_st, o, state.elm.count, jnp.zeros((n_streams,), jnp.bool_), cfg.prune
        )
        queried = training & want_query & teacher_available
        # Auto-theta only observes training-mode steps with a live teacher.
        controller_on = training & teacher_available
    else:
        if drift_active is None:
            drift_active = jnp.zeros((n_streams,), jnp.bool_)
        new_drift = state.drift
        training = jnp.ones((n_streams,), jnp.bool_)
        prune_st = state.prune
        want_query = pruning.should_query(
            prune_st, o, state.elm.count, drift_active, cfg.prune
        )
        queried = want_query & teacher_available
        controller_on = teacher_available

    theta = pruning.theta_of(prune_st, cfg.prune)
    meter = state.meter.charge_query(x.shape[-1], queried)
    # Skip accounting happens now: a skipped sample's ladder transition uses
    # only (conf > theta), never the teacher's answer (pruning.update with
    # queried=False ignores ``agree``), so it must not wait for the label.
    off = jnp.zeros((n_streams,), jnp.bool_)
    new_prune = _tree_where(
        controller_on & jnp.logical_not(queried),
        pruning.update(prune_st, off, off, conf, cfg.prune),
        prune_st,
    )

    new_state = sharding.constrain_fleet(
        EngineState(elm=state.elm, prune=new_prune, drift=new_drift, meter=meter)
    )
    out = PlanOutput(
        h=h,
        pred=c,
        outputs=o,
        confidence=conf,
        queried=queried,
        controller_on=controller_on,
        theta=theta,
        mode_training=training,
    )
    return new_state, out


def learn(
    state: EngineState,
    h: jnp.ndarray,  # (S, N) hidden activations captured at plan time
    labels: jnp.ndarray,  # (S,) int32 teacher answers (valid where mask)
    pred: jnp.ndarray,  # (S,) int32 plan-time local predictions
    confidence: jnp.ndarray,  # (S,) f32 plan-time P1P2 confidence
    mask: jnp.ndarray,  # (S,) bool — answered queries to apply
    controller_on: jnp.ndarray,  # (S,) bool — plan-time controller gate
    cfg: EngineConfig,
    theta: Optional[jnp.ndarray] = None,  # (S,) plan-time threshold
) -> EngineState:
    """Deferred half of a tick: masked rank-1 RLS on the teacher's answers
    plus the auto-theta ladder transition for the answered queries.

    ``h`` / ``pred`` / ``confidence`` / ``theta`` are the plan-time values,
    so a label arriving ticks later (or out of order) still trains on the
    features it was asked about and is judged against the threshold the
    query decision used — a disagreement on a low-confidence query steps
    theta up even if other ticks moved the ladder while the answer was in
    flight.  A stream outside ``mask`` is an exact identity.
    """
    y = labels_mod.one_hot(labels, cfg.elm.n_out)  # (S, m)
    agree = pred == labels
    new_elm = oselm.fleet_rank1_update_h(
        state.elm, h, y, cfg.elm, mask=mask.astype(jnp.float32)
    )
    new_prune = _tree_where(
        controller_on & mask,
        pruning.update(state.prune, mask, agree, confidence, cfg.prune, theta=theta),
        state.prune,
    )
    return sharding.constrain_fleet(
        state._replace(elm=new_elm, prune=new_prune)
    )


def learn_rows(
    state: EngineState,
    rows: Optional[jnp.ndarray],  # (K,) int32 sorted unique row index, or None
    h: jnp.ndarray,
    labels: jnp.ndarray,
    pred: jnp.ndarray,
    confidence: jnp.ndarray,
    mask: jnp.ndarray,
    controller_on: jnp.ndarray,
    cfg: EngineConfig,
    theta: Optional[jnp.ndarray] = None,
) -> EngineState:
    """``learn`` over the rows ``rows`` alone (``learn`` itself when
    ``rows`` is None).

    Gathers the K rows of every ``elm`` and ``prune`` leaf and of every
    per-row argument, runs ``learn`` on that K-row state, and scatters the
    updated rows back.  ``rows`` must be sorted, unique and in range, and
    hold every row of ``mask`` (``stream.learn_rows_index`` builds it); a
    row of ``rows`` outside ``mask`` learns as the identity ``learn`` makes
    it, so it is written back as it was.  Row r of the result is therefore
    row r of ``learn``'s, while a donated caller reads and writes K rows of
    P instead of S.
    """
    if rows is None:
        return learn(
            state, h, labels, pred, confidence, mask, controller_on, cfg, theta=theta
        )
    at = dict(indices_are_sorted=True, unique_indices=True, mode="promise_in_bounds")

    def take(a):
        return a.at[rows].get(**at)

    def put(full, part):
        return full.at[rows].set(part, **at)

    sub = state._replace(
        elm=jax.tree.map(take, state.elm), prune=jax.tree.map(take, state.prune)
    )
    new = learn(
        sub, take(h), take(labels), take(pred), take(confidence), take(mask),
        take(controller_on), cfg, theta=None if theta is None else take(theta),
    )
    return state._replace(
        elm=jax.tree.map(put, state.elm, new.elm),
        prune=jax.tree.map(put, state.prune, new.prune),
    )


def fleet_step(
    state: EngineState,
    x: jnp.ndarray,  # (S, n_in)
    labels: jnp.ndarray,  # (S,) int32 teacher answers (used only where queried)
    cfg: EngineConfig,
    mode: str = "algo1",
    teacher_available: Optional[jnp.ndarray] = None,  # (S,) bool
    drift_active: Optional[jnp.ndarray] = None,  # (S,) bool (train_phase only)
) -> tuple[EngineState, FleetStepOutput]:
    """One fused tick for all S streams — ``learn`` composed directly on
    ``plan`` (a zero-latency teacher).  Semantics per stream are exactly the
    scalar Algorithm-1 ``step`` (mode='algo1') / §3 retraining
    ``train_phase_step`` (mode='train_phase') of ``engine/scalar.py``.
    """
    state, p = plan(
        state, x, cfg, mode=mode,
        teacher_available=teacher_available, drift_active=drift_active,
    )
    state = learn(
        state, p.h, labels, p.pred, p.confidence, p.queried, p.controller_on, cfg,
        theta=p.theta,
    )
    out = FleetStepOutput(
        pred=p.pred,
        outputs=p.outputs,
        queried=p.queried,
        trained=p.queried,
        theta=p.theta,
        confidence=p.confidence,
        mode_training=p.mode_training,
    )
    return state, out


def fleet_accuracy(
    state: EngineState,
    xs: jnp.ndarray,  # (B, n_in) shared test batch
    ys: jnp.ndarray,  # (B,) int32
    cfg: EngineConfig,
) -> jnp.ndarray:
    """Per-stream test accuracy of every head against one shared batch:
    one hidden projection, per-stream readout via einsum — returns (S,)."""
    h = oselm.hidden(xs, cfg.elm)  # (B, N)
    o = jnp.einsum("bn,snm->sbm", h, state.elm.beta)  # (S, B, m)
    preds = jnp.argmax(o, axis=-1)  # (S, B)
    return jnp.mean((preds == ys[None, :]).astype(jnp.float32), axis=-1)


# ---------------------------------------------------------------------------
# Chunked time scan
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=RUNNER_CACHE_SIZE)
def _chunk_runner(cfg: EngineConfig, mode: str, donate: bool):
    """One compiled executable per (cfg, mode, chunk shape): scans fleet_step
    over a (chunk, S) block of ticks.  Cached (bounded LRU — a long-lived
    server must not leak one executable per retired config) so chunk
    boundaries reuse the same jitted function, and the state argument is
    donated so P/beta update in place on accelerators."""

    def run_chunk(state, xs, labels, avail):
        def body(st, inp):
            x_t, lab_t, av_t = inp
            return fleet_step(st, x_t, lab_t, cfg, mode=mode, teacher_available=av_t)

        return jax.lax.scan(body, state, (xs, labels, avail))

    return jax.jit(run_chunk, donate_argnums=(0,) if donate else ())


def runner_cache_info() -> dict:
    """Hit/miss/size counters of the compiled-runner cache, for serving
    stats (``engine.stream.cache_stats`` merges these with its own)."""
    out = {}
    for name, fn in (
        ("chunk_runner", _chunk_runner),
        ("patch_learn_runner", _patch_learn_runner),
    ):
        info = fn.cache_info()
        out[name] = {
            "hits": info.hits,
            "misses": info.misses,
            "size": info.currsize,
            "maxsize": info.maxsize,
        }
    return out


def run_fleet(
    state: EngineState,
    xs: jnp.ndarray,  # (T, S, n_in)
    labels: jnp.ndarray,  # (T, S) int32
    cfg: EngineConfig,
    mode: str = "algo1",
    teacher_available: Optional[jnp.ndarray] = None,  # (T, S) bool
    chunk: Optional[int] = None,
    donate: Optional[bool] = None,
) -> tuple[EngineState, FleetStepOutput]:
    """Run T ticks of S streams through the engine, ``chunk`` ticks per
    dispatch.  Returns (final state, outputs stacked over (T, S)).

    ``donate`` defaults to True off-CPU.  On CPU it defaults to False so
    ad-hoc callers may keep using the input state after the call — but CPU
    donation *does* alias buffers in-place (no copy, no warning), and at
    mega-fleet sizes the non-donated path is dominated by page-zeroing
    churn on the ~16 KB/stream P re-allocation (sys-time, not compute).
    Resident callers that own their state (``run_fleet_sharded``,
    ``run_fleet_shards``, the streaming runtime) pass ``donate=True``
    explicitly and get ~2.7x on CPU at S=65,536.

    When T is a multiple of ``chunk`` every dispatch hits the same compiled
    executable; a ragged final chunk costs exactly one extra compile.
    """
    t_total = xs.shape[0]
    if t_total == 0:
        s = xs.shape[1]
        m = cfg.elm.n_out
        empty = FleetStepOutput(
            pred=jnp.zeros((0, s), jnp.int32),
            outputs=jnp.zeros((0, s, m), jnp.float32),
            queried=jnp.zeros((0, s), jnp.bool_),
            trained=jnp.zeros((0, s), jnp.bool_),
            theta=jnp.zeros((0, s), jnp.float32),
            confidence=jnp.zeros((0, s), jnp.float32),
            mode_training=jnp.zeros((0, s), jnp.bool_),
        )
        return state, empty
    if chunk is None or chunk > t_total:
        chunk = t_total
    if donate is None:
        donate = jax.default_backend() != "cpu"
    if teacher_available is None:
        teacher_available = jnp.ones(xs.shape[:2], jnp.bool_)

    runner = _chunk_runner(cfg, mode, donate)
    outs = []
    t = 0
    while t < t_total:
        c = min(chunk, t_total - t)
        state, out = runner(
            state, xs[t : t + c], labels[t : t + c], teacher_available[t : t + c]
        )
        outs.append(out)
        t += c
    if len(outs) == 1:
        return state, outs[0]
    return state, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *outs)


# ---------------------------------------------------------------------------
# Serving entry points: one tick split at the teacher round-trip.
# ---------------------------------------------------------------------------


class GateOutput(NamedTuple):
    """Plan-time decision context of one serving tick.

    Everything ``apply_labels`` needs to judge a teacher answer that comes
    back ticks later: the hidden activations the query trained on, the
    local prediction/confidence the agreement check compares against, and
    the threshold the query decision was made under.  Mirrors
    ``PlanOutput`` for the ``gate``/``apply_labels`` serving split.
    """

    h: jnp.ndarray  # (S, N) hidden activations at query time
    pred: jnp.ndarray  # (S,) int32 local prediction c
    outputs: jnp.ndarray  # (S, m) raw outputs O
    confidence: jnp.ndarray  # (S,) f32 p1 - p2 at query time
    queried: jnp.ndarray  # (S,) bool — streams shipping feats to the teacher
    theta: jnp.ndarray  # (S,) f32 threshold in force at query time
    feats: jnp.ndarray  # (S, n_in) the raw features (for a real teacher RPC)
    drift_active: jnp.ndarray  # (S,) bool


def gate(
    state: EngineState,
    x: jnp.ndarray,  # (S, n_in) features, one per stream
    cfg: EngineConfig,
) -> tuple[EngineState, GateOutput]:
    """Predict + decide which streams must consult the teacher.

    Runs the drift detector (a drifting stream is forced to query — the
    paper's pruning condition 2), charges the comm meter for issued
    queries, and accounts the ladder's skip events for the non-querying
    streams (same split as ``plan``/``learn``: skip transitions belong to
    decision time, query transitions to answer time — so applying several
    deferred replies in one tick cannot multiply skip counts).  Labels
    arrive later via ``apply_labels``, which takes the returned
    ``GateOutput`` so delayed replies are judged against *this* tick's
    prediction/confidence/theta, not whatever the weights say by the time
    the answer lands.
    """
    h, c, o = _predict(state, x, cfg)
    conf = pruning.confidence(o)
    s = drift_mod.score(x, o, cfg.drift)
    new_drift = drift_mod.update(state.drift, s, cfg.drift)
    query_mask = pruning.should_query(
        state.prune, o, state.elm.count, new_drift.active, cfg.prune
    )
    theta = pruning.theta_of(state.prune, cfg.prune)
    meter = state.meter.charge_query(x.shape[-1], query_mask)
    off = jnp.zeros_like(query_mask)
    new_prune = _tree_where(
        jnp.logical_not(query_mask),
        pruning.update(state.prune, off, off, conf, cfg.prune),
        state.prune,
    )
    new_state = sharding.constrain_fleet(
        state._replace(drift=new_drift, meter=meter, prune=new_prune)
    )
    out = GateOutput(
        h=h,
        pred=c,
        outputs=o,
        confidence=conf,
        queried=query_mask,
        theta=theta,
        feats=x,
        drift_active=new_drift.active,
    )
    return new_state, out


def apply_labels(
    state: EngineState,
    ctx: Union[GateOutput, PlanOutput],
    labels: jnp.ndarray,  # (S,) int32 teacher answers (valid where mask)
    mask: jnp.ndarray,  # (S,) bool — streams whose teacher answered
    cfg: EngineConfig,
) -> EngineState:
    """Asynchronous label application: masked rank-1 RLS + auto-theta step.

    ``ctx`` is the ``GateOutput`` (or ``PlanOutput``) captured when the
    query was issued: the RLS update trains on the plan-time ``h`` and the
    ladder judges agreement against the plan-time ``pred``/``confidence``
    under the plan-time ``theta`` — exactly like ``learn``.  Recomputing
    those from the *current* state (the pre-ISSUE-3 behavior, removed in
    ISSUE 4) is wrong with a laggy teacher: weights updated while the
    answer was in flight change the prediction, so the agree/confidence
    judgment no longer describes the decision the query belongs to.

    Only the answered streams (``mask``) transition the ladder — the skip
    accounting for everyone else already happened in ``gate`` — so calling
    this once per arrived reply (zero, one, or many per tick, depending on
    teacher latency) keeps per-tick controller semantics.
    """
    if not isinstance(ctx, (GateOutput, PlanOutput)):
        raise TypeError(
            "apply_labels needs the plan-time decision context: pass the "
            "GateOutput returned by gate() (or a PlanOutput from plan()). "
            "The raw-features recompute path was removed — it judged "
            "delayed replies against the *current* weights (stale-reply "
            f"semantics). Got {type(ctx).__name__}."
        )
    h, pred, conf, theta = ctx.h, ctx.pred, ctx.confidence, ctx.theta
    agree = pred == labels
    y = labels_mod.one_hot(labels, cfg.elm.n_out)
    new_elm = oselm.fleet_rank1_update_h(
        state.elm, h, y, cfg.elm, mask=mask.astype(jnp.float32)
    )
    new_prune = _tree_where(
        mask,
        pruning.update(state.prune, mask, agree, conf, cfg.prune, theta=theta),
        state.prune,
    )
    return sharding.constrain_fleet(
        state._replace(elm=new_elm, prune=new_prune)
    )


# ---------------------------------------------------------------------------
# Mesh-sharded fleets: the stream axis over a ("fleet",) device mesh.
# ---------------------------------------------------------------------------
#
# Every per-stream op above is elementwise or einsum-batched over S with all
# contractions on unsharded dims (n_in / n_hidden), so splitting the stream
# axis — whether by GSPMD partitioning one dispatch (``shard_fleet`` +
# ``run_fleet_sharded``) or by explicit per-shard dispatches
# (``split_fleet`` + ``run_fleet_shards``) — is bit-for-bit the unsharded
# run row-for-row, with zero cross-shard communication on the hot path
# (locked by tests/test_mesh_fleet.py).

# Streams per block for the explicit shard-local path: P is ~16 KB/stream,
# so 512-stream blocks keep each block's working set (~8 MB of P) inside a
# host L3 across the whole T-tick scan instead of streaming GBs per tick.
DEFAULT_STREAM_BLOCK = 512


def pad_streams(state: EngineState, cfg: EngineConfig, n_pad: int) -> EngineState:
    """Append ``n_pad`` fresh-init dead rows to a fleet (padding S up to a
    multiple of the shard count).  Dead rows are driven with
    ``teacher_available=False`` so they never query or learn; callers meter
    them (bench/stream stats report ``padded_streams``) and strip their
    rows from outputs."""
    if n_pad <= 0:
        return state
    return stack_streams([state, init_fleet(cfg, n_pad)])


def shard_fleet(
    state: EngineState, cfg: EngineConfig, mesh=None
) -> tuple[EngineState, int]:
    """GSPMD placement: pad S to a multiple of the mesh's fleet-axis size
    and ``device_put`` every leaf with a ``NamedSharding`` splitting its
    leading axis over the ``stream`` rule.  Returns ``(placed_state,
    n_pad)``.  Identity (and ``n_pad=0``) with no mesh.

    The placed state is meant to stay *resident*: advance it with
    ``run_fleet_sharded`` (donated dispatches keep P/beta updating in place
    per shard) and only pull it off the mesh at checkpoint time.
    """
    if mesh is not None and mesh is not sharding.mesh_or_none():
        with sharding.activate(mesh):
            return shard_fleet(state, cfg)
    if sharding.mesh_or_none() is None:
        return state, 0
    n_shards = sharding.fleet_axis_size()
    s = jax.tree.leaves(state)[0].shape[0]
    n_pad = (-s) % n_shards
    state = pad_streams(state, cfg, n_pad)
    return (
        jax.tree.map(
            lambda a: jax.device_put(a, sharding.fleet_sharding(a.ndim, a.shape)),
            state,
        ),
        n_pad,
    )


def run_fleet_sharded(
    state: EngineState,  # shard_fleet-placed (possibly padded) fleet
    xs: jnp.ndarray,  # (T, S_real, n_in)
    labels: jnp.ndarray,  # (T, S_real) int32
    cfg: EngineConfig,
    mode: str = "algo1",
    teacher_available: Optional[jnp.ndarray] = None,  # (T, S_real) bool
    chunk: Optional[int] = None,
) -> tuple[EngineState, FleetStepOutput]:
    """Advance a ``shard_fleet``-placed fleet by donated full-width
    dispatches; XLA partitions each dispatch over the mesh (state stays
    resident per shard, inputs are staged with matching shardings so no
    resharding happens inside the step).  Inputs are in *real* (unpadded)
    width: dead rows are appended here with ``teacher_available=False`` and
    stripped from the returned outputs, so callers never see padding.
    """
    s_pad = jax.tree.leaves(state)[0].shape[0]
    t, s_real = xs.shape[0], xs.shape[1]
    if teacher_available is None:
        teacher_available = jnp.ones((t, s_real), jnp.bool_)
    if s_pad != s_real:
        pad = s_pad - s_real
        if pad < 0:
            raise ValueError(f"state has {s_pad} streams < input width {s_real}")
        xs = jnp.concatenate(
            [xs, jnp.zeros((t, pad) + xs.shape[2:], xs.dtype)], axis=1
        )
        labels = jnp.concatenate([labels, jnp.zeros((t, pad), labels.dtype)], axis=1)
        teacher_available = jnp.concatenate(
            [teacher_available, jnp.zeros((t, pad), jnp.bool_)], axis=1
        )
    if sharding.mesh_or_none() is not None:

        def put(a):
            ns = sharding.named_sharding(
                None, "stream", *((None,) * (a.ndim - 2)), shape=a.shape
            )
            return jax.device_put(a, ns)

        xs, labels = put(xs), put(labels)
        teacher_available = put(teacher_available)
    state, out = run_fleet(
        state, xs, labels, cfg, mode=mode,
        teacher_available=teacher_available, chunk=chunk, donate=True,
    )
    if s_pad != s_real:
        out = jax.tree.map(lambda a: a[:, :s_real], out)
    return state, out


class FleetShards(NamedTuple):
    """Explicit shard-local layout: the fleet split into per-block states,
    shard k's blocks resident on mesh device k.

    Where ``shard_fleet`` hands one logical array to GSPMD, this layout
    makes the no-communication structure literal — each block is advanced
    by its own donated block-width dispatch, so a shard's P/beta never
    leave its device and (on cache-starved hosts) each block's working set
    stays L3-resident across the T-tick scan.  The streaming runtime's
    per-shard pending rings (``stream.ShardedStreamSession``) use the same
    row partition.
    """

    states: tuple  # per-block EngineState, block b on its shard's device
    bounds: tuple  # per-block (lo, hi) row window in the padded fleet
    n_pad: int  # dead rows appended to the tail (never surfaced in outputs)


def split_fleet(
    state: EngineState,
    cfg: EngineConfig,
    n_shards: Optional[int] = None,
    block: Optional[int] = None,
    devices=None,
) -> FleetShards:
    """Split a fleet into ``FleetShards``: pad S to a multiple of
    ``n_shards`` (default: the active mesh's fleet-axis size, 1 with no
    mesh), sub-divide each shard into ``block``-stream blocks (default
    ``DEFAULT_STREAM_BLOCK``, capped at the shard width), and place shard
    k's blocks on ``devices[k]`` (default: the active mesh's devices, else
    everything stays on the default device)."""
    if n_shards is None:
        n_shards = sharding.fleet_axis_size()
    if devices is None:
        mesh = sharding.mesh_or_none()
        if mesh is not None:
            devices = list(mesh.devices.flat)
    if devices is not None and len(devices) < n_shards:
        raise ValueError(f"{n_shards} shards > {len(devices)} devices")
    s = jax.tree.leaves(state)[0].shape[0]
    n_pad = (-s) % n_shards
    state = pad_streams(state, cfg, n_pad)
    width = (s + n_pad) // n_shards
    if block is None:
        block = DEFAULT_STREAM_BLOCK
    block = max(1, min(block, width))
    states, bounds = [], []
    for k in range(n_shards):
        dev = devices[k] if devices is not None else None
        lo = k * width
        while lo < (k + 1) * width:
            hi = min(lo + block, (k + 1) * width)
            sub = slice_streams(state, lo, hi)
            if dev is not None:
                sub = jax.device_put(sub, dev)
            states.append(sub)
            bounds.append((lo, hi))
            lo = hi
    return FleetShards(states=tuple(states), bounds=tuple(bounds), n_pad=n_pad)


def merge_fleet(shards: FleetShards) -> EngineState:
    """Reassemble one host-side fleet from shard-local blocks, stripping
    the dead-row padding (checkpoint/inspection path — the hot path never
    gathers)."""
    full = stack_streams([jax.device_get(st) for st in shards.states])
    s = jax.tree.leaves(full)[0].shape[0]
    if shards.n_pad:
        full = slice_streams(full, 0, s - shards.n_pad)
    return full


def run_fleet_shards(
    shards: FleetShards,
    xs: jnp.ndarray,  # (T, S_real, n_in)
    labels: jnp.ndarray,  # (T, S_real) int32
    cfg: EngineConfig,
    mode: str = "algo1",
    teacher_available: Optional[jnp.ndarray] = None,  # (T, S_real) bool
    chunk: Optional[int] = None,
) -> tuple[FleetShards, FleetStepOutput]:
    """Advance every block of a ``FleetShards`` by shard-local donated
    dispatches and restitch the outputs in row order.  Bit-for-bit the
    unsharded ``run_fleet`` at equal S (row independence — see the module
    banner); dead tail rows run with ``teacher_available=False`` and are
    stripped from the outputs.

    Block dispatches are shard-LOCAL (each block's state lives on one
    device), so the whole loop runs under ``sharding.deactivate()`` — a
    caller's multi-device mesh scope must not leak in, or the step's
    ``constrain_fleet`` would demand the full device set for
    single-device operands."""
    t, s_real = xs.shape[0], xs.shape[1]
    if teacher_available is None:
        teacher_available = jnp.ones((t, s_real), jnp.bool_)
    with sharding.deactivate():
        return _run_fleet_shards_body(
            shards, xs, labels, cfg, mode, teacher_available, chunk)


# odlint: shard-local
def _run_fleet_shards_body(
    shards, xs, labels, cfg, mode, teacher_available, chunk
) -> tuple[FleetShards, FleetStepOutput]:
    t, s_real = xs.shape[0], xs.shape[1]
    new_states, outs = [], []
    for st, (lo, hi) in zip(shards.states, shards.bounds):
        dev = None
        leaf = jax.tree.leaves(st)[0]
        if hasattr(leaf, "devices"):
            (dev,) = leaf.devices()
        real_hi = max(lo, min(hi, s_real))  # block may sit wholly in padding
        n_dead = hi - real_hi
        x_b = xs[:, lo:real_hi]
        lab_b = labels[:, lo:real_hi]
        av_b = teacher_available[:, lo:real_hi]
        if n_dead:
            x_b = jnp.concatenate(
                [x_b, jnp.zeros((t, n_dead) + xs.shape[2:], xs.dtype)], axis=1
            )
            lab_b = jnp.concatenate(
                [lab_b, jnp.zeros((t, n_dead), labels.dtype)], axis=1
            )
            av_b = jnp.concatenate(
                [av_b, jnp.zeros((t, n_dead), jnp.bool_)], axis=1
            )
        if dev is not None:
            x_b, lab_b, av_b = (
                jax.device_put(x_b, dev),
                jax.device_put(lab_b, dev),
                jax.device_put(av_b, dev),
            )
        st, out = run_fleet(
            st, x_b, lab_b, cfg, mode=mode,
            teacher_available=av_b, chunk=chunk, donate=True,
        )
        if n_dead:
            keep = out.pred.shape[1] - n_dead
            out = jax.tree.map(lambda a: a[:, :keep], out)
        new_states.append(st)
        outs.append(out)
    merged = jax.tree.map(
        lambda *a: jnp.concatenate([jax.device_get(x) for x in a], axis=1), *outs
    )
    return shards._replace(states=tuple(new_states)), merged
