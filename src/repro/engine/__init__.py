"""repro.engine — fleet-scale ODL: Algorithm 1 batched over streams.

This package is the single owner of the paper's ODL state machine (OS-ELM
+ P1P2 auto-pruning + drift gating) at every scale: the S=1 paper repro
(``engine.scalar``, re-exported by the deprecated ``core/odl_head.py``
alias), the offline batched fleet (``run_fleet``), and the online
streaming deployment with a laggy teacher (``engine.stream``).

State layout
------------
``EngineState`` (``engine/types.py``) is a single pytree with a leading
stream axis ``S`` on every leaf::

    EngineState
    ├── elm:   OSELMState   beta (S, N, m) · P (S, N, N) · count (S,)
    ├── prune: PruneState   level/streak/queries/skips/phase_trained (S,)
    ├── drift: DriftState   mean/var/steps/hits/calm/active (S,)
    └── meter: CommMeter    up_bytes/down_bytes (S,)

One tick, split at the teacher round-trip
-----------------------------------------
``plan(state, x: (S, n_in))`` performs predict → confidence → drift update
→ should_query for all S streams (one hidden-projection matmul, everything
else elementwise), charges the comm meter, and accounts the pruning
ladder's skip events.  ``learn(state, h, labels, pred, conf, mask, ...)``
later applies teacher answers: masked einsum-batched rank-1 Woodbury RLS
(optionally the fused Pallas kernel via ``cfg.elm.use_kernel``) plus the
ladder transition for the answered queries — against the *plan-time*
features, so answers may arrive ticks later and out of order.
``fleet_step`` is exactly ``learn`` composed on ``plan`` (a zero-latency
teacher) and stays the offline single-dispatch tick.

Chunked time scan
-----------------
``run_fleet(state, xs: (T, S, n_in), labels: (T, S))`` scans ``fleet_step``
over time inside jit, in chunks of ``chunk`` ticks: a Python loop dispatches
one donated jit call per chunk (``donate_argnums=0`` — P, the dominant
buffer at S·N²·4 bytes, is updated in place on TPU), and each chunk's
compiled executable is cached per ``(cfg, mode, chunk shape)`` in a
*bounded* LRU (``fleet.RUNNER_CACHE_SIZE``; hit/miss counters via
``fleet.runner_cache_info`` / ``stream.cache_stats``) so chunk boundaries
never recompile and long-lived servers never leak executables.

Streaming runtime & teacher protocol
------------------------------------
``stream.run(state, ticks, cfg, teacher)`` drives the same state machine
from an *iterator* of (S, n_in) ticks — nothing materializes over T.  A
``stream.Teacher`` (``ask(feats, mask, tick) -> ticket`` /
``poll(tick) -> [TeacherReply]`` / ``in_flight()``) answers with real
latency; ``stream.LatencyTeacher`` models latency, jitter, loss, and
permanent outage.  In-flight tickets wait in a fixed-capacity
``PendingRing`` (overflow drops the oldest, metered), answers apply out of
order through masked ``learn`` — over the answered rows alone when they
fit a compact row capacity (``fleet.learn_rows``, ``stream.learn_rows_index``),
so P traffic follows the query rate — and host ingestion of tick t+1 overlaps
device compute of tick t (double buffering).  ``StreamStats`` reports
p50/p95 tick latency, label latency in ticks, and drop/orphan/loss
counters.  With a zero-latency teacher the runtime reproduces
``run_fleet`` bit-for-bit (locked by ``tests/test_stream.py``).

Sharding
--------
Every step constrains the leading axis of all state leaves to the
``"stream"`` logical axis (``distributed/sharding.py``), which the default
rule table maps to ``("pod", "data")`` — under an active mesh the fleet
splits across devices with zero cross-stream communication.

Modes
-----
* ``mode="algo1"``       — the paper's full Algorithm 1: the per-stream
  drift detector switches predicting ↔ training; queries only happen in
  training mode.
* ``mode="train_phase"`` — the §3 evaluation protocol: an explicit
  retraining phase, pruning always armed, optional per-stream
  ``teacher_available`` outage modelling.
* ``mode="serve"``       — the serving cascade: live drift detector
  (a drifting stream is forced to query — pruning condition 2), controller
  always armed, no training-mode gating.  Exactly the ``gate`` decision
  logic, so ``plan(mode='serve')``/``learn`` and ``gate``/``apply_labels``
  are the same state machine (``launch/serve.py`` multiplexes the former;
  ``models/model.py``'s fused decode step uses the latter).

Multi-tenant multiplexer & backpressure
---------------------------------------
``multiplex.run(tenants)`` serves N independent fleets — each a
``multiplex.Tenant`` with its own config, state, tick source, ``Teacher``,
pending ring, and *backpressure policy* — from one process, round-robin
with a ``quantum``-tick time slice (cache locality; results are
quantum-invariant).  Tenants with the same ``(cfg, mode,
donate)`` share a compiled executable through the bounded runner LRUs, so
a tenant using an already-served config costs no compile.  The pending
ring's saturation behavior is pluggable (``stream.BACKPRESSURE_POLICIES``):
``drop_oldest`` (evict, metered), ``drop_newest`` (refuse the new ask),
``block`` (defer the ask until a slot frees), and ``coalesce`` (merge a
re-querying stream into its in-flight ticket — no duplicate teacher
traffic).  Query accounting reconciles exactly: ``queries_issued ==
labels_applied + queries_dropped + queries_lost (+ queries_coalesced)``.
``engine.rpc.RpcTeacher`` speaks the same Teacher protocol over a real TCP
socket with timeout→loss mapping, so the latency model is no longer the
only teacher transport; ``engine.rpc.BatchedRpcClient`` shares **one**
such connection across all tenants of a teacher host, coalescing asks
that land within a flush window into single length-prefixed binary
frames (v2 wire format; v1 newline-JSON stays supported) and demuxing
replies to per-tenant ``BatchedRpcTeacher`` handles —
``multiplex.shared_rpc_teachers`` dedups endpoints into shared clients.

Serving entry points (``gate`` / ``apply_labels``) remain for callers that
carry their own features (``models/model.py``'s decode loop feeds backbone
hidden states): ``gate`` returns a ``GateOutput`` capturing the plan-time
decision context (h/pred/confidence/theta), and ``apply_labels`` judges
the — possibly delayed — teacher answer against exactly that context
(raw query-time features are rejected: recomputing the judgment from
current weights is stale-reply semantics), the same contract as
``plan``/``learn``.  ``launch/serve.py`` multiplexes N tenant fleets over
the decode loop with these same pieces.

Scheduling is round-robin by default; ``multiplex`` also offers deficit
round robin (``sched="drr"``) that charges each tick its stream count, so
an S=512 tenant cannot starve an S=16 one — per-tenant results are
bit-for-bit identical under either scheduler.

Durable sessions
----------------
On-device learned state is paid for in teacher-communication energy, so a
crash must not discard it.  ``engine/snapshot.py`` serializes a live
``StreamSession`` with full fidelity — ``EngineState``, the pending ring
with each ticket's plan-time context and raw features, backpressure-policy
state (deferred ``block`` asks; ``coalesce``'s merge map is the ring
masks), ``StreamStats``, the in-flight tick, the tick-source cursor, and
(when supported, e.g. ``LatencyTeacher``) the teacher's own state —
published atomically with keep-k GC through
``runtime.checkpoint.CheckpointManager``.  ``StreamSession.snapshot()`` /
``StreamSession.restore()`` are the session-level API; a restored run is
bit-for-bit the uninterrupted one under a deterministic snapshot-capable
teacher (``tests/test_snapshot.py``, every backpressure policy).  Teachers
that cannot be snapshot (``rpc.RpcTeacher`` — sockets) have their
in-flight tickets re-asked through the fresh connection and metered
(``tickets_reasked``), preserving the query-accounting identity.
``engine/durable.py`` drives a single durable session (and is the
kill-and-resume CI smoke: ``python -m repro.engine.durable
--crash-smoke``); ``multiplex.Multiplexer`` adds per-tenant cadence
snapshots + ``resume``, ``run_supervised`` wraps attempts in
``runtime.fault.run_with_restarts``, and ``extract``/``admit`` implement
live tenant migration (quiesce → snapshot → restore into another
multiplexer).  ``launch/serve.py`` exposes all of it
(``--snapshot-dir``/``--snapshot-every``/``--resume``/``--migrate``).
"""

from repro.engine.fleet import (  # noqa: F401
    EngineConfig,
    EngineState,
    FleetShards,
    FleetStepOutput,
    GateOutput,
    PlanOutput,
    apply_labels,
    broadcast_streams,
    fleet_accuracy,
    fleet_step,
    gate,
    init_fleet,
    init_state,
    learn,
    merge_fleet,
    pad_streams,
    plan,
    run_fleet,
    run_fleet_sharded,
    run_fleet_shards,
    runner_cache_info,
    shard_fleet,
    split_fleet,
    stream_slice,
)

# fleet must import first: its repro.core imports resolve the
# core -> odl_head(alias) -> engine.scalar cycle before scalar/stream load.
# (engine.durable and engine.rpc are importable leaves with CLIs — kept out
# of the package import so ``python -m repro.engine.durable`` stays clean.)
from repro.engine import multiplex, scalar, snapshot, stream  # noqa: E402,F401
