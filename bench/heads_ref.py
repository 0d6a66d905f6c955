"""Plain reference of the per-user head: OS-ELM with P1P2 auto-pruning.

Written from the paper (arXiv:2408.01283 §2) and the configuration file,
in NumPy, one row per user.  It imports nothing of the program and takes
nothing it made: the hash weights, the batch init and every state are its
own.

    h    = sigmoid(x alpha * scale / sqrt(n))        alpha from Xorshift16
    o    = h beta,  c = argmax o,  conf = clip(p1 - p2, 0, 1)
    query unless (count >= min_trained and no drift and conf > theta)
    P'   = P - P h h' P / (1 + h' P h),  beta' = beta + P' h (y - h' beta)
    theta steps down the ladder after X successes, up on a disagreement

The drift detector is the EWMA k-sigma test the configuration states.
``replay`` drives rows through a run's recorded teacher traffic: it plans
every tick itself, follows the asks and replies the run recorded (which
tickets were asked at which tick, which replies came back when), and
reports where its own ask decisions differ from the run's.  ``rnd`` rounds
every result; the reference leaves it at float64, the control rounds to a
lower precision.
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np

MIX = (0x2D2B, 0x9E35, 0xC2B3)


def identity(a):
    return a


def bf16(a):
    """Round to bfloat16 (the control's precision) and back to float64."""
    import ml_dtypes

    return np.asarray(a, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


@dataclasses.dataclass(frozen=True)
class Head:
    n_in: int
    n_hidden: int
    n_out: int
    hash_seed: int
    hash_rounds: int
    ridge: float
    alpha_scale: float
    ladder: tuple
    x_consec: int
    min_trained: int
    ewma_decay: float
    k_sigma: float
    warmup: int
    enter_hits: int
    exit_calm: int

    @staticmethod
    def from_config(doc: dict, n_in: int) -> "Head":
        h, p, d = doc["head"], doc["head"]["prune"], doc["head"]["drift"]
        return Head(n_in=n_in, n_hidden=h["n_hidden"], n_out=h["n_out"],
                    hash_seed=h["hash_seed"], hash_rounds=h["hash_rounds"],
                    ridge=h["ridge"], alpha_scale=h["alpha_scale"],
                    ladder=tuple(p["ladder"]), x_consec=p["x_consec"],
                    min_trained=p["min_trained"], ewma_decay=d["ewma_decay"],
                    k_sigma=d["k_sigma"], warmup=d["warmup"],
                    enter_hits=d["enter_hits"], exit_calm=d["exit_calm"])


def alpha_hash(seed: int, n_in: int, n_hidden: int, rounds: int) -> np.ndarray:
    """ODLHash input weights: entry (k, j) hashes the counter k*N + j + 1
    xor the seed with (7, 9, 8) Xorshift16 rounds, each followed by a
    multiply by an odd constant, then maps the 16 bits to [-1, 1)."""
    ctr = (np.arange(n_in, dtype=np.uint64)[:, None] * n_hidden
           + np.arange(n_hidden, dtype=np.uint64)[None, :] + 1)
    x = ((ctr ^ np.uint64(seed)) & np.uint64(0xFFFF)).astype(np.uint32)
    x = np.where(x == 0, 0x9E37, x).astype(np.uint32)
    for r in range(rounds):
        x ^= (x << 7) & 0xFFFF
        x ^= x >> 9
        x ^= (x << 8) & 0xFFFF
        x = (x * MIX[r % len(MIX)]) & 0xFFFF
    return x.astype(np.float64) / 32768.0 - 1.0


class Rows:
    """The state of R heads, one per row, all float64 (or ``rnd``-rounded)."""

    def __init__(self, head: Head, beta, p, count, rnd=identity):
        r = len(count)
        self.head, self.rnd = head, rnd
        self.alpha = rnd(alpha_hash(head.hash_seed, head.n_in, head.n_hidden,
                                    head.hash_rounds))
        self.beta = rnd(np.array(beta, np.float64))
        self.P = rnd(np.array(p, np.float64))
        self.count = np.array(count, np.int64)
        z = np.zeros(r, np.int64)
        self.level, self.streak, self.queries, self.skips = z.copy(), z.copy(), z.copy(), z.copy()
        self.mean = np.zeros(r)
        self.var = np.ones(r)
        self.steps, self.hits, self.calm = z.copy(), z.copy(), z.copy()
        self.active = np.zeros(r, bool)

    @staticmethod
    def batch_init(head: Head, x0, y0, n_rows: int, rnd=identity) -> "Rows":
        """P0 = (H0'H0 + ridge I)^-1, beta0 = P0 H0' Y0, for every row."""
        tmp = Rows(head, np.zeros((1, head.n_hidden, head.n_out)),
                   np.zeros((1, head.n_hidden, head.n_hidden)), [0], rnd)
        h0 = tmp.hidden(np.asarray(x0, np.float64))
        y0 = np.eye(head.n_out)[np.asarray(y0)]
        gram = rnd(rnd(h0.T @ h0) + head.ridge * np.eye(head.n_hidden))
        p0 = rnd(np.linalg.inv(gram))
        beta0 = rnd(p0 @ rnd(h0.T @ y0))
        return Rows(head, np.broadcast_to(beta0, (n_rows,) + beta0.shape),
                    np.broadcast_to(p0, (n_rows,) + p0.shape),
                    np.full(n_rows, len(y0)), rnd)

    def hidden(self, x):
        rnd, hd = self.rnd, self.head
        z = rnd(rnd(x) @ self.alpha) * (hd.alpha_scale / np.sqrt(hd.n_in))
        return rnd(1.0 / (1.0 + np.exp(-rnd(z))))

    def _ladder(self, queried, agree, conf, theta, rows):
        hd = self.head
        high = conf > theta
        low_query = queried & ~high
        success = high | (low_query & agree)
        mismatch = low_query & ~agree
        streak = np.where(success, self.streak[rows] + 1, 0)
        hit = streak >= hd.x_consec
        level = self.level[rows]
        level = np.where(hit, np.minimum(level + 1, len(hd.ladder) - 1), level)
        level = np.where(mismatch, np.maximum(level - 1, 0), level)
        self.streak[rows] = np.where(hit | mismatch, 0, streak)
        self.level[rows] = level
        self.queries[rows] += queried
        self.skips[rows] += ~queried

    def _drift(self, s):
        hd = self.head
        d = s - self.mean
        floor = np.square(1e-3 * np.abs(self.mean)) + 1e-12
        std = np.sqrt(np.maximum(self.var, floor))
        outlier = (self.steps >= hd.warmup) & (np.abs(d) > hd.k_sigma * std)
        a = hd.ewma_decay
        mean = np.where(outlier, self.mean, a * self.mean + (1 - a) * s)
        var = np.where(outlier, self.var, a * self.var + (1 - a) * d * d)
        boot = self.steps < 8
        mean = np.where(boot, (self.mean * self.steps + s) / (self.steps + 1), mean)
        var = np.where(boot, np.maximum((self.var * self.steps + d * d)
                                        / (self.steps + 1), 1e-9), var)
        hits = np.where(outlier, self.hits + 1, 0)
        calm = np.where(outlier, 0, self.calm + 1)
        enter, leave = hits >= hd.enter_hits, calm >= hd.exit_calm
        self.active = np.where(self.active, ~leave, enter)
        self.mean, self.var = mean, var
        self.steps = self.steps + 1
        self.hits = np.where(enter, 0, hits)
        self.calm = np.where(leave, 0, calm)

    def plan(self, x) -> dict:
        """Predict, run the drift test, decide the queries, and account the
        ladder for the rows that skip.  Returns the decision context a
        later answer is judged against."""
        rnd, hd = self.rnd, self.head
        x = np.asarray(x, np.float64)
        h = self.hidden(x)
        o = rnd(np.einsum("rn,rnm->rm", h, self.beta))
        top = -np.sort(-o, axis=1)
        gap = rnd(top[:, 0] - top[:, 1])
        conf = np.clip(gap, 0.0, 1.0)
        self._drift(rnd((np.mean(np.abs(rnd(x)), axis=1) - gap) / 2.0))
        theta = np.asarray(hd.ladder)[np.clip(self.level, 0, len(hd.ladder) - 1)]
        warm = self.count >= hd.min_trained
        queried = ~(warm & ~self.active & (conf > theta))
        skip = np.flatnonzero(~queried)
        if len(skip):
            self._ladder(np.zeros(len(skip), bool), np.zeros(len(skip), bool),
                         conf[skip], theta[skip], skip)
        return {"h": h, "pred": np.argmax(o, axis=1), "conf": conf,
                "theta": theta, "queried": queried}

    def learn(self, ctx: dict, labels, mask) -> None:
        """Rank-1 RLS on the masked rows, with the plan-time context."""
        rnd = self.rnd
        rows = np.flatnonzero(mask)
        if not len(rows):
            return
        h = ctx["h"][rows]
        y = np.eye(self.head.n_out)[np.asarray(labels)[rows]]
        p = self.P[rows]
        ph = rnd(np.einsum("rnk,rk->rn", p, h))
        den = rnd(1.0 + rnd(np.einsum("rn,rn->r", h, ph)))
        new_p = rnd(p - rnd(ph[:, :, None] * rnd(ph[:, None, :] / den[:, None, None])))
        self.P[rows] = rnd(0.5 * (new_p + new_p.transpose(0, 2, 1)))
        err = rnd(y - rnd(np.einsum("rn,rnm->rm", h, self.beta[rows])))
        gain = rnd(ph / den[:, None])
        self.beta[rows] = rnd(self.beta[rows] + rnd(gain[:, :, None] * err[:, None, :]))
        self.count[rows] += 1
        self._ladder(np.ones(len(rows), bool), ctx["pred"][rows] == np.asarray(labels)[rows],
                     ctx["conf"][rows], ctx["theta"][rows], rows)


def replay(rows: Rows, events: list, x_at, n_ticks: int, capacity: int) -> dict:
    """Drive ``rows`` through one tenant's recorded run.

    ``events`` is the teacher's log, in order: ``("ask", tick, ticket,
    mask, labels)`` with the asked mask and the labels of these rows, and
    ``("poll", tick, tickets)`` for each poll that returned replies.
    ``x_at(t)`` gives tick t's features of these rows.  The run advanced
    ``n_ticks`` ticks with a ``capacity``-slot ring under the coalesce
    policy: a stream already covered by an in-flight ticket is not asked
    again.  Returns the count of (row, tick) ask decisions that differ from
    the run's, and of decisions made."""
    asks, polls = {}, collections.defaultdict(list)
    for ev in events:
        if ev[0] == "ask":
            asks[ev[1]] = ev
        else:
            polls[ev[1]].extend(ev[2])
    last = max([n_ticks - 1] + list(polls))
    ring = collections.OrderedDict()  # ticket -> (tick, mask)
    labels = {ev[2]: ev[4] for ev in asks.values()}
    ctx = {0: rows.plan(x_at(0))} if n_ticks else {}
    flips = decisions = 0
    r = len(rows.count)
    for t in range(last + 1):
        if t < n_ticks:
            entries = list(ring.values())
            ask = asks.get(t)
            if ask is not None and len(ring) >= capacity:
                entries = entries[1:]  # the ask evicts the oldest ticket
            cover = np.zeros(r, bool)
            for _, m in entries:
                cover |= m
            mine = ctx[t]["queried"] & ~cover
            seen = ask[3] if ask is not None else np.zeros(r, bool)
            flips += int((mine != seen).sum())
            decisions += r
            if ask is not None:
                if len(ring) >= capacity:
                    ring.popitem(last=False)
                ring[ask[2]] = (t, ask[3])
        for ticket in polls.get(t, ()):
            ent = ring.pop(ticket, None)
            if ent is not None:
                rows.learn(ctx[ent[0]], labels[ticket], ent[1])
        if t + 1 < n_ticks:
            ctx[t + 1] = rows.plan(x_at(t + 1))
            keep = min([t + 1] + [tk for tk, _ in ring.values()])
            for old in [k for k in ctx if k < keep]:
                del ctx[old]
    return {"flips": flips, "decisions": decisions}


def rel_err(got, want) -> np.ndarray:
    """Per-row relative Frobenius error of ``got`` against ``want``."""
    got = np.asarray(got, np.float64).reshape(len(want), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    return (np.linalg.norm(got - want, axis=1)
            / np.maximum(np.linalg.norm(want, axis=1), 1e-30))
