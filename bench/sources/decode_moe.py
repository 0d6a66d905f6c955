"""Source kind ``decode_moe``: the ``decode`` kind over a backbone with
latent attention and an expert share (DeepSeek-V2's block).

The program serves as in ``decode``: one decode batch, re-prefilled when
the cache is full, feeds every tenant's heads.  What differs:

* the work of a tick is ``costs_moe``'s: the absorbed latent attention, the
  dense layer, the router, the shared experts and the held experts' part;
* the tick keeps, on the device, only what the check reads of the rows it
  follows (``check.feature_rows`` streams drawn from the seed): their
  features, input tokens and each MoE layer's top-k experts; the logits of
  the first ``check.logit_rows`` of them go to the host two ticks later.
  A whole (streams, d_model) tick kept over a window would take gigabytes;
* the old decode state is freed before each re-prefill, so two caches
  never live together;
* the check adds ``logit_err_max`` (logits against the reference's output
  head over its own features) and ``route_flip_share`` (the share of
  (token, MoE layer) whose top-k set differs from the reference's), and
  the heads are checked on the followed rows;
* the expert share's counters (``model.expert_load``) are read at each
  re-prefill and after the window, and their load logged.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs_moe, harness, heads_ref
from bench.sources import Cell, SharedFeed, base_key, batch_head_state, engine_config

decode = harness.load_module(pathlib.Path(__file__).with_name("decode.py"), "bench_source_decode")

# Fields of the program's ModelConfig this configuration cannot run without.
NEEDS = ("first_k_dense", "norm_topk_prob", "routed_scaling_factor", "experts_held",
         "expert_first", "yarn_factor")


class Source(decode.Source):
    tick_op = "bench_decode"

    def setup_program(self, log):
        from repro.configs.base import ModelConfig
        from repro.models import model as model_lib
        from repro.models import transformer

        fields = {f.name for f in dataclasses.fields(ModelConfig)}
        missing = [k for k in NEEDS if k not in fields]
        if missing:
            raise RuntimeError(f"the program's ModelConfig has no {missing}: it cannot "
                               f"run {self.doc['name']!r}")
        b, m, chk = self.doc["backbone"], self.mix, self.mix["check"]
        self.mcfg = mcfg = decode.model_config(self.doc)
        self.n_in = b["d_model"]
        self.ecfg = engine_config(self.doc, self.n_in)
        self.batch, self.prompt_len = m["streams"], m["prompt_len"]
        self.max_len, temp = m["max_len"], float(m["temperature"])
        self.decode_len = self.max_len - self.prompt_len
        key = base_key(self.seed)
        self.params = decode.make_params(mcfg, b["init_std"], jax.random.fold_in(key, 1))
        self.key = jax.random.fold_in(key, 2)
        batch, plen, max_len, vocab = self.batch, self.prompt_len, self.max_len, b["vocab_size"]
        n = min(chk["feature_rows"], batch)
        self.rows = np.sort(np.random.default_rng([self.seed, 23]).choice(batch, n, replace=False))
        rows = jnp.asarray(self.rows)
        logit_rows = rows[: chk["logit_rows"]]

        def bench_prefill(params, key, cycle):
            k = jax.random.fold_in(key, cycle)
            tokens = jax.random.randint(jax.random.fold_in(k, 0), (batch, plen), 0, vocab)
            hidden, st = model_lib.prefill(params, tokens, mcfg, max_len=max_len)
            logits = transformer.lm_logits(params, hidden[:, -1:], mcfg)[:, 0]
            tok = jax.random.categorical(jax.random.fold_in(k, 1),
                                         logits.astype(jnp.float32) / temp)
            return tokens, tok, hidden.astype(jnp.float32), st

        def bench_decode(params, st, tok, key, cycle, step):
            logits, feats, st = model_lib.decode_step(params, st, tok[:, None], mcfg)
            k = jax.random.fold_in(jax.random.fold_in(key, cycle), step + 2)
            nxt = jax.random.categorical(k, logits.astype(jnp.float32) / temp)
            kept = {"feats": feats[rows], "tok": tok[rows],
                    "route": st.caches["layers"]["route"][:, rows]}
            return feats, nxt, st, kept, logits[logit_rows]

        self._prefill = jax.jit(bench_prefill)
        self._decode = jax.jit(bench_decode, donate_argnums=(1,))
        self.cycles: list = []  # per cycle: prompt tokens, then per tick what the check keeps
        self.positions: list = []  # per tick: position decoded
        self.logits: list = []  # per tick: logits of the logit rows, on the host
        self._in_flight: collections.deque = collections.deque()
        self.load = None  # the expert share's counters, summed over cycles
        self._cycle = -1
        n_out, d = self.ecfg.elm.n_out, b["d_model"]
        tenants = [f"tenant{i}" for i in range(m["tenants"])]
        h = self.doc["head"]
        alpha = heads_ref.alpha_hash(h["hash_seed"], d, h["n_hidden"], h["hash_rounds"])
        prng = np.random.default_rng([self.seed, 17])
        self.probes = {t: (alpha @ prng.normal(size=(h["n_hidden"], n_out))).astype(np.float32)
                       for t in tenants}
        self._new_cycle()
        x0 = self._prefill0.reshape(-1, d)
        self.init_batch = {t: (x0, self.probe(t, x0)) for t in tenants}
        states = {t: batch_head_state(self.ecfg, x0, y0, self.batch, h["init_precision"])
                  for t, (x0, y0) in self.init_batch.items()}
        self.streams = {t: self.batch for t in tenants}
        self.feed = SharedFeed(self._produce, len(tenants))
        feeds = {t: self.feed.reader(i) for i, t in enumerate(tenants)}
        labelers = {t: (lambda tick, feats, t=t: self.probe(t, feats)) for t in tenants}
        self._build_mux(states, feeds, labelers, {t: self.rows for t in tenants})
        del states
        log(f"set-up: {len(tenants)} tenant(s) x {self.batch} streams over one decode batch, "
            f"prompt {plen}, decode {self.decode_len}, max_len {max_len}; experts "
            f"{b['expert_first']}..{b['expert_first'] + b['experts_held'] - 1} of "
            f"{b['n_experts']} held; check follows {n} rows")

    def _count_load(self):
        """Add the decode state's expert counters to ``load`` (one host read
        a cycle)."""
        from repro.models import model as model_lib

        got = model_lib.expert_load(self._st)
        if got is not None:
            self.load = got if self.load is None else {
                k: self.load[k] + got[k] for k in got}

    def _new_cycle(self):
        if self._cycle >= 0:
            self._count_load()
            self._st = None  # free the old cache before the prefill makes the next
        self._cycle += 1
        tokens, tok, hidden, self._st = self._prefill(self.params, self.key, self._cycle)
        if self._cycle == 0:
            self._prefill0 = np.asarray(hidden)
        del hidden
        self._tok = tok
        self._step = 0
        self.cycles.append({"prompt": tokens[jnp.asarray(self.rows)], "kept": []})

    def _decode_tick(self):
        if self._step == self.decode_len:
            self._new_cycle()
        feats, self._tok, self._st, kept, logits = self._decode(
            self.params, self._st, self._tok, self.key, self._cycle, self._step)
        logits.copy_to_host_async()
        self._in_flight.append(logits)
        while len(self._in_flight) > 2:  # long since computed: no wait
            self.logits.append(np.asarray(self._in_flight.popleft()))
        self.cycles[-1]["kept"].append(kept)
        self.positions.append(self.prompt_len + self._step)
        self._step += 1
        return feats

    def work(self, t0: int, t1: int) -> dict:
        work = Cell.work(self, t0, t1)
        work["decode"] = [costs_moe.decode_step(self.doc["backbone"], self.batch,
                                                self.positions[t]) for t in range(t0, t1)]
        return work

    def log_load(self, log):
        """Token-choices each held expert got per step of each MoE layer:
        the largest and the mean, and the share of the rows the experts
        computed that no token chose (padding)."""
        if self.load is None:
            return
        tok, rows = self.load["tokens"], self.load["rows"]
        steps = rows[0, 0] / self.batch
        log(f"expert load over {steps:.0f} decode steps: token-choices per held expert "
            f"a step max {tok.max() / steps:.2f}, mean {tok.mean() / steps:.2f}; padding "
            f"{100 * (1 - tok.sum() / rows.sum()):.2f}% of {rows.sum()} rows computed")

    def check(self, control: bool, log) -> dict:
        """Features, logits and routes of the followed rows against the
        plain forward pass, heads against the plain OS-ELM replay, and the
        program's query accounting."""
        finals = {n: self.head_rows_state(n, self.rows) for n in self.teachers}
        identity = sum(not r.stats.reconciled for r in self.results.values())
        n_ticks = {n: r.stats.ticks for n, r in self.results.items()}
        self._count_load()
        self.log_load(log)
        self.logits += [np.asarray(a) for a in self._in_flight]
        self._in_flight.clear()
        cycles = [{"prompt": np.asarray(c["prompt"]),
                   **{k: np.stack([np.asarray(kt[k]) for kt in c["kept"]], axis=1)
                      for k in ("feats", "tok", "route") if c["kept"]}}
                  for c in self.cycles]
        self.cycles = None
        self.free_program()
        out = self._model_check(cycles, control, log)
        feats = np.concatenate([c["feats"] for c in cycles if "feats" in c], axis=1)
        x_at = {n: (lambda t: feats[:, t]) for n in self.teachers}
        out.update(self.replay_heads(finals, n_ticks, self.init_batch, x_at,
                                     {n: self.rows for n in self.teachers}, control))
        out["identity_breaks"] = identity
        return out

    def _model_check(self, cycles, control, log) -> dict:
        """Widest relative gap of features (the first prefill's, which the
        heads' init took, and every decoded position) and of the logits,
        and the share of (token, MoE layer) whose top-k expert set differs,
        between the program and the plain forward pass teacher-forced on
        the same tokens, ``feature_block`` rows to a call of the reference."""
        ref, b, chk = self.ref_module, self.doc["backbone"], self.mix["check"]
        blk, n_logit, plen = chk["feature_block"], chk["logit_rows"], self.prompt_len
        feat_worst = logit_worst = 0.0
        flips = routed = 0
        med, first = [], 0
        for c, cyc in enumerate(cycles):
            if "feats" not in cyc:
                continue
            steps = cyc["feats"].shape[1]
            tokens = np.zeros((len(self.rows), self.max_len), np.int32)
            tokens[:, :plen] = cyc["prompt"]
            tokens[:, plen: plen + steps] = cyc["tok"]
            lo = 0 if c == 0 else plen  # the first cycle's prompt is the heads' init
            errs = []
            for i in range(0, len(self.rows), blk):
                tok = jnp.asarray(tokens[i: i + blk])
                want, routes = ref.forward(self.params, tok, b)
                if control:
                    ctrl, got_routes = ref.forward(self.params, tok, b, control=True)
                    got = np.asarray(ctrl)[:, lo: plen + steps]
                    got_routes = np.asarray(got_routes)[:, :, plen: plen + steps]
                else:
                    got = cyc["feats"][i: i + blk]
                    if lo == 0:
                        got = np.concatenate([self._prefill0[self.rows[i: i + blk]], got], 1)
                    got_routes = np.moveaxis(cyc["route"][:, :, i: i + blk], 1, 2)
                want_f = np.asarray(want)[:, lo: plen + steps]
                errs.append(rel_gap(got, want_f))
                want_r = np.sort(np.asarray(routes)[:, :, plen: plen + steps], -1)
                diff = np.any(np.sort(got_routes, -1) != want_r, -1)
                flips, routed = flips + int(diff.sum()), routed + diff.size
                k = min(n_logit - i, blk)
                if k > 0:
                    dec = want[:k, plen: plen + steps]
                    want_l = np.asarray(ref.logits(self.params, dec))
                    if control:
                        got_l = np.asarray(ref.logits(
                            self.params, ctrl[:k, plen: plen + steps], control=True))
                    else:
                        got_l = np.stack([self.logits[t][i: i + k]
                                          for t in range(first, first + steps)], 1)
                    logit_worst = float(np.max([logit_worst, rel_gap(got_l, want_l).max()]))
            first += steps
            err = np.concatenate(errs)
            feat_worst = float(np.max([feat_worst, err.max()]))  # a NaN must fail the check
            med.append(float(np.median(err)))
        log(f"features: {len(med)} cycle(s) x {len(self.rows)} streams; median relative gap "
            f"per cycle from {min(med, default=0):.3e} to {max(med, default=0):.3e}; "
            f"{flips} of {routed} (token, layer) routes differ")
        return {"feat_err_max": feat_worst, "logit_err_max": logit_worst,
                "route_flip_share": flips / max(routed, 1)}


def rel_gap(got, want) -> np.ndarray:
    """Relative L2 gap of each vector along the last axis."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return (np.linalg.norm(got - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))
