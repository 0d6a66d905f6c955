"""Source kind ``decode``: a backbone decode batch feeding the heads of
every tenant.

Prompts of seeded tokens are prefilled, then tokens are sampled at the
mix's temperature from a seeded key; each decode step is one tick whose
final hidden states feed every tenant's heads (one ``SharedFeed``).  When
the cache is full the batch is prefilled again with fresh prompts (users'
sessions end and new ones start).  The teacher labels a tick by a fixed
seeded linear probe of its features, drawn in the span of the heads' hash
projection so that heads can learn it.

The backbone is the configuration's ``backbone``: its keys that name a
field of the program's ``ModelConfig`` build it, and ``init_std`` gives
each weight's init scale by name (see ``init_scale``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs, heads_ref
from bench.sources import Cell, SharedFeed, base_key, batch_head_state, engine_config


def init_scale(rules: dict, name: str, shape: tuple) -> float:
    """Init std of one weight from the configuration's ``init_std``: a
    number is the std itself, a list of axes gives 1/sqrt(product of the
    shape over them), the fan-in.  An unknown name is an error, so a layout
    change shows."""
    if name not in rules:
        raise KeyError(f"no init rule for weight {name!r}")
    rule = rules[name]
    if isinstance(rule, list):
        return float(np.prod([shape[a] for a in rule])) ** -0.5
    return float(rule)


def make_params(mcfg, rules: dict, key):
    """Every weight of the backbone, made on the device from ``key`` in one
    jitted call, in the program's parameter layout and float32."""
    from repro.models import layers
    from repro.models import model as model_lib

    shapes = jax.eval_shape(lambda: layers.init_params(
        model_lib.build_schema(mcfg), jax.random.PRNGKey(0)))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def bench_weights(key):
        out = []
        for i, (path, sds) in enumerate(flat):
            std = init_scale(rules, path[-1].key, sds.shape)
            if std == 0.0:
                out.append(jnp.zeros(sds.shape, jnp.float32))
            else:
                out.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                                   sds.shape, jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(bench_weights)(key)


def model_config(doc: dict):
    """The program's ``ModelConfig`` from the configuration's backbone."""
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    b = doc["backbone"]
    return ModelConfig(arch_id=doc["name"], **{k: v for k, v in b.items() if k in fields})


class Source(Cell):
    tick_op = "bench_decode"

    def setup_program(self, log):
        from repro.models import model as model_lib
        from repro.models import transformer

        b, m = self.doc["backbone"], self.mix
        self.mcfg = mcfg = model_config(self.doc)
        self.n_in = b["d_model"]
        self.ecfg = engine_config(self.doc, self.n_in)
        self.batch, self.prompt_len = m["streams"], m["prompt_len"]
        self.max_len, temp = m["max_len"], float(m["temperature"])
        self.decode_len = self.max_len - self.prompt_len
        key = base_key(self.seed)
        self.params = make_params(mcfg, b["init_std"], jax.random.fold_in(key, 1))
        self.key = jax.random.fold_in(key, 2)
        batch, plen, max_len, vocab = self.batch, self.prompt_len, self.max_len, b["vocab_size"]

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
            return feats, nxt, st

        self._prefill = jax.jit(bench_prefill)
        self._decode = jax.jit(bench_decode, donate_argnums=(1,))
        self.cycles: list = []  # per cycle: prompt tokens, decode input tokens
        self.feats: list = []  # per tick: (B, d) features
        self.positions: list = []  # per tick: position decoded
        self._cycle = -1
        n_out, d = self.ecfg.elm.n_out, b["d_model"]
        tenants = [f"tenant{i}" for i in range(m["tenants"])]
        # The teacher's probe lies in the span of the heads' hash projection
        # (alpha, from the configuration's hash seed), so a head can learn
        # it and the pruning ladder can relax.
        h = self.doc["head"]
        alpha = heads_ref.alpha_hash(h["hash_seed"], d, h["n_hidden"], h["hash_rounds"])
        prng = np.random.default_rng([self.seed, 17])
        self.probes = {n: (alpha @ prng.normal(size=(h["n_hidden"], n_out))).astype(np.float32)
                       for n in tenants}
        self._new_cycle()
        x0 = self._prefill0.reshape(-1, d)
        self.init_batch = {n: (x0, self.probe(n, x0)) for n in tenants}
        states = {n: batch_head_state(self.ecfg, x0, y0, self.batch, h["init_precision"])
                  for n, (x0, y0) in self.init_batch.items()}
        self.streams = {n: self.batch for n in tenants}
        self.feed = SharedFeed(self._produce, len(tenants))
        feeds = {n: self.feed.reader(i) for i, n in enumerate(tenants)}
        labelers = {n: (lambda tick, feats, n=n: self.probe(n, feats)) for n in tenants}
        self.rows = np.arange(self.batch)
        self._build_mux(states, feeds, labelers, {n: self.rows for n in tenants})
        del states
        log(f"set-up: {len(tenants)} tenants x {self.batch} streams over one decode "
            f"batch, prompt {plen}, decode {self.decode_len}, max_len {max_len}")

    def probe(self, name, feats):
        return np.argmax(np.asarray(feats, np.float32) @ self.probes[name], axis=-1)

    def _new_cycle(self):
        self._cycle += 1
        tokens, tok, hidden, self._st = self._prefill(self.params, self.key, self._cycle)
        if self._cycle == 0:
            self._prefill0 = np.asarray(hidden)
        self._tok = tok
        self._step = 0
        self.cycles.append({"prompt": tokens, "inputs": []})

    def _produce(self, k):
        with jax.profiler.TraceAnnotation("bench.tick_source"):
            return self._decode_tick()

    def _decode_tick(self):
        if self._step == self.decode_len:
            self._new_cycle()
        self.cycles[-1]["inputs"].append(self._tok)
        feats, self._tok, self._st = self._decode(self.params, self._st, self._tok,
                                                  self.key, self._cycle, self._step)
        self.positions.append(self.prompt_len + self._step)
        self._step += 1
        self.feats.append(feats)
        return feats

    def stop_sources(self):
        self.feed.stop()

    def handed(self) -> dict:
        return dict(zip(self.teachers, self.feed.cursors))

    def work(self, t0: int, t1: int) -> dict:
        work = super().work(t0, t1)
        work["decode"] = [costs.decode_step(self.doc["backbone"], self.batch,
                                            self.positions[t]) for t in range(t0, t1)]
        return work

    def free_program(self):
        self._st = None
        self._tok = None
        super().free_program()

    def check(self, control: bool, log) -> dict:
        """Prefill and decode features against the plain forward pass, heads
        against the plain OS-ELM replay, and the program's query
        accounting."""
        finals = {n: self.head_rows_state(n, self.rows) for n in self.teachers}
        identity = sum(not r.stats.reconciled for r in self.results.values())
        n_ticks = {n: r.stats.ticks for n, r in self.results.items()}
        feats = [np.asarray(f) for f in self.feats]
        self.feats = None
        self.free_program()
        out = {"feat_err_max": self._feature_check(feats, control, log)}
        x_at = {n: (lambda t: feats[t][self.rows]) for n in self.teachers}
        out.update(self.replay_heads(finals, n_ticks, self.init_batch, x_at,
                                     {n: self.rows for n in self.teachers}, control))
        out["identity_breaks"] = identity
        return out

    def _feature_check(self, feats, control, log) -> float:
        """Widest relative gap between the features the heads got and the
        plain forward pass teacher-forced on the same tokens: the first
        prefill's hidden states (the heads' batch init) and every decoded
        position of every cycle, for ``feature_rows`` streams drawn from the
        seed, ``feature_block`` streams to a call of the reference."""
        ref, b = self.ref_module, self.doc["backbone"]
        n = min(self.mix["check"]["feature_rows"], self.batch)
        blk = self.mix["check"]["feature_block"]
        rows = np.sort(np.random.default_rng([self.seed, 23]).choice(self.batch, n, replace=False))
        plen = self.prompt_len
        worst, med = 0.0, []
        for c, cyc in enumerate(self.cycles):
            steps = len(cyc["inputs"])
            if not steps:
                continue
            tokens = np.zeros((self.batch, self.max_len), np.int32)
            tokens[:, :plen] = np.asarray(cyc["prompt"])
            tokens[:, plen: plen + steps] = np.stack([np.asarray(t) for t in cyc["inputs"]],
                                                     axis=1)
            first = c * self.decode_len
            got_all = np.stack(feats[first: first + steps], axis=1)
            lo = 0 if c == 0 else plen  # the first cycle's prompt is the heads' init
            errs = []
            for i in range(0, n, blk):
                r = rows[i: i + blk]
                tok = jnp.asarray(tokens[r])
                want = np.asarray(ref.features(self.params, tok, b))[:, lo: plen + steps]
                if control:
                    got = np.asarray(ref.features(self.params, tok, b, control=True))
                    got = got[:, lo: plen + steps]
                else:
                    got = got_all[r]
                    if lo == 0:
                        got = np.concatenate([self._prefill0[r], got], axis=1)
                errs.append(np.linalg.norm(got - want, axis=-1)
                            / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))
            err = np.concatenate(errs)
            worst = float(np.max([worst, err.max()]))  # a NaN must fail the check
            med.append(float(np.median(err)))
        log(f"features: {len(med)} cycle(s) x {n} streams; median relative gap "
            f"per cycle from {min(med, default=0):.3e} to {max(med, default=0):.3e}")
        return worst
