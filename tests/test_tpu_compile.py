"""Compile the serve path's kernels and steps for a described TPU v5e.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(a cast Mosaic lacks, a program that does not fit 16 GB of HBM).  This is
the only file that describes the topology, and it does so inside a
module-scoped fixture: describing it loads the TPU library, which one
process at a time may hold, so it must never happen while a module is
imported or collected.  The persistent compile cache is off around these
compiles (an entry compiled for a described chip cannot be read back).
"""

import dataclasses
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs, engine
from repro.configs import har_odl
from repro.engine import stream
from repro.kernels import oselm_update, xorshift_proj
from repro.configs.base import ModelConfig
from repro.models import model as model_lib
from repro.models import transformer

ARCH = "h2o-danube-1.8b"  # the serve path's full-width backbone
S, N, N_IN, N_OUT = 32, 128, 2560, 6  # serve batch, head width, d_model
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this installation
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _kernel_case(name):
    """(fn, shapes) of one Pallas kernel at the serve path's widths."""
    f32 = jnp.float32
    if name == "xorshift_projection":
        return (lambda x: xorshift_proj.xorshift_projection(
            x, seed=0x2D2A, n_hidden=N, interpret=False),
            [jax.ShapeDtypeStruct((S, N_IN), f32)])
    if name == "oselm_rls_update":
        return (lambda p, b, h, y: oselm_update.oselm_rls_update(
            p, b, h, y, interpret=False),
            [jax.ShapeDtypeStruct(s, f32)
             for s in ((N, N), (N, N_OUT), (S, N), (S, N_OUT))])
    return (lambda p, b, h, y: oselm_update.oselm_rls_update_fleet(
        p, b, h, y, interpret=False),
        [jax.ShapeDtypeStruct(s, f32)
         for s in ((S, N, N), (S, N, N_OUT), (S, 1, N), (S, 1, N_OUT))])


@pytest.mark.parametrize(
    "name", ["xorshift_projection", "oselm_rls_update",
             "oselm_rls_update_fleet"])
def test_kernel_compiles_to_mosaic(one_chip, name):
    fn, shapes = _kernel_case(name)
    compiled = jax.jit(fn).lower(*_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_full_width_decode_step_fits_one_chip(one_chip):
    """The serve path's decode step at h2o-danube-1.8b's published widths,
    float32 weights, decode batch 32: compiles, and its arguments,
    temporaries and outputs fit one v5e."""
    cfg = configs.get_config(ARCH, "full")
    params = jax.eval_shape(
        lambda k: model_lib.layers.init_params(model_lib.build_schema(cfg), k),
        jax.random.PRNGKey(0))
    _, state = jax.eval_shape(
        lambda p, t: model_lib.prefill(p, t, cfg, max_len=128),
        params, jax.ShapeDtypeStruct((S, 16), jnp.int32))
    tok = jax.ShapeDtypeStruct((S, 1), jnp.int32)
    compiled = jax.jit(
        lambda p, st, t: model_lib.decode_step(p, st, t, cfg)
    ).lower(*_on(one_chip, (params, state, tok))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 7e9  # the weights really are f32
    assert total < V5E_HBM_BYTES, mem


def test_learn_plan_runner_compiles_at_backbone_width(one_chip):
    """The fused learn+plan runner a serve tenant steps every tick, at
    n_in = d_model = 2560, with donation as on the chip."""
    cfg = model_lib.core_config(configs.get_config(ARCH, "full"))
    assert cfg.elm.n_in == N_IN
    st = jax.eval_shape(lambda: engine.init_fleet(cfg, S))
    x = jax.ShapeDtypeStruct((S, N_IN), jnp.float32)

    def v(dt):
        return jax.ShapeDtypeStruct((S,), dt)

    learn_args = (jax.ShapeDtypeStruct((S, N), jnp.float32), v(jnp.int32),
                  v(jnp.int32), v(jnp.float32), v(jnp.bool_), v(jnp.bool_),
                  v(jnp.float32))
    runner = stream._learn_plan_runner(cfg, "serve", True)
    args = _on(one_chip, (st.elm, st.prune, st.drift, st.meter, *learn_args,
                          x))
    compiled = runner.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0  # the head state is donated in place


def test_compacted_learn_plan_updates_p_in_place(one_chip):
    """The learn+plan runner at the HAR cell's width (S 65536, n_in 561,
    N 128) over a compacted 8192-row index: the scatter writes into the
    donated P, so the program holds no P-sized copy, and its temporaries
    are a fraction of P."""
    s, k = 65536, 8192
    cfg = har_odl.full()
    n_in = cfg.elm.n_in
    assert (n_in, cfg.elm.n_hidden) == (561, N)
    st = jax.eval_shape(lambda: engine.init_fleet(cfg, s))

    def v(dt):
        return jax.ShapeDtypeStruct((s,), dt)

    learn_args = (jax.ShapeDtypeStruct((s, N), jnp.float32), v(jnp.int32),
                  v(jnp.int32), v(jnp.float32), v(jnp.bool_), v(jnp.bool_),
                  v(jnp.float32))
    runner = stream._learn_plan_runner(cfg, "serve", True)
    args = _on(one_chip, (st.elm, st.prune, st.drift, st.meter, *learn_args,
                          jax.ShapeDtypeStruct((s, n_in), jnp.float32)))
    rows = _on(one_chip, jax.ShapeDtypeStruct((k,), jnp.int32))
    compiled = runner.lower(*args, rows=rows).compile()
    p_shape = f"f32[{s},{N},{N}]"
    copies = [line for line in compiled.as_text().splitlines()
              if p_shape in line and re.search(r"\bcopy(-start)?\(", line)]
    assert not copies, copies[:3]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= s * N * N * 4  # P is donated
    assert mem.temp_size_in_bytes < s * N * N * 4 / 2


def _dsv2lite():
    """DeepSeek-V2-Lite as the benchmark cuts it to one chip (its
    configuration file's backbone): published widths, 8 of 64 experts
    held, the dense layer and 10 MoE layers."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"
            / "deepseek-v2-lite.json")
    b = json.loads(path.read_text())["backbone"]
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(arch_id="deepseek-v2-lite",
                       **{k: v for k, v in b.items() if k in fields})


DSV2_B, DSV2_PROMPT, DSV2_MAX = 512, 128, 512  # the dsv2lite.decode512 cell
V5E_PEAK_LIMIT = 14.5e9


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_dsv2lite_serve_step_fits_one_chip(one_chip, step):
    """The expert-share decode (absorbed latent attention) and prefill at
    DeepSeek-V2-Lite's published widths and the configuration's depth,
    float32 weights, 512 streams, 512-slot latent cache: they compile, fit
    under 14.5 GB, and the decode never expands the cache into per-head
    keys or values ((streams, slots, heads, ...) tensors)."""
    cfg = _dsv2lite()
    params = jax.eval_shape(
        lambda k: model_lib.layers.init_params(model_lib.build_schema(cfg), k),
        jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((DSV2_B, DSV2_PROMPT), jnp.int32)

    def prefill(p, t):
        hidden, st = model_lib.prefill(p, t, cfg, max_len=DSV2_MAX)
        return transformer.lm_logits(p, hidden[:, -1:], cfg), st

    if step == "prefill":
        lowered = jax.jit(prefill).lower(*_on(one_chip, (params, tokens)))
    else:
        _, state = jax.eval_shape(prefill, params, tokens)
        tok = jax.ShapeDtypeStruct((DSV2_B, 1), jnp.int32)
        lowered = jax.jit(lambda p, st, t: model_lib.decode_step(p, st, t, cfg),
                          donate_argnums=(1,)).lower(*_on(one_chip, (params, state, tok)))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 6e9  # the weights really are f32
    assert total < V5E_PEAK_LIMIT, mem
    if step == "decode":
        expanded = f"[{DSV2_B},{DSV2_MAX},{cfg.n_heads},"
        assert expanded not in compiled.as_text()
        assert mem.alias_size_in_bytes > 3e9  # the latent cache is updated in place


def _hlo(text):
    """The compiled program as {computation: {instruction: fields}}: the
    output's dimensions (one tuple per array), whether it lies in device
    memory (``hbm``: no ``S(n)`` memory space in its layout), the opcode,
    the operands and the computations it calls, and whether it is its
    computation's root."""
    comps, cur = {}, None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line.rstrip())
            cur = comps.setdefault(m.group(1), {}) if m else None
            continue
        m = re.match(r"\s*(ROOT )?%([\w.\-]+) = (.*)$", line)
        if cur is None or not m:
            continue
        rest = m.group(3)
        if rest.startswith("("):  # a tuple: up to its closing parenthesis
            depth = 0
            for n, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            shape, rest = rest[:n + 1], rest[n + 2:]
        else:
            shape, _, rest = rest.partition(" ")
        op, _, args = rest.partition("(")
        depth = 1
        for end, ch in enumerate(args):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        cur[m.group(2)] = dict(
            dims=[tuple(int(d) for d in g.split(",") if d)
                  for g in re.findall(r"\w+\[([\d,]*)\]", shape)],
            hbm="S(" not in shape, op=op, root=bool(m.group(1)),
            operands=re.findall(r"%([\w.\-]+)", args[:end]),
            calls=re.findall(r"(?:calls|body)=%([\w.\-]+)", args[end:]))
    return comps


def _row_write(comps, scope, ins, stacks, layers):
    """A whole stack written in place by a scatter or dynamic-update-slice
    (alone, or as a fusion's root) whose update is rows, not a layer."""
    if ins["dims"] != [ins["dims"][0]] or ins["dims"][0] not in stacks:
        return False
    if ins["op"] == "fusion":
        scope = comps[ins["calls"][0]]
        ins = next(i for i in scope.values() if i["root"])
    if ins["op"] not in ("scatter", "dynamic-update-slice"):
        return False
    update = scope[ins["operands"][2 if ins["op"] == "scatter" else 1]]
    return not set(update["dims"]) & (stacks | layers)


@pytest.mark.parametrize("cell", ["danube.decode32", "dsv2lite.decode512"])
def test_decode_step_writes_rows_into_the_carried_stack(one_chip, cell):
    """The cells' decode steps at their shapes, the state donated as the
    benchmark donates it: in the layer scan's body no op puts a whole
    layer's cache or the whole stack into device memory but the in-place
    row write into the carried stack (so no slice-write-put-back round trip
    and no one-hot rewrite; a layer staged in on-chip memory for attention
    to read is the read itself), no carried stack is copied anywhere, and
    the caches are updated where they lie."""
    if cell == "danube.decode32":
        cfg, b, prompt, max_len = configs.get_config(ARCH, "full"), S, 128, 512
    else:
        cfg, b, prompt, max_len = _dsv2lite(), DSV2_B, DSV2_PROMPT, DSV2_MAX
    params = jax.eval_shape(
        lambda k: model_lib.layers.init_params(model_lib.build_schema(cfg), k),
        jax.random.PRNGKey(0))
    _, state = jax.eval_shape(
        lambda p, t: model_lib.prefill(p, t, cfg, max_len=max_len),
        params, jax.ShapeDtypeStruct((b, prompt), jnp.int32))
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    compiled = jax.jit(lambda p, st, t: model_lib.decode_step(p, st, t, cfg),
                       donate_argnums=(1,)).lower(*_on(one_chip, (params, state, tok))).compile()
    caches = [a for a in jax.tree.leaves(state.caches) if a.ndim >= 4]
    stacks = {a.shape for a in caches}
    layers = {a.shape[1:] for a in caches} | {(1,) + a.shape[1:] for a in caches}
    comps = _hlo(compiled.as_text())
    bodies = [c for ops in comps.values() for i in ops.values() if i["op"] == "while"
              for c in i["calls"] if any(d in stacks for d in i["dims"])]
    assert bodies  # the layer scan carries the stack
    for body in bodies:
        for name, ins in comps[body].items():
            if ins["op"] in ("parameter", "get-tuple-element", "tuple", "bitcast"):
                continue
            if ins["hbm"] and any(d in stacks | layers for d in ins["dims"]):
                assert _row_write(comps, comps[body], ins, stacks, layers), (body, name, ins)
    carried = {a.shape for a in caches if a.shape[0] > 1}
    copies = [name for ops in comps.values() for name, i in ops.items()
              if i["op"] in ("copy", "copy-start") and any(d in carried for d in i["dims"])]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in caches)
