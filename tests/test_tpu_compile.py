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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs, engine
from repro.engine import stream
from repro.kernels import oselm_update, xorshift_proj
from repro.models import model as model_lib

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
