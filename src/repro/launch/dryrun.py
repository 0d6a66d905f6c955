import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count at first init.
# The dry-run (and ONLY the dry-run) runs with 512 placeholder CPU devices so
# jax.make_mesh can build the production meshes (16x16 and 2x16x16).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. builds the production mesh (single-pod 256 or multi-pod 512 chips),
  2. constructs abstract params/optimizer/cache state (ShapeDtypeStruct +
     NamedSharding — zero allocation),
  3. ``jax.jit(step).lower(...).compile()`` — any sharding mismatch,
     non-divisible partition, unsupported collective, or compile-time OOM
     is a FAILURE of the framework and crashes the cell,
  4. records ``compiled.memory_analysis()`` (proves it fits),
     ``compiled.cost_analysis()`` (FLOPs/bytes for the roofline) and the
     collective-byte census parsed from the optimized HLO,
  5. appends one JSON record per cell to ``results/dryrun.jsonl``.

Usage:
  python -m repro.launch.dryrun                       # all cells, 1-pod
  python -m repro.launch.dryrun --multi-pod           # all cells, 2 pods
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --variant sp          # hillclimb variants
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import TrainConfig
from repro.distributed import sharding
from repro.launch.hlo_census import collective_census
from repro.launch.mesh import make_production_mesh
from repro.models import encdec, model as model_lib


def _rules_for(cfg, variant: str, mesh):
    """Sharding-rule overrides per arch + hillclimb variant."""
    msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    rules = {}
    if cfg.n_heads % msize != 0:
        # Heads don't divide the model axis (deepseek-coder 56H): fall back
        # to attention sequence-sharding for the score tensors.
        rules["seq_attn"] = "model"
    if "sp" in variant.split("+"):
        # Megatron-style sequence parallelism on the residual stream.
        rules["seq"] = "model"
    if variant == "dp_only":
        rules.update({k: None for k in ("heads", "kv_heads", "mlp", "experts", "vocab")})
    return rules


def _cfg_for_variant(cfg, variant: str):
    """Hillclimb variants that change the model program itself."""
    for part in variant.split("+"):
        if part == "flash":
            cfg = cfg.replace(attention_impl="chunked", attention_chunk=1024)
        elif part == "flash512":
            cfg = cfg.replace(attention_impl="chunked", attention_chunk=512)
        elif part == "ep":
            cfg = cfg.replace(moe_impl="ep")
        elif part == "dus":
            cfg = cfg.replace(cache_update="dus")
    return cfg


def _w16(variant: str) -> bool:
    return "w16" in variant.split("+")


# Per-arch dry-run overrides: models whose f32 master state cannot fit the
# pod (see TrainConfig.param_dtype) and depth/size-driven microbatch counts.
TRAIN_OVERRIDES = {
    # NOTE: temp bytes GROW with microbatch count on this backend (measured
    # 18.9 GB @ mb=8 -> 32.8 GB @ mb=32 — see EXPERIMENTS.md §Perf refuted
    # hypothesis H2), so the override keeps mb moderate.
    "deepseek-v2-236b": dict(param_dtype="bfloat16", microbatches=8),
    "deepseek-coder-33b": dict(microbatches=8),
    "chameleon-34b": dict(microbatches=8),
}


def _train_cell(cfg, shape, tcfg: TrainConfig):
    state = model_lib.abstract_train_state(cfg, tcfg)
    batch = model_lib.input_specs(cfg, shape)

    def step(st, b):
        return model_lib.train_step(st, b, cfg, tcfg)

    # donate_argnums=(0,): the new TrainState aliases the old one — without
    # this, peak memory double-counts params+moments (in + out).
    return jax.jit(step, donate_argnums=(0,)), (state, batch)


def _prefill_cell(cfg, shape, w16: bool = False):
    schema = model_lib.build_schema(cfg)
    params = model_lib.layers.abstract_params(
        schema, dtype=jnp.bfloat16 if w16 else jnp.float32
    )
    specs = model_lib.input_specs(cfg, shape)
    if cfg.enc_dec:

        def step(p, frames):
            return model_lib.encdec_prefill(p, frames, cfg, max_len=shape.seq_len)

        return jax.jit(step), (params, specs["frames"])

    def step(p, tokens):
        return model_lib.prefill(p, tokens, cfg)

    return jax.jit(step), (params, specs["tokens"])


def _decode_cell(cfg, shape, w16: bool = False):
    schema = model_lib.build_schema(cfg)
    params = model_lib.layers.abstract_params(
        schema, dtype=jnp.bfloat16 if w16 else jnp.float32
    )
    token = model_lib.input_specs(cfg, shape)["token"]
    b = shape.global_batch
    if cfg.enc_dec:
        # Decoder decode: self cache + precomputed cross K/V over the source.
        enc_sds = model_lib._sds((b, min(shape.seq_len, cfg.max_source_len), cfg.d_model),
                                 jnp.bfloat16, "batch", "seq_kv", "embed")
        caches = jax.eval_shape(
            lambda e: encdec.init_caches(
                model_lib.layers.abstract_params(schema), e, cfg, shape.seq_len
            ),
            enc_sds,
        )
        caches = model_lib._abstract_like(
            caches, model_lib._axes_like(caches, model_lib.cache_axes)
        )
        pos = model_lib._sds((b,), jnp.int32, "stream")

        def step(p, tok, c, q):
            return encdec.decode_step(p, tok, c, q, cfg)

        return jax.jit(step), (params, token, caches, pos)

    state = model_lib.abstract_serve_state(cfg, b, shape.seq_len)

    def step(p, st, tok):
        return model_lib.serve_step(p, st, tok, cfg)

    # Donate the serve state: the KV cache updates in place.
    return jax.jit(step, donate_argnums=(1,)), (params, state, token)


def _cell_for(cfg, shape, tcfg_mb, w16: bool = False):
    if shape.kind == "train":
        return _train_cell(cfg, shape, tcfg_mb)
    if shape.kind == "prefill":
        return _prefill_cell(cfg, shape, w16)
    return _decode_cell(cfg, shape, w16)


def _cost_compile(cfg, shape, mesh, rules, param_dtype, w16=False):
    """Compile one (possibly shrunk+unrolled) variant; return cost numbers."""
    with sharding.activate(mesh, rules):
        jitted, args = _cell_for(
            cfg, shape, TrainConfig(microbatches=1, param_dtype=param_dtype), w16
        )
        compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis() or {}
    census = collective_census(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(census.get("total_bytes", 0)),
    }


def cost_extrapolation(cfg, shape, mesh, rules, param_dtype, w16=False) -> dict:
    """XLA cost_analysis counts loop bodies ONCE, so scanned stacks
    under-report by the trip count.  We compile UNROLLED shrunk variants at
    two depths and extrapolate linearly: cost(L) = a + b*L  (embed/logits/
    optimizer in `a`, per-layer in `b`).  Hybrid stacks extrapolate in
    pattern-groups; enc-dec varies both stacks together (equal depths)."""
    if cfg.hybrid_pattern:
        p = len(cfg.hybrid_pattern)
        l1, l2 = p, 2 * p  # 1 and 2 full groups, no tail
    else:
        l1, l2 = 1, 2

    def shrink(n):
        kw = dict(n_layers=n, unroll_layers=True)
        if cfg.enc_dec:
            kw["n_enc_layers"] = n
        return cfg.replace(**kw)

    c1 = _cost_compile(shrink(l1), shape, mesh, rules, param_dtype, w16)
    c2 = _cost_compile(shrink(l2), shape, mesh, rules, param_dtype, w16)
    out = {}
    for k in ("flops", "bytes", "coll"):
        slope = (c2[k] - c1[k]) / (l2 - l1)
        base = c1[k] - slope * l1
        out[k] = base + slope * cfg.n_layers
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, variant: str = "base",
             microbatches: int = 8) -> dict:
    cfg = _cfg_for_variant(configs.get_config(arch), variant)
    shape = configs.shape_by_name(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant,
        "kind": shape.kind,
        "n_devices": mesh.size,
    }
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        rec.update(status="skipped", reason="full attention is quadratic at 524k")
        return rec
    if shape.kind == "decode" and cfg.enc_dec and shape.name == "long_500k":
        rec.update(status="skipped", reason="enc-dec decoder capped below 500k")
        return rec

    t0 = time.time()
    with sharding.activate(mesh, _rules_for(cfg, variant, mesh)):
        if shape.kind == "train":
            over = dict(TRAIN_OVERRIDES.get(arch, {}))
            mb = min(over.pop("microbatches", microbatches), shape.global_batch)
            jitted, args = _train_cell(cfg, shape, TrainConfig(microbatches=mb, **over))
        elif shape.kind == "prefill":
            jitted, args = _prefill_cell(cfg, shape, _w16(variant))
        else:
            jitted, args = _decode_cell(cfg, shape, _w16(variant))
        lowered = jitted.lower(*args)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    census = collective_census(compiled.as_text())
    arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
    out_b = int(getattr(mem, "output_size_in_bytes", 0))
    tmp_b = int(getattr(mem, "temp_size_in_bytes", 0))
    alias_b = int(getattr(mem, "alias_size_in_bytes", 0))
    rec.update(
        status="ok",
        compile_s=round(time.time() - t0, 1),
        raw_flops=float(cost.get("flops", 0.0)),
        raw_bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        argument_bytes=arg_b,
        output_bytes=out_b,
        temp_bytes=tmp_b,
        alias_bytes=alias_b,
        # Live bytes per device: donated outputs alias their inputs.
        peak_bytes=arg_b + max(out_b - alias_b, 0) + tmp_b,
        collectives=census,
    )

    # Roofline cost terms (single-pod only, per the assignment): correct the
    # loop-body undercount via unrolled 1-/2-layer extrapolation.
    if not multi_pod:
        over = TRAIN_OVERRIDES.get(arch, {})
        ext = cost_extrapolation(
            cfg, shape, mesh, _rules_for(cfg, variant, mesh),
            over.get("param_dtype", "float32"), _w16(variant),
        )
        rec.update(
            flops=ext["flops"],
            bytes_accessed=ext["bytes"],
            collective_bytes=ext["coll"],
        )
    else:
        rec.update(
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", 0.0)),
            collective_bytes=float(census.get("total_bytes", 0)),
        )
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape name (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--out", default="results/dryrun.jsonl")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(configs.ARCH_IDS)
    shapes = [args.shape] if args.shape else [s.name for s in configs.LM_SHAPES]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    failures = 0
    with open(args.out, "a") as f:
        for arch in archs:
            for shape in shapes:
                for mp in meshes:
                    try:
                        rec = run_cell(arch, shape, mp, args.variant, args.microbatches)
                    except Exception as e:  # noqa: BLE001 — report and continue
                        failures += 1
                        rec = {
                            "arch": arch, "shape": shape,
                            "mesh": "2x16x16" if mp else "16x16",
                            "variant": args.variant, "status": "error",
                            "error": f"{type(e).__name__}: {e}",
                        }
                        traceback.print_exc()
                    print(json.dumps({k: v for k, v in rec.items() if k != "collectives"}))
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
    print(f"done; failures={failures}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
