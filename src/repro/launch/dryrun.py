import os
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")
# ^ MUST be the first lines: jax locks the device count on first init.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run: AOT ``.lower().compile()`` of every
(architecture x input-shape x mesh) cell on the production meshes.

For each cell this driver:
  1. builds the step function (train_step / prefill_step / serve_step per
     the shape kind) with FSDP+TP in/out shardings from the rule engine,
  2. lowers and compiles it against ShapeDtypeStruct stand-ins (no device
     allocation — the full configs never materialize),
  3. records ``compiled.memory_analysis()`` (proves it fits) and
     ``compiled.cost_analysis()`` + the trip-aware HLO cost walk
     (FLOPs / HBM bytes / collective bytes for §Roofline).

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --out results.json
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config, shape_applicable
from repro.configs.base import SHAPES, TPU_V5E, ModelConfig, ShapeConfig
from repro.launch import hlo_cost
from repro.launch.mesh import (auto_mesh, make_ctx, make_production_mesh,
                               make_rules)
from repro.models import zoo
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.sharding import mesh_ctx
from repro.sharding.rules import ShardingRules


# ------------------------------------------------------------- shardings --
def _guard(mesh, shape, spec: P) -> P:
    """Shrink axes that do not divide the dim: try successively shorter
    prefixes of the axis tuple before replicating (e.g. batch 256 on a
    512-way ("pod","data","model") dp falls back to ("pod","data"))."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def axsize(names):
        total = 1
        for nm in names:
            total *= sizes.get(nm, 1)
        return total

    fixed = []
    for dim, entry in enumerate(spec):
        if entry is None or dim >= len(shape):
            fixed.append(None)
            continue
        names = tuple(entry) if isinstance(entry, tuple) else (entry,)
        while names and shape[dim] % axsize(names) != 0:
            names = names[:-1]
        fixed.append(names if len(names) > 1 else
                     (names[0] if names else None))
    return P(*fixed)


def batch_shardings(cfg: ModelConfig, avals: dict, mesh, ctx):
    out = {}
    for name, a in avals.items():
        if name == "pos":
            out[name] = NamedSharding(mesh, P())
            continue
        base = [None] * len(a.shape)
        spec = ctx.resolve("dp", *base[1:])
        out[name] = NamedSharding(mesh, _guard(mesh, a.shape, spec))
    return out


def _shardings_from_specs(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


# ------------------------------------------------------- step functions --
def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               rules: ShardingRules, adamw: AdamWConfig = AdamWConfig(),
               profile: str = "tp_sp", accum: int = 1):
    """Returns (fn, args_avals, in_shardings, out_shardings, donate)."""
    ctx = make_ctx(mesh, profile)
    p_avals = zoo.param_avals(cfg)
    p_specs = rules.tree_specs(p_avals, mesh)
    p_shard = _shardings_from_specs(mesh, p_specs)
    b_avals = zoo.batch_shapes(cfg, shape)
    b_shard = batch_shardings(cfg, b_avals, mesh, ctx)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        o_avals = jax.eval_shape(adamw_init, p_avals)
        # optimizer moments inherit param specs; step replicated
        o_shard = type(o_avals)(
            step=repl,
            m=_shardings_from_specs(mesh, rules.tree_specs(o_avals.m, mesh)),
            v=_shardings_from_specs(mesh, rules.tree_specs(o_avals.v, mesh)))

        def train_step(params, opt_state, batch):
            if accum > 1:
                # gradient accumulation: scan over microbatches (divides
                # the activation peak by accum — §Perf memory iteration)
                from repro.models.layers import trip_scope
                micro = jax.tree.map(
                    lambda x: x.reshape(accum, x.shape[0] // accum,
                                        *x.shape[1:]), batch)

                def body(acc, mb):
                    with trip_scope(accum):
                        loss, g = jax.value_and_grad(
                            lambda p: zoo.loss_fn(p, cfg, mb)[0])(params)
                    return (acc[0] + loss,
                            jax.tree.map(jnp.add, acc[1], g)), None
                zero = (jnp.zeros(()),
                        jax.tree.map(
                            lambda p: jnp.zeros(p.shape, jnp.float32),
                            params))
                (loss_sum, grads), _ = jax.lax.scan(body, zero, micro)
                loss = loss_sum / accum
                grads = jax.tree.map(lambda g: g / accum, grads)
                metrics = {"xent": loss, "aux": jnp.zeros(())}
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: zoo.loss_fn(p, cfg, batch),
                    has_aux=True)(params)
            new_p, new_o, om = adamw_update(adamw, grads, opt_state, params)
            return new_p, new_o, {"loss": loss, **metrics, **om}

        args = (p_avals, o_avals, b_avals)
        in_sh = (p_shard, o_shard, b_shard)
        out_sh = (p_shard, o_shard,
                  {"loss": repl, "xent": repl, "aux": repl,
                   "grad_norm": repl})
        return train_step, args, in_sh, out_sh, (0, 1)

    if shape.kind == "prefill":
        max_seq = shape.seq_len // 2 if cfg.family == "audio" else \
            shape.seq_len

        def prefill_step(params, batch):
            logits, cache = zoo.prefill(params, cfg, batch, max_seq=max_seq)
            return jnp.argmax(logits, -1), cache

        cache_av = jax.eval_shape(
            lambda p, b: zoo.prefill(p, cfg, b, max_seq=max_seq)[1],
            p_avals, b_avals)
        c_specs = zoo.cache_specs(cfg, cache_av, mesh)
        c_shard = _shardings_from_specs(mesh, c_specs)
        tok_sh = batch_shardings(cfg, {"t": jax.ShapeDtypeStruct(
            (shape.global_batch, 1), jnp.int32)}, mesh, ctx)["t"]
        return (prefill_step, (p_avals, b_avals), (p_shard, b_shard),
                (tok_sh, c_shard), ())

    # decode: one token, cache of seq_len
    cache_av = zoo.decode_cache_avals(cfg, shape)
    c_specs = zoo.cache_specs(cfg, cache_av, mesh)
    c_shard = _shardings_from_specs(mesh, c_specs)
    tok_aval = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos_aval = jax.ShapeDtypeStruct((), jnp.int32)
    tok_sh = batch_shardings(cfg, {"token": tok_aval}, mesh, ctx)["token"]

    def serve_step(params, cache, token, pos):
        logits, cache = zoo.decode_step(params, cfg,
                                        {"token": token, "pos": pos}, cache)
        return jnp.argmax(logits, -1), cache

    args = (p_avals, cache_av, tok_aval, pos_aval)
    in_sh = (p_shard, c_shard, tok_sh, NamedSharding(mesh, P()))
    out_sh = (tok_sh, c_shard)
    return serve_step, args, in_sh, out_sh, (1,)


# -------------------------------------------------------------- dry run --
def dryrun_cell(arch: str, shape_name: str, mesh, *, verbose: bool = True,
                rules: ShardingRules | None = None,
                profile: str = "tp_sp", accum: int = 1,
                cfg_overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "profile": profile,
           "overrides": cfg_overrides or {},
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "n_devices": mesh.devices.size}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    rules = rules or make_rules(mesh, profile)
    try:
        t0 = time.time()
        with mesh_ctx(make_ctx(mesh, profile)):
            fn, args, in_sh, out_sh, donate = build_cell(
                cfg, shape, mesh, rules, profile=profile, accum=accum)
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            with mesh:
                lowered = jitted.lower(*args)
                t_lower = time.time() - t0
                t0 = time.time()
                compiled = lowered.compile()
                t_compile = time.time() - t0
        mem = {}
        try:
            ma = compiled.memory_analysis()
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    mem[k] = int(v)
        except Exception as e:                        # pragma: no cover
            mem["error"] = str(e)
        try:
            ca = compiled.cost_analysis()
            cost = {k: float(ca[k]) for k in ("flops", "bytes accessed")
                    if k in ca}
        except Exception as e:                        # pragma: no cover
            cost = {"error": str(e)}
        walk = hlo_cost.parse_hlo_costs(compiled.as_text())
        hbm = (mem.get("argument_size_in_bytes", 0)
               + mem.get("temp_size_in_bytes", 0)
               + mem.get("output_size_in_bytes", 0)
               - mem.get("alias_size_in_bytes", 0))
        mem["hbm_per_device"] = hbm
        # XLA:CPU has no native bf16: it promotes bf16 temps to f32, so the
        # CPU-reported temp overstates the TPU bf16 footprint ~2x (verified
        # empirically: bf16 and f32 configs compile to equal temp sizes).
        mem["hbm_per_device_tpu_bf16_est"] = int(
            mem.get("argument_size_in_bytes", 0)
            + 0.55 * mem.get("temp_size_in_bytes", 0)
            + mem.get("output_size_in_bytes", 0)
            - mem.get("alias_size_in_bytes", 0))
        rec.update(status="ok", lower_s=round(t_lower, 2),
                   compile_s=round(t_compile, 2), memory=mem,
                   xla_cost=cost, hlo_walk=walk.as_dict(),
                   model_params=cfg.param_count(),
                   model_active_params=cfg.active_param_count())
        if verbose:
            tot = hbm
            print(f"[ok] {arch} x {shape_name} x {rec['mesh']}: "
                  f"lower {t_lower:.1f}s compile {t_compile:.1f}s, "
                  f"mem/dev ~{tot / 1e9:.2f} GB, "
                  f"walk flops {walk.flops / 1e12:.2f}T, "
                  f"coll {walk.collective_bytes / 1e9:.3f} GB")
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[ERR] {arch} x {shape_name}: {e}")
    return rec


def smoke_cell(arch: str, mesh, profile: str = "tp_sp") -> dict:
    """Reduced-config tiny-shape compile on a small mesh — a fast
    integration check of the whole dry-run machinery (used by tests)."""
    from repro.configs import reduced_config
    cfg = reduced_config(get_config(arch))
    shape = ShapeConfig("smoke", seq_len=64, global_batch=4, kind="train")
    rules = make_rules(mesh, profile)
    with mesh_ctx(make_ctx(mesh, profile)):
        fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, mesh,
                                                     rules, profile=profile)
        with mesh:
            compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                               donate_argnums=donate).lower(*args).compile()
    walk = hlo_cost.parse_hlo_costs(compiled.as_text())
    return {"arch": arch, "flops": walk.flops,
            "collective_count": walk.collective_count}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_NAMES + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs on a (2,2) mesh (CI-speed)")
    ap.add_argument("--profile", default="tp_sp",
                    choices=["tp_sp", "fsdp", "fsdp_sp", "fsdp_ep"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.smoke:
        mesh = auto_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4])
        for arch in (ARCH_NAMES if not args.arch else [args.arch]):
            rec = smoke_cell(arch, mesh, args.profile)
            print(f"[smoke-ok] {arch}: flops={rec['flops']:.3g} "
                  f"collectives={rec['collective_count']}")
        return

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(multi_pod=False),
                  make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    arches = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    records = []
    for mesh in meshes:
        for arch in arches:
            for shape in shapes:
                rec = dryrun_cell(arch, shape, mesh, profile=args.profile)
                records.append(rec)
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(records, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\n== dry-run: {n_ok} ok / {n_skip} skipped / {n_err} errors ==")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
