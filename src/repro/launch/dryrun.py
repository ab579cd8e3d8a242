import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell against the production mesh and record memory / cost / collective
numbers for the roofline analysis (EXPERIMENTS.md §Dry-run, §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --cell neurlz_enhance
Options: --multi-pod / --single-pod (default: both), --out experiments/dryrun,
         --remat {nothing,dots}, --seq-shard (sequence parallelism).
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from .. import configs  # noqa: E402
from ..configs.base import SHAPES  # noqa: E402
from ..distributed import sharding as sh  # noqa: E402
from ..models import model as M  # noqa: E402
from . import hlo_cost  # noqa: E402
from . import roofline as rl  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402


def _jsonable(d):
    if isinstance(d, dict):
        return {k: _jsonable(v) for k, v in d.items()}
    if isinstance(d, (list, tuple)):
        return [_jsonable(v) for v in d]
    if hasattr(d, "item"):
        return d.item()
    return d


def lower_cell(arch: str, shape_name: str, mesh, *, remat: str = "nothing",
               seq_shard: bool = False, donate: bool = True,
               microbatch: int = 4, skip_uncausal: bool = False,
               moe_group: int | None = None, sp_residual: bool = False):
    """Lower + compile one cell; returns the record dict."""
    import dataclasses
    cfg = configs.get_config(arch)
    if skip_uncausal:
        cfg = dataclasses.replace(cfg, attn_skip_uncausal=True)
    if moe_group is not None:
        cfg = dataclasses.replace(cfg, moe_group_size=moe_group)
    if sp_residual:
        cfg = dataclasses.replace(cfg, sp_residual=True)
    shape = SHAPES[shape_name]
    model_axis = mesh.shape["model"]
    n_chips = int(jax.device_count()) if False else 1
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    model = M.build_model(cfg, model_axis=model_axis)

    abs_params = M.abstract_params(model)
    pspecs = sh.param_pspecs(abs_params, mesh)
    params_ns = sh.to_named(pspecs, mesh)
    abs_params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abs_params, params_ns)

    specs = M.input_specs(cfg, shape)
    in_specs = sh.input_pspecs(specs, mesh, seq_shard=seq_shard)
    in_ns = {k: jax.sharding.NamedSharding(mesh, v) for k, v in in_specs.items()}
    batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=in_ns[k])
                 for k, v in specs.items()}

    t0 = time.time()
    sh.set_active_mesh(mesh)
    with mesh:
        if shape.kind == "train":
            abs_opt = M.abstract_opt_state(abs_params)
            opt_specs = sh.opt_pspecs(pspecs)
            opt_ns = sh.to_named(opt_specs, mesh)
            abs_opt = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                abs_opt, opt_ns)
            step_fn = M.make_train_step(model, remat_policy=remat,
                                        microbatch=microbatch)
            jitted = jax.jit(
                step_fn,
                in_shardings=(params_ns, opt_ns, in_ns, None),
                out_shardings=(params_ns, opt_ns, None),
                donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(abs_params, abs_opt, batch_abs,
                                   jax.ShapeDtypeStruct((), jnp.int32))
        elif shape.kind == "prefill":
            fn = (M.make_encode_step(model) if cfg.family == "audio"
                  else M.make_prefill_step(model, remat_policy=remat))
            jitted = jax.jit(fn, in_shardings=(params_ns, in_ns))
            lowered = jitted.lower(abs_params, batch_abs)
        else:  # decode
            abs_cache = M.abstract_cache(model, shape.global_batch, shape.seq_len)
            cache_specs = sh.cache_pspecs(abs_cache, mesh, shape.global_batch)
            cache_ns = sh.to_named(cache_specs, mesh)
            abs_cache = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                abs_cache, cache_ns)
            step_fn = M.make_decode_step(model)
            jitted = jax.jit(
                step_fn,
                in_shardings=(params_ns, cache_ns, in_ns["tokens"], None),
                out_shardings=(None, cache_ns),
                donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(abs_params, abs_cache, batch_abs["tokens"],
                                   jax.ShapeDtypeStruct((), jnp.int32))
        lower_s = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t1
    sh.set_active_mesh(None)

    ma = compiled.memory_analysis()
    ca = hlo_cost.xla_cost_dict(compiled)
    hlo = hlo_cost.analyze(compiled.as_text())   # loop-aware per-device cost
    flops = hlo["flops"]
    bytes_acc = hlo["bytes"]
    coll = {"wire_bytes": hlo["collective_wire_bytes"],
            "per_kind_wire": hlo["collective_per_kind"],
            "per_kind_count": hlo["collective_count"]}
    terms = rl.roofline_terms(flops, bytes_acc, coll["wire_bytes"])
    mflops = rl.model_flops(cfg, shape, n_chips)

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(mesh.shape), "n_chips": n_chips,
        "kind": shape.kind, "remat": remat, "seq_shard": seq_shard,
        "microbatch": microbatch if shape.kind == "train" else None,
        "skip_uncausal": skip_uncausal, "moe_group": moe_group,
        "sp_residual": sp_residual,
        "lower_s": round(lower_s, 1), "compile_s": round(compile_s, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_hbm_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes,
        },
        "cost": {"flops_per_device": flops, "bytes_per_device": bytes_acc,
                 "transcendentals": hlo["transcendentals"],
                 "xla_flops_loops_once": float(ca.get("flops", 0.0)),
                 "xla_bytes_loops_once": float(ca.get("bytes accessed", 0.0))},
        "collectives": coll,
        "roofline": terms,
        "model_flops_per_device": mflops,
        "useful_compute_ratio": (mflops / flops) if flops else None,
        "n_active_params": cfg.n_active_params(),
        "n_params": cfg.n_params_estimate(),
    }
    return record


def lower_neurlz_enhance(mesh, *, n_blocks: int = 512, side: int = 512,
                         batch_slices: int = 10):
    """The paper-technique cell: pod-scale batched online enhancer training.

    One train step for ``n_blocks`` per-block skipping-DNN enhancers at once
    (vmap over blocks; blocks sharded over every mesh axis) — the TPU-native
    reformulation of the paper's per-block GPU loop (DESIGN.md §3).
    """
    from ..core import skipping_dnn  # enables x64 (compressor stack) ...
    jax.config.update("jax_enable_x64", False)  # ... switch it back off

    net_cfg = skipping_dnn.SkippingDNNConfig(c_in=2)  # cross-field channels
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v

    def one_block_step(params, opt, xb, yb):
        from ..optim import adamw_update

        def loss_fn(p):
            pred = skipping_dnn.forward(p, xb, regulated=True, skip=True)
            return jnp.mean(jnp.square(pred - yb))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt = adamw_update(grads, opt, params, lr=1e-2)
        return params, opt, loss

    def train_step(params_stack, opt_stack, inputs, targets):
        p, o, losses = jax.vmap(one_block_step)(params_stack, opt_stack,
                                                inputs, targets)
        loss = jnp.mean(losses)
        try:  # under shard_map: global mean (the run's only collective)
            loss = jax.lax.pmean(loss, tuple(mesh.shape.keys()))
        except NameError:
            pass
        return p, o, loss

    def init_all():
        from ..optim import adamw_init
        keys = jax.random.split(jax.random.PRNGKey(0), n_blocks)
        params = jax.vmap(lambda k: skipping_dnn.init_params(k, net_cfg))(keys)
        return params, jax.vmap(lambda _: adamw_init(
            skipping_dnn.init_params(jax.random.PRNGKey(0), net_cfg)))(
                jnp.arange(n_blocks))

    abs_ps, abs_opt = jax.eval_shape(init_all)
    every = tuple(mesh.shape.keys())
    block_spec = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(every))

    def shard_stack(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=block_spec),
            tree)

    abs_ps, abs_opt = shard_stack(abs_ps), shard_stack(abs_opt)
    xin = jax.ShapeDtypeStruct((n_blocks, batch_slices, side, side, 2),
                               jnp.float32, sharding=block_spec)
    yin = jax.ShapeDtypeStruct((n_blocks, batch_slices, side, side, 1),
                               jnp.float32, sharding=block_spec)

    # Per-block training is embarrassingly parallel: shard_map over every
    # mesh axis pins the block dim per-device (plain pjit replicated the
    # conv activations -> 227 GiB/device, §Perf iteration C0->C1).
    from jax.sharding import PartitionSpec as P
    spec = P(every)
    smapped = jax.shard_map(train_step, mesh=mesh,
                            in_specs=(spec, spec, spec, spec),
                            out_specs=(spec, spec, P()), check_vma=False)

    t0 = time.time()
    with mesh:
        jitted = jax.jit(smapped, donate_argnums=(0, 1))
        lowered = jitted.lower(abs_ps, abs_opt, xin, yin)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    hlo = hlo_cost.analyze(compiled.as_text())
    flops = hlo["flops"]
    bytes_acc = hlo["bytes"]
    coll = {"wire_bytes": hlo["collective_wire_bytes"],
            "per_kind_wire": hlo["collective_per_kind"],
            "per_kind_count": hlo["collective_count"]}
    return {
        "arch": "neurlz_enhance", "shape": f"{n_blocks}x{side}x{side}",
        "mesh": dict(mesh.shape), "n_chips": n_chips, "kind": "train",
        "compile_s": round(time.time() - t0, 1),
        "memory": {"argument_bytes": ma.argument_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes,
                   "peak_hbm_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes},
        "cost": {"flops_per_device": flops, "bytes_per_device": bytes_acc},
        "collectives": coll,
        "roofline": rl.roofline_terms(flops, bytes_acc, coll["wire_bytes"]),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--cell", default=None, help="special cell: neurlz_enhance")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--remat", default="nothing", choices=["nothing", "dots"])
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--skip-uncausal", action="store_true")
    ap.add_argument("--moe-group", type=int, default=None,
                    help="override MoE routing group size (perf lever)")
    ap.add_argument("--sp-residual", action="store_true",
                    help="sequence-parallel residual stream (perf lever)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose JSON already exists with status ok")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = []
    if args.single_pod or not args.multi_pod:
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.multi_pod or not args.single_pod:
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    cells = []
    if args.cell == "neurlz_enhance":
        cells = [("neurlz_enhance", None)]
    elif args.all:
        cells = configs.cells() + [("neurlz_enhance", None)]
    elif args.arch:
        shapes = [args.shape] if args.shape else [
            s for a, s in configs.cells() if a == args.arch]
        cells = [(args.arch, s) for s in shapes]
    else:
        ap.error("pass --all, --arch, or --cell")

    failures = 0
    for mesh_name, mesh in meshes:
        for arch, shape in cells:
            tag = f"{arch}_{shape or 'na'}_{mesh_name}" + (
                f"_{args.tag}" if args.tag else "")
            path = os.path.join(args.out, tag + ".json")
            if args.resume and os.path.exists(path):
                try:
                    with open(path) as f:
                        if json.load(f).get("status") == "ok":
                            print(f"=== {tag} === (cached)", flush=True)
                            continue
                except Exception:
                    pass
            print(f"=== {tag} ===", flush=True)
            try:
                if arch == "neurlz_enhance":
                    rec = lower_neurlz_enhance(mesh)
                else:
                    rec = lower_cell(arch, shape, mesh, remat=args.remat,
                                     seq_shard=args.seq_shard,
                                     microbatch=args.microbatch,
                                     skip_uncausal=args.skip_uncausal,
                                     moe_group=args.moe_group,
                                     sp_residual=args.sp_residual)
                rec["status"] = "ok"
                r = rec["roofline"]
                print(f"  compile={rec.get('compile_s', '?')}s "
                      f"peak_hbm={rec['memory']['peak_hbm_bytes']/2**30:.2f}GiB "
                      f"compute={r['compute_s']*1e3:.2f}ms "
                      f"memory={r['memory_s']*1e3:.2f}ms "
                      f"coll={r['collective_s']*1e3:.2f}ms "
                      f"dominant={r['dominant']}", flush=True)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures += 1
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "status": "fail", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"  FAIL: {type(e).__name__}: {e}", flush=True)
            with open(path, "w") as f:
                json.dump(_jsonable(rec), f, indent=1)
    print(f"done; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
