import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

This proves the distribution config is coherent without real hardware:
  - the production mesh builds (16×16 single pod; 2×16×16 multi-pod),
  - every step function lowers and compiles under SPMD partitioning,
  - memory_analysis() reports the per-device footprint,
  - cost_analysis() + HLO collective parsing feed the §Roofline table.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.jsonl
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.fl.dist import OTAConfig
from repro.launch import mesh as mesh_lib
from repro.launch import roofline
from repro.launch import steps as steps_lib
from repro.models.api import Model
from repro.models.config import INPUT_SHAPES
from repro.optim import optimizers


# The dry-run compiles on host devices and models the production target,
# so the roofline is priced at the target's peaks, not the host's.
TARGET_DEVICE_KIND = "TPU v5 lite"


def _mesh_name(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def build_lowered(arch: str, shape_name: str, mesh, *,
                  ota: bool = True, fsdp=None, worker_axes=None,
                  dtype=jnp.bfloat16, remat: bool = True):
    """Lower the right step function for (arch, shape) on `mesh`."""
    cfg = registry.get_config(arch, shape_name)
    shape = INPUT_SHAPES[shape_name]
    model = Model(cfg)
    plan = steps_lib.plan_for(cfg, mesh, force_fsdp=fsdp,
                              force_worker_axes=worker_axes)
    params_sds, pspecs = steps_lib.abstract_params(model, mesh, plan, dtype)
    meta = {
        "arch": arch, "shape": shape_name, "mesh": _mesh_name(mesh),
        "kind": shape.kind, "worker_axes": list(plan.worker_axes),
        "fsdp_axes": list(plan.fsdp_axes),
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    with jax.set_mesh(mesh):  # in-model sharding constraints
        if shape.kind == "train":
            opt = optimizers.adamw(1e-4)
            ota_cfg = OTAConfig() if ota else None
            step = steps_lib.make_train_step(model, mesh, plan, opt,
                                             ota_cfg=ota_cfg, remat=remat)
            opt_sds = steps_lib.abstract_opt_state(opt, params_sds, mesh,
                                                   pspecs)
            batch_sds = steps_lib.abstract_batch(cfg, shape, mesh, plan,
                                                 dtype)
            key_sds, step_sds = steps_lib.abstract_scalars(mesh)
            lowered = jax.jit(step).lower(params_sds, opt_sds, batch_sds,
                                          key_sds, step_sds)
            ntok = shape.global_batch * shape.seq_len
            meta["model_flops"] = roofline.model_flops(cfg, ntok)  # 6ND
        elif shape.kind == "prefill":
            fn = steps_lib.make_prefill_step(model)
            batch_sds = steps_lib.abstract_batch(cfg, shape, mesh, plan,
                                                 dtype)
            lowered = jax.jit(fn).lower(params_sds, batch_sds)
            ntok = shape.global_batch * shape.seq_len
            meta["model_flops"] = 2.0 * cfg.active_param_count() * ntok
        else:  # decode: ONE new token against a seq_len KV cache
            fn = steps_lib.make_decode_step(model)
            caches_sds = steps_lib.abstract_caches(model, shape, mesh, plan,
                                                   dtype)
            B = shape.global_batch
            nb = 1
            for a in plan.batch_axes:
                nb *= mesh.shape[a]
            tok_spec = jax.sharding.PartitionSpec(
                plan.batch_axes if len(plan.batch_axes) > 1 else
                (plan.batch_axes[0] if plan.batch_axes else None))
            if B % max(nb, 1) or B < nb:
                tok_spec = jax.sharding.PartitionSpec()
            tokens_sds = jax.ShapeDtypeStruct(
                (B, 1), jnp.int32,
                sharding=jax.sharding.NamedSharding(mesh, tok_spec))
            pos_sds = jax.ShapeDtypeStruct(
                (), jnp.int32,
                sharding=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
            lowered = jax.jit(fn).lower(params_sds, caches_sds, tokens_sds,
                                        pos_sds)
            meta["model_flops"] = 2.0 * cfg.active_param_count() * B
    return lowered, meta


def run_one(arch: str, shape_name: str, mesh, **kw):
    t0 = time.time()
    lowered, meta = build_lowered(arch, shape_name, mesh, **kw)
    compiled = lowered.compile()
    meta["compile_s"] = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    if mem is not None:
        meta["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        }
        live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        meta["memory"]["live_bytes"] = int(live)
        meta["memory"]["fits_16gb"] = bool(live < 16e9)
    rf = roofline.analyze(compiled, TARGET_DEVICE_KIND)
    meta["roofline"] = rf.to_dict()
    if meta.get("model_flops"):
        n_chips = 1
        for a in mesh.axis_names:
            n_chips *= mesh.shape[a]
        useful = meta["model_flops"] / n_chips
        meta["roofline"]["useful_flops_frac"] = (
            useful / rf.flops if rf.flops else None)
    return meta


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-ota", dest="ota", action="store_false")
    ap.add_argument("--fsdp", choices=["auto", "on", "off"], default="auto")
    ap.add_argument("--no-remat", dest="remat", action="store_false")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list(registry.ARCHS) if args.all or not args.arch \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape \
        else [args.shape]
    meshes = []
    if args.both_meshes:
        meshes = [mesh_lib.make_production_mesh(multi_pod=False),
                  mesh_lib.make_production_mesh(multi_pod=True)]
    else:
        meshes = [mesh_lib.make_production_mesh(multi_pod=args.multi_pod)]
    fsdp = {"auto": None, "on": True, "off": False}[args.fsdp]

    results, failures = [], []
    out_f = open(args.out, "a") if args.out else None
    for mesh in meshes:
        for arch in archs:
            for shape in shapes:
                if not registry.applicable(arch, shape):
                    print(f"SKIP  {arch:22s} {shape:12s} "
                          f"({registry.SKIPS[(arch, shape)]})")
                    continue
                tag = f"{arch:22s} {shape:12s} {_mesh_name(mesh)}"
                try:
                    meta = run_one(arch, shape, mesh, ota=args.ota,
                                   fsdp=fsdp, remat=args.remat)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((tag, repr(e)))
                    print(f"FAIL  {tag}: {e}")
                    continue
                rf = meta["roofline"]
                mem = meta.get("memory", {})
                print(f"OK    {tag}  compile={meta['compile_s']}s "
                      f"flops/dev={rf['flops']:.3e} "
                      f"bytes/dev={rf['bytes_accessed']:.3e} "
                      f"coll/dev={rf['collective_bytes']:.3e} "
                      f"bottleneck={rf['bottleneck']} "
                      f"live={mem.get('live_bytes', 0)/1e9:.2f}GB")
                results.append(meta)
                if out_f:
                    out_f.write(json.dumps(meta) + "\n")
                    out_f.flush()
    if out_f:
        out_f.close()
    print(f"\n{len(results)} OK, {len(failures)} FAIL")
    for tag, err in failures:
        print(f"  FAIL {tag}: {err[:160]}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
