"""End-to-end training driver: OTA-FL aggregation on a real device mesh.

Runs on whatever devices exist (CPU smoke / TPU pod).  For the production
dry-run (ShapeDtypeStructs, 512 placeholder devices) use ``dryrun.py``.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \\
      --steps 20 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch rwkv6-7b --smoke \\
      --policy perfect --steps 50
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.configs import registry
from repro.core.objectives import Case
from repro.data import synthetic
from repro.fl.dist import OTAConfig
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models.api import Model
from repro.models.config import ShapeConfig
from repro.optim import optimizers


def build(args):
    cfg = registry.get_config(args.arch)
    if args.smoke:
        cfg = registry.reduced(cfg)
    model = Model(cfg)
    mesh = mesh_lib.make_smoke_mesh(model=args.model_parallel)
    plan = steps_lib.plan_for(cfg, mesh)
    opt = optimizers.adamw(args.lr)
    ota = None
    if args.policy != "perfect":
        ota = OTAConfig(policy=args.policy,
                        granularity=args.granularity,
                        n_buckets=args.buckets,
                        case=Case.GD_NONCONVEX)
    step_fn = steps_lib.make_train_step(model, mesh, plan, opt, ota_cfg=ota,
                                        remat=not args.no_remat)
    return cfg, model, mesh, plan, opt, step_fn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (2 layer-groups, d_model<=512)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--policy", default="inflota",
                    choices=["inflota", "random", "perfect"])
    ap.add_argument("--granularity", default="tensor",
                    choices=["tensor", "bucket"])
    ap.add_argument("--buckets", type=int, default=64)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg, model, mesh, plan, opt, step_fn = build(args)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)} workers over {plan.worker_axes} "
          f"policy={args.policy}")

    key = jax.random.PRNGKey(args.seed)
    with jax.set_mesh(mesh):
        params = model.init(key, dtype=jnp.float32)
        opt_state = opt.init(params)
        start = 0
        if args.ckpt_dir and store.latest_step(args.ckpt_dir) is not None:
            (params, opt_state), extra = store.restore(
                args.ckpt_dir, (params, opt_state))
            start = extra.get("step", 0)
            print(f"restored step {start} from {args.ckpt_dir}")

        stream = synthetic.token_stream(args.batch, args.seq,
                                        cfg.vocab_size, seed=args.seed)
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))
        carry = None   # stateful ChannelModel state, threaded step-to-step
        t0 = time.time()
        for t in range(start, args.steps):
            np_batch = next(stream)
            batch = {k: jnp.asarray(v) for k, v in np_batch.items()}
            if cfg.family == "encdec":
                batch["frames"] = jnp.asarray(np.random.default_rng(t).normal(
                    size=(args.batch, cfg.encoder_seq, cfg.d_model)) * 0.1,
                    jnp.float32)
            if cfg.family == "vlm":
                batch["patches"] = jnp.asarray(np.random.default_rng(t).normal(
                    size=(args.batch, cfg.prefix_tokens, cfg.d_model)) * 0.1,
                    jnp.float32)
            params, opt_state, m = jitted(params, opt_state, batch, key,
                                          jnp.int32(t), carry)
            new_carry = m.pop("channel_carry", None)
            if new_carry is not None and jax.tree.leaves(new_carry):
                # stateful fading models: thread the state (the structure
                # change None -> carry retraces once, on step 2 only)
                carry = new_carry
            if t == start:
                print(f"compile+first step {time.time()-t0:.1f}s")
            loss = float(m["loss"])
            assert np.isfinite(loss), f"non-finite loss at step {t}"
            extras = ""
            if "selected_frac" in m:
                extras = (f" sel={float(m['selected_frac']):.2f}"
                          f" b={float(m['b_mean']):.3g}")
            print(f"step {t:4d}  loss {loss:.4f}{extras}")
            if (args.ckpt_dir and args.ckpt_every
                    and (t + 1) % args.ckpt_every == 0):
                store.save(args.ckpt_dir, t + 1, (params, opt_state),
                           extra={"step": t + 1}, keep=3)
        dt = time.time() - t0
        print(f"done: {args.steps - start} steps in {dt:.1f}s")


if __name__ == "__main__":
    main()
