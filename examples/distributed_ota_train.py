"""End-to-end driver: OTA aggregation as a first-class feature of
data-parallel LM training (the framework layer).

Trains a ~100M-parameter qwen2-family model for a few hundred steps on the
synthetic token stream, with the paper's INFLOTA worker-selection/power-
scaling policy applied to every gradient aggregation.  Each data-parallel
shard of the mesh is one FL worker.

On this CPU container it runs a reduced model by default; pass --d-model /
--layers to scale up to the full ~100M (slow on CPU, shape-identical on
TPU).

Run:  PYTHONPATH=src python examples/distributed_ota_train.py --steps 200
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core.objectives import Case
from repro.data import synthetic
from repro.fl.dist import OTAConfig
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models.api import Model
from repro.optim import optimizers

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--seq", type=int, default=256)
ap.add_argument("--d-model", type=int, default=256)
ap.add_argument("--layers", type=int, default=4)
ap.add_argument("--vocab", type=int, default=4096)
ap.add_argument("--policy", default="inflota",
                choices=["inflota", "random", "perfect"])
ap.add_argument("--lr", type=float, default=3e-4)
args = ap.parse_args()

# a qwen2-family config scaled for this machine (~100M at d=768/L=12)
base = registry.get_config("qwen2-0.5b")
cfg = dataclasses.replace(
    base, name="qwen2-ota-example",
    n_layers=args.layers, d_model=args.d_model,
    n_heads=max(4, args.d_model // 64), n_kv_heads=2,
    head_dim=64, d_ff=args.d_model * 4, vocab_size=args.vocab)
model = Model(cfg)
print(f"model: {cfg.param_count()/1e6:.1f}M params, "
      f"{cfg.n_layers}L d={cfg.d_model}")

mesh = mesh_lib.make_smoke_mesh()
plan = steps_lib.plan_for(cfg, mesh)
opt = optimizers.adamw(args.lr, grad_clip_norm=1.0)
ota = None if args.policy == "perfect" else OTAConfig(
    policy=args.policy, granularity="bucket", n_buckets=32,
    case=Case.GD_NONCONVEX)
train_step = steps_lib.make_train_step(model, mesh, plan, opt, ota_cfg=ota)

key = jax.random.PRNGKey(0)
with jax.set_mesh(mesh):
    params = model.init(key, jnp.float32)
    opt_state = opt.init(params)
    stream = synthetic.token_stream(args.batch, args.seq, cfg.vocab_size)
    jitted = jax.jit(train_step, donate_argnums=(0, 1))
    losses = []
    t0 = time.time()
    for t in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(stream).items()}
        params, opt_state, m = jitted(params, opt_state, batch, key,
                                      jnp.int32(t))
        losses.append(float(m["loss"]))
        if t % 20 == 0 or t == args.steps - 1:
            sel = (f"  sel={float(m['selected_frac']):.2f}"
                   if "selected_frac" in m else "")
            print(f"step {t:4d}  loss {losses[-1]:.4f}{sel}")
    dt = time.time() - t0

first, last = np.mean(losses[:10]), np.mean(losses[-10:])
print(f"\n{args.steps} steps in {dt:.0f}s "
      f"({args.steps * args.batch * args.seq / dt:.0f} tok/s)")
print(f"loss {first:.3f} -> {last:.3f} "
      f"({'LEARNING' if last < first - 0.1 else 'check hyperparams'})")
