"""Serving driver: batched prefill + KV-cache decode on real devices.

Serving has no over-the-air aggregation (DESIGN.md §4): these paths
exercise the framework's inference side for the assigned decode shapes.

NOTE: this is MODEL INFERENCE serving (token generation).  Serving
experiment grids — the long-lived sweep daemon answering SweepSpec
requests from the result store — is the separate ``repro.serve``
package (``python -m repro.serve``, docs/service.md).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \\
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch import mesh as mesh_lib
from repro.models.api import Model


def generate(model: Model, params, prompt, max_seq: int, gen: int,
             temperature: float = 0.0, key=None):
    """Greedy/sampled generation. prompt: (B, P) int32. Returns (B, gen)."""
    cfg = model.cfg
    B, P = prompt.shape
    caches = model.init_decode_caches(B, max_seq, dtype=jnp.float32)

    # prefill the prompt through decode steps (robust for every family)
    decode = jax.jit(model.decode_step)

    def sample(logits, k):
        # embeddings are padded to a shardable vocab multiple; mask the pad
        vpad = logits.shape[-1]
        if vpad != cfg.vocab_size:
            mask = jnp.arange(vpad) < cfg.vocab_size
            logits = jnp.where(mask, logits, -1e30)
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(k, logits / temperature).astype(
            jnp.int32)

    toks = []
    key = key if key is not None else jax.random.PRNGKey(0)
    last = None
    for p in range(P):
        last, caches = decode(params, caches, prompt[:, p:p + 1],
                              jnp.int32(p))
    cur = sample(last, key)
    toks.append(cur)
    for g in range(1, gen):
        key, k = jax.random.split(key)
        last, caches = decode(params, caches, cur[:, None],
                              jnp.int32(P + g - 1))
        cur = sample(last, k)
        toks.append(cur)
    return jnp.stack(toks, axis=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = registry.get_config(args.arch)
    if args.smoke:
        cfg = registry.reduced(cfg)
    model = Model(cfg)
    mesh = mesh_lib.make_smoke_mesh(model=args.model_parallel)
    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(args.seed), jnp.float32)
        t0 = time.time()
        out = generate(model, params, prompt,
                       max_seq=args.prompt_len + args.gen, gen=args.gen,
                       temperature=args.temperature)
        out.block_until_ready()
        dt = time.time() - t0
    n_tok = args.batch * args.gen
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"generated {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s incl. compile)")
    print("sample token ids:", np.asarray(out[0])[:12])
    assert out.shape == (args.batch, args.gen)
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))
    print("OK")


if __name__ == "__main__":
    main()
