"""Batched serving example: prefill-free incremental decode for three
architecture families (dense GQA, RWKV6 SSM, RecurrentGemma hybrid).

Run:  PYTHONPATH=src python examples/serve_batched.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch import mesh as mesh_lib
from repro.launch.serve import generate
from repro.models.api import Model

BATCH, PROMPT, GEN = 4, 24, 12

for arch in ("qwen2-0.5b", "rwkv6-7b", "recurrentgemma-2b"):
    cfg = registry.reduced(registry.get_config(arch))
    model = Model(cfg)
    mesh = mesh_lib.make_smoke_mesh()
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (BATCH, PROMPT)),
                         jnp.int32)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        out = generate(model, params, prompt, max_seq=PROMPT + GEN,
                       gen=GEN, temperature=0.8)
    assert out.shape == (BATCH, GEN)
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))
    print(f"{arch:20s} family={cfg.family:7s} "
          f"generated {out.shape} ids, first row: {np.asarray(out[0])[:8]}")
print("OK")
