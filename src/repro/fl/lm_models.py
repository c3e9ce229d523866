"""Moonlight-16B-A3B's one-chip share as the local model of an FL round.

Moonlight-16B-A3B (moonshotai, ``model_type`` deepseek_v3) at its
published widths: hidden 2,048; latent attention (MLA) with 16 heads, no
q-LoRA, a 512-wide KV latent and 64 rotary dims per head; 64 routed
SwiGLU experts of width 1,408 (6 per token, sigmoid router with a
selection bias, ``noaux_tc``) plus 2 shared experts; a dense 11,264-wide
SwiGLU as the first layer.  One chip holds the share that an 8-chip
expert-parallel deployment gives each chip of a layer: 8 of the 64
routed experts (``expert_offset`` names which), attention, the shared
experts and the dense layer whole, and 1/8 of the vocabulary (20,480
ids).  Depth is the dense layer plus 4 MoE layers; the other layers
would be further pipeline stages.  ``LMShape()`` is that share, with
D = 568,484,352 parameters.

Forward, per token position: embed, then per layer
``h += attn(rms(h))`` and ``h += ffn(rms(h))``, a final RMSNorm and the
head over the vocabulary slice.  The router scores all 64 experts,
picks the top 6 by score + bias, and weighs them by their scores
normalised over the 6 and scaled by 2.446; the held experts compute
their share of the routed tokens with no capacity limit (dropless: each
held expert runs over every token, weighted by the token's routing
weight for it, zero where not chosen), and what the 56 absent experts
would add is left out.  The selection bias is a
buffer SGD does not train: it stays out of the parameters, at zero.

Departures from the Hugging Face modelling code (all of them shared by
the plain reference ``bench/reference/moonlight_fl.py``):

* RoPE rotates the two halves of the 64 rotary dims (``rotate_half``)
  where DeepseekV3 first de-interleaves the pairs: the same rotation
  with the rope columns of ``q_proj`` and ``kv_a_proj_with_mqa``
  permuted, which random weights cannot tell apart;
* every matmul runs in f32 at ``Precision.HIGHEST`` (``fl.models``);
* weights are drawn normal with std 0.02 and norms start at 1, each
  parameter leaf from its own ``fold_in(key, leaf index)`` in ravel
  order, so the reference draws the same weights from the same key.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.fl.models import TaskModel, matmul
from repro.models.attention import rope
from repro.models.layers import softmax_cross_entropy


@dataclasses.dataclass(frozen=True)
class LMShape:
    """Widths and depth of the model; the defaults are the one-chip
    share of Moonlight-16B-A3B (its ``config.json`` names in comments)."""

    hidden: int = 2048            # hidden_size
    n_dense: int = 1              # first_k_dense_replace
    n_moe: int = 4                # MoE layers held (of 26)
    dense_ff: int = 11264         # intermediate_size
    heads: int = 16               # num_attention_heads
    nope: int = 128               # qk_nope_head_dim
    rope_dim: int = 64            # qk_rope_head_dim
    v_dim: int = 128              # v_head_dim
    kv_latent: int = 512          # kv_lora_rank
    n_experts: int = 64           # n_routed_experts (all, routed over)
    n_held: int = 8               # experts held on this chip
    expert_ff: int = 1408         # moe_intermediate_size
    shared_ff: int = 2816         # n_shared_experts x moe_intermediate_size
    top_k: int = 6                # num_experts_per_tok
    routed_scale: float = 2.446   # routed_scaling_factor
    vocab: int = 20480            # vocab_size / 8, the slice held
    rope_theta: float = 50000.0   # rope_theta
    eps: float = 1e-5             # rms_norm_eps
    init_std: float = 0.02
    expert_offset: int = 0        # first global expert held here


def _ffn(d: int, f: int) -> Dict[str, Tuple[int, ...]]:
    return {"down": (f, d), "gate": (d, f), "up": (d, f)}


def param_shapes(shape: LMShape) -> Dict[str, Any]:
    """The parameter tree's leaf shapes."""
    d, H = shape.hidden, shape.heads

    def layer(moe: bool):
        lyr = {"attn_norm": (d,), "mlp_norm": (d,),
               "q": (d, H * (shape.nope + shape.rope_dim)),
               # (out, in), as the Hugging Face weights: every leaf's
               # last dim is then a multiple of 128 at the published
               # widths, so the flat state's leaf slices stay views of
               # one lane-dense buffer on the TPU
               "kv_a": (shape.kv_latent + shape.rope_dim, d),
               "kv_norm": (shape.kv_latent,),
               "kv_b": (shape.kv_latent, H * (shape.nope + shape.v_dim)),
               "o": (H * shape.v_dim, d)}
        if moe:
            e, f = shape.n_held, shape.expert_ff
            lyr["moe"] = {"experts": {"down": (e, f, d), "gate": (e, d, f),
                                      "up": (e, d, f)},
                          "router": (shape.n_experts, d),
                          "shared": _ffn(d, shape.shared_ff)}
        else:
            lyr["mlp"] = _ffn(d, shape.dense_ff)
        return lyr

    return {"embed": (shape.vocab, d), "head": (d, shape.vocab),
            "layers": [layer(i >= shape.n_dense)
                       for i in range(shape.n_dense + shape.n_moe)],
            "norm": (d,)}


def _is_norm(path) -> bool:
    return str(getattr(path[-1], "key", "")).endswith("norm")


def init_params(key, shape: LMShape):
    """Seeded random weights: leaf i (ravel order) is
    ``init_std * normal(fold_in(key, i))``; RMSNorm weights are ones."""
    shapes = param_shapes(shape)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shp) in enumerate(leaves):
        if _is_norm(path):
            out.append(jnp.ones(shp, jnp.float32))
        else:
            out.append(shape.init_std * jax.random.normal(
                jax.random.fold_in(key, i), shp, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def rms_norm(x, w, eps: float):
    """DeepseekV3RMSNorm: ``w * x / sqrt(mean(x^2) + eps)`` in f32."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w * (x * jax.lax.rsqrt(var + eps))


def swiglu(x, p):
    return matmul(jax.nn.silu(matmul(x, p["gate"])) * matmul(x, p["up"]),
                  p["down"])


def mla(x, p, shape: LMShape):
    """Multi-head latent attention, causal, no q-LoRA.  x: (B, T, d)."""
    B, T, _ = x.shape
    H, nope, r = shape.heads, shape.nope, shape.rope_dim
    pos = jnp.arange(T)
    q = matmul(x, p["q"]).reshape(B, T, H, nope + r)
    kv_a = matmul(x, p["kv_a"].T)
    c_kv = rms_norm(kv_a[..., :shape.kv_latent], p["kv_norm"], shape.eps)
    k_pe = rope(kv_a[..., None, shape.kv_latent:], pos, shape.rope_theta)
    kv = matmul(c_kv, p["kv_b"]).reshape(B, T, H, nope + shape.v_dim)
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], pos, shape.rope_theta)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, T, H, r))], -1)
    v = kv[..., nope:]
    scores = matmul(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) \
        * (nope + r) ** -0.5                                # (B, H, T, T)
    scores = jnp.where(pos[:, None] >= pos[None, :], scores, -1e30)
    out = matmul(jax.nn.softmax(scores, axis=-1), v.transpose(0, 2, 1, 3))
    return matmul(out.transpose(0, 2, 1, 3).reshape(B, T, H * shape.v_dim),
                  p["o"])


def route(x, router_w, shape: LMShape):
    """Sigmoid router over all experts (``noaux_tc``, one group): the top
    ``top_k`` by score + bias (bias zero, untrained), weighted by their
    scores normalised over the chosen and scaled.  x: (N, d) ->
    (ids (N, k), weights (N, k))."""
    scores = jax.nn.sigmoid(matmul(x, router_w.T))
    bias = jnp.zeros((shape.n_experts,), scores.dtype)
    _, ids = jax.lax.top_k(scores + bias, shape.top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * shape.routed_scale


def held_experts(x, ids, w, experts, shape: LMShape):
    """The held experts' part of the routed output, dropless.

    Each held expert runs over every token and is weighted by that
    token's routing weight for it, zero where the token did not choose
    it: no capacity, no dropped token, and plain batched matmuls that
    ``vmap`` and ``grad`` take as they are (``lax.ragged_dot`` has no
    batching rule for unbatched weights).  Returns ((N, d) output,
    (n_held,) routed token counts)."""
    held = shape.expert_offset + jnp.arange(shape.n_held)
    chosen = ids[None, :, :] == held[:, None, None]           # (E, N, k)
    gates = jnp.sum(jnp.where(chosen, w[None], 0.0), axis=-1)  # (E, N)
    g = matmul(x[None], experts["gate"])                       # (E, N, f)
    u = matmul(x[None], experts["up"])
    out = matmul(jax.nn.silu(g) * u, experts["down"])          # (E, N, d)
    return (jnp.sum(gates[..., None] * out, axis=0),
            jnp.sum(chosen, axis=(1, 2)))


def forward(params, tokens, shape: LMShape):
    """tokens (B, T) int -> (logits (B, T, vocab), [(n_held,) routed
    counts per MoE layer])."""
    h = jnp.take(params["embed"], tokens, axis=0)
    B, T, d = h.shape
    loads: List[jax.Array] = []
    for lyr in params["layers"]:
        h = h + mla(rms_norm(h, lyr["attn_norm"], shape.eps), lyr, shape)
        m = rms_norm(h, lyr["mlp_norm"], shape.eps)
        if "moe" in lyr:
            mf = m.reshape(B * T, d)
            ids, w = route(mf, lyr["moe"]["router"], shape)
            out, load = held_experts(mf, ids, w, lyr["moe"]["experts"],
                                     shape)
            h = h + (out + swiglu(mf, lyr["moe"]["shared"])).reshape(B, T, d)
            loads.append(load)
        else:
            h = h + swiglu(m, lyr["mlp"])
    h = rms_norm(h, params["norm"], shape.eps)
    return matmul(h, params["head"]), loads


def expert_load_max(loads) -> jax.Array:
    """The most-loaded held expert's routed tokens over the held
    experts' mean, the worst MoE layer's (0 where none is routed)."""
    ratios = [jnp.where(jnp.sum(c) > 0,
                        jnp.max(c) / jnp.maximum(jnp.mean(c), 1e-30), 0.0)
              for c in (load.astype(jnp.float32) for load in loads)]
    return jnp.max(jnp.stack(ratios)) if ratios else jnp.float32(0.0)


def moonlight_model(shape: LMShape = LMShape()) -> TaskModel:
    """The task model: next-token cross-entropy over the vocabulary
    slice; ``x`` holds (B, T) input ids and ``y`` the (B, T) next ids."""

    def init(key):
        return init_params(key, shape)

    def loss(p, x, y):
        logits, _ = forward(p, x, shape)
        return softmax_cross_entropy(logits, y, shape.vocab)

    def metrics(p, x, y):
        logits, loads = forward(p, x, shape)
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return {"ce": softmax_cross_entropy(logits, y, shape.vocab),
                "accuracy": acc, "expert_load_max": expert_load_max(loads)}

    return TaskModel(init=init, loss=loss, metrics=metrics,
                     sharded_only=True)


def param_count(shape: LMShape) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(shape), is_leaf=lambda x: isinstance(x, tuple)))
