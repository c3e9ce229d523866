"""Operations and bytes one worker-sharded round of the Moonlight-16B-A3B
share needs, whatever kernels or fusions compute it (f32 throughout).

Work: every worker's one SGD step on ``k_b`` sequences of ``seq_len``
tokens, at 6 FLOPs per active parameter per token (2 forward, 4
backward) plus causal attention (QK^T over the 128 + 64 query-key dims
and AV over the 128 value dims, each 2 FLOPs per head per earlier
position, tripled for the backward).  Active per token: the head, every
layer's attention, the dense layer's FFN, each MoE layer's router and
shared experts, and the held experts at the expected routing (``top_k``
of ``n_experts`` choose each, so ``n_held * top_k / n_experts`` held
experts a token).  The embedding is a lookup and not counted.  Then the
O(U log U) Theorem-4 search (9 U D for the curves and their argmin), the
transmit (``transmit`` below, every block) and the descale with the
three per-entry beta reductions (6 U D + 3 D).

Bytes are the D-streams that must cross HBM at least once: each worker
reads the parameters once for its step, and the round reads w_{t-1} and
w_{t-2} and writes w_t, so 4 (U + 3) D.  Activations, gradients and the
selection are intermediates and not counted.
"""

import math

F32 = 4


def active_params(shape: dict) -> float:
    """Parameters a token uses in the forward pass, at the expected
    routing (``shape`` under ``repro.fl.lm_models.LMShape``'s names)."""
    d, H = shape["hidden"], shape["heads"]
    attn = (d * H * (shape["nope"] + shape["rope_dim"])
            + d * (shape["kv_latent"] + shape["rope_dim"])
            + shape["kv_latent"] * H * (shape["nope"] + shape["v_dim"])
            + H * shape["v_dim"] * d)
    dense = 3 * d * shape["dense_ff"]
    held = (shape["n_held"] * shape["top_k"] / shape["n_experts"]
            * 3 * d * shape["expert_ff"])
    moe = shape["n_experts"] * d + 3 * d * shape["shared_ff"] + held
    return (d * shape["vocab"] + shape["n_dense"] * (attn + dense)
            + shape["n_moe"] * (attn + moe))


def attention_flops(shape: dict, seq_len: int) -> float:
    """Forward and backward FLOPs of the score and value products of one
    sequence, causal (position t attends to t + 1 positions)."""
    H = shape["heads"]
    per_pos = 2 * H * (shape["nope"] + shape["rope_dim"] + shape["v_dim"])
    pairs = seq_len * (seq_len + 1) / 2
    layers = shape["n_dense"] + shape["n_moe"]
    return 3.0 * layers * per_pos * pairs


def transmit(U_b: int, D: int) -> dict:
    """One block's transmit added onto the carried superposition: beta
    from the rank-1 factors 3 U_b D, |K b w / h| 4 U_b D, the clipped
    signal 3 U_b D, its superposition 2 U_b D; read w (U_b D), s, b and
    the carried y (3 D) and the six per-worker scalars, write y (D)."""
    return {"flops": float(12 * U_b * D),
            "bytes": float(F32 * (U_b * D + 4 * D + 6 * U_b))}


def counts(shape: dict, U: int, k_b: int, seq_len: int, D: int,
           workers_per_block: int = 1) -> dict:
    tokens = U * k_b * seq_len
    local = (6.0 * active_params(shape) * tokens
             + U * k_b * attention_flops(shape, seq_len))
    search = 2 * U * math.log2(max(U, 2)) + U + 9 * U * D
    blocks = U // workers_per_block
    tx = blocks * transmit(workers_per_block, D)["flops"]
    flops = local + search + tx + 6 * U * D + 3 * D
    nbytes = F32 * (U + 3) * D
    return {"flops": float(flops), "bytes": float(nbytes)}
