"""Plain float32 reference of an OTA-FL round whose local model is
Moonlight-16B-A3B's one-chip share.

The model (``huggingface.co/moonshotai/Moonlight-16B-A3B``, deepseek_v3):
per layer ``h += MLA(rms(h))`` and ``h += FFN(rms(h))``; MLA with no
q-LoRA (q = x W_q split into 128 nope and 64 rotary dims per head; a
512 + 64 wide KV projection, an RMSNorm on the 512 latent, K/V from the
latent, one rotary key shared by the heads, softmax scale 1/sqrt(192),
causal); the first layer's FFN a dense SwiGLU, the others a sigmoid
router over all experts (top-k by score + a zero bias, weights
normalised over the k and scaled) feeding the experts held here, plus
the shared experts; a final RMSNorm and the head over the vocabulary
slice.  The held experts run densely over every token, each weighted by
that token's routing weight for it (zero where not chosen): dropless by
construction.  What the absent experts would add is left out, as in the
system.

Departures from the Hugging Face modelling code, shared with the system
(``src/repro/fl/lm_models.py``): RoPE rotates the two halves of the 64
rotary dims (``rotate_half``) where DeepseekV3 de-interleaves pairs
first, which is the same rotation up to a fixed permutation of random
weight columns; every matmul is f32 at ``highest`` precision; weights
are normal with std 0.02 (leaf i of the ravel order from
``fold_in(key, i)``), norms start at 1.

The round (arXiv 2104.03490, Algorithm 1 with Theorem 4): each worker,
one at a time, takes one SGD step on k_b sequences drawn without
replacement; the PS draws iid Exp(1) gains, solves Theorem 4 for the
rank-1 channel (per candidate worker k, in worker order, its curve
R_t over every entry; a later candidate wins an entry only when
strictly better), the workers transmit with Algorithm 1's clipping, the
MAC superposes with noise and the PS descales.  Written in plain
``jax.numpy``; no kernels, and no blocks beyond what one chip's memory
needs: the stages run as separate programs so that no more than six
(D,) vectors are live, and each worker's update is added onto the
superposition as it is made.  It imports nothing of the system under
test and follows its seed conventions (see ``mlp_fl``).

``precision="bf16"`` rounds every matmul operand of the local updates
to bfloat16 (f32 accumulation): the control that ``correct`` must
refuse.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_EPS = 1e-12
_TOL = 1e-6


def layout(cfg: dict):
    """[(path, shape)] of the parameters in the system's ravel order
    (dict keys sorted, layers in order)."""
    d, H = cfg["hidden"], cfg["heads"]
    nope, r, v, lat = cfg["nope"], cfg["rope_dim"], cfg["v_dim"], \
        cfg["kv_latent"]

    def ffn(prefix, f):
        return [(prefix + "down", (f, d)), (prefix + "gate", (d, f)),
                (prefix + "up", (d, f))]

    out = [("embed", (cfg["vocab"], d)), ("head", (d, cfg["vocab"]))]
    for i in range(cfg["n_dense"] + cfg["n_moe"]):
        p = f"layers.{i}."
        out += [(p + "attn_norm", (d,)), (p + "kv_a", (lat + r, d)),
                (p + "kv_b", (lat, H * (nope + v))),
                (p + "kv_norm", (lat,))]
        if i < cfg["n_dense"]:
            out += ffn(p + "mlp.", cfg["dense_ff"])
            out += [(p + "mlp_norm", (d,))]
        else:
            e, f = cfg["n_held"], cfg["expert_ff"]
            out += [(p + "mlp_norm", (d,)),
                    (p + "moe.experts.down", (e, f, d)),
                    (p + "moe.experts.gate", (e, d, f)),
                    (p + "moe.experts.up", (e, d, f)),
                    (p + "moe.router", (cfg["n_experts"], d))]
            out += ffn(p + "moe.shared.", cfg["shared_ff"])
        out += [(p + "o", (H * v, d)), (p + "q", (d, H * (nope + r)))]
    out.append(("norm", (d,)))
    return out


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for _, s in layout(cfg))


def leaves(flat, cfg: dict) -> dict:
    """Split a flat parameter vector into {path: flat slice}."""
    out, ofs = {}, 0
    for name, shape in layout(cfg):
        n = math.prod(shape)
        out[name] = flat[ofs:ofs + n]
        ofs += n
    return out


def unflatten(flat, cfg: dict) -> dict:
    return {k: v.reshape(dict(layout(cfg))[k])
            for k, v in leaves(flat, cfg).items()}


def init_flat(key, cfg: dict):
    """The initial parameters as one flat vector (norms 1, the rest
    normal with std ``init_std``, leaf i from ``fold_in(key, i)``)."""
    parts = []
    for i, (name, shape) in enumerate(layout(cfg)):
        if name.endswith("norm"):
            parts.append(jnp.ones((math.prod(shape),), jnp.float32))
        else:
            parts.append(cfg["init_std"] * jax.random.normal(
                jax.random.fold_in(key, i), (math.prod(shape),),
                jnp.float32))
    return jnp.concatenate(parts)


def _mm(a, b, precision):
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b)


def _rms(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


def _rope(x, theta):
    """Rotate halves; x (B, T, H, r), positions 0..T-1."""
    T, r = x.shape[1], x.shape[-1]
    half = r // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, w_gate, w_up, w_down, pr):
    return _mm(jax.nn.silu(_mm(x, w_gate, pr)) * _mm(x, w_up, pr),
               w_down, pr)


def forward(p: dict, tokens, cfg: dict, precision="highest"):
    """(logits (B, T, V), [per MoE layer (n_held,) routed-pair counts])."""
    pr = precision
    H, nope, r, v = cfg["heads"], cfg["nope"], cfg["rope_dim"], \
        cfg["v_dim"]
    lat, eps = cfg["kv_latent"], cfg["eps"]
    h = p["embed"][tokens]
    B, T, d = h.shape
    causal = jnp.tril(jnp.ones((T, T), bool))
    loads = []
    for i in range(cfg["n_dense"] + cfg["n_moe"]):
        q = f"layers.{i}."
        a = _rms(h, p[q + "attn_norm"], eps)
        qh = _mm(a, p[q + "q"], pr).reshape(B, T, H, nope + r)
        kv_a = _mm(a, p[q + "kv_a"].T, pr)
        c_kv = _rms(kv_a[..., :lat], p[q + "kv_norm"], eps)
        k_pe = _rope(kv_a[..., None, lat:], cfg["rope_theta"])
        kv = _mm(c_kv, p[q + "kv_b"], pr).reshape(B, T, H, nope + v)
        qh = jnp.concatenate(
            [qh[..., :nope], _rope(qh[..., nope:], cfg["rope_theta"])], -1)
        kh = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, H, r))], -1)
        s = _mm(qh.transpose(0, 2, 1, 3), kh.transpose(0, 2, 3, 1), pr) \
            / math.sqrt(nope + r)
        s = jnp.where(causal, s, -1e30)
        o = _mm(jax.nn.softmax(s, -1), kv[..., nope:].transpose(0, 2, 1, 3),
                pr)
        h = h + _mm(o.transpose(0, 2, 1, 3).reshape(B, T, H * v),
                    p[q + "o"], pr)
        m = _rms(h, p[q + "mlp_norm"], eps)
        if i < cfg["n_dense"]:
            h = h + _swiglu(m, p[q + "mlp.gate"], p[q + "mlp.up"],
                            p[q + "mlp.down"], pr)
            continue
        mf = m.reshape(B * T, d)
        scores = jax.nn.sigmoid(_mm(mf, p[q + "moe.router"].T, pr))
        _, ids = jax.lax.top_k(scores + jnp.zeros((cfg["n_experts"],)),
                               cfg["top_k"])
        w = jnp.take_along_axis(scores, ids, -1)
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * cfg["routed_scale"]
        out = _swiglu(mf, p[q + "moe.shared.gate"], p[q + "moe.shared.up"],
                      p[q + "moe.shared.down"], pr)
        counts = []
        for e in range(cfg["n_held"]):
            chosen = ids == cfg["expert_offset"] + e          # (N, k)
            gate = jnp.sum(jnp.where(chosen, w, 0.0), -1)     # (N,)
            out = out + gate[:, None] * _swiglu(
                mf, p[q + "moe.experts.gate"][e], p[q + "moe.experts.up"][e],
                p[q + "moe.experts.down"][e], pr)
            counts.append(jnp.sum(chosen))
        loads.append(jnp.stack(counts))
        h = h + out.reshape(B, T, d)
    h = _rms(h, p["norm"], eps)
    return _mm(h, p["head"], pr), loads


def cross_entropy(p, x, y, cfg, precision):
    logits, _ = forward(p, x, cfg, precision)
    gold = jnp.take_along_axis(logits, y[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def expert_load_max(loads):
    """Most-loaded held expert over the held mean, worst MoE layer."""
    return max(float(jnp.max(c) / jnp.maximum(jnp.mean(c), 1e-30))
               if float(jnp.sum(c)) > 0 else 0.0
               for c in (c.astype(jnp.float32) for c in loads))


class Round:
    """The reference round for one configuration and worker data, run
    stage by stage: ``step(flat, prev, delta, t, key)`` -> ((new flat,
    flat, delta, t + 1, next key), stats).  ``flat`` and ``prev`` are
    consumed (their buffers donated)."""

    def __init__(self, cfg: dict, X, Y, mask, k_i, *, lr, k_b, sigma2,
                 p_max, L=1.0, mu=0.5, rho1=1.0, rho2=0.01,
                 precision="highest"):
        self.cfg, self.pr = cfg, precision
        self.data = tuple(jnp.asarray(a) for a in (X, Y, mask))
        self.k_i = jnp.asarray(k_i, jnp.float32)
        U = self.k_i.shape[0]
        self.U = U
        self.k_eff = jnp.full((U,), float(k_b))
        self.pmax = jnp.full((U,), float(p_max))
        self.lr, self.k_b, self.sigma2 = lr, k_b, sigma2
        self.L, self.mu, self.rho1, self.rho2 = L, mu, rho1, rho2
        # the search's per-candidate denominators use K_b for every
        # worker (SGD), its numerator sum(k_eff) rho1 (eq. 36)
        self.numer = jnp.sum(self.k_eff) * rho1
        self.K = jnp.sum(self.k_i)

    # ---- stages (each one program; see the module docstring)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(2,))
    def _stat(self, flat, prev):
        eta = jnp.abs(flat - prev) + 1e-8
        return 1.0 / (jnp.abs(flat) + eta), jnp.mean(eta)

    @functools.partial(jax.jit, static_argnums=0)
    def _search(self, s, cw):
        thr = cw * (1.0 + _TOL)
        den = jnp.sum(self.k_eff[None, :] * (cw[:, None] <= thr[None, :]),
                      axis=1)                            # (U,) per candidate

        def body(k, best):
            best_r, best_b = best
            b_k = cw[k] * s
            r_k = (self.L * self.sigma2
                   / (2.0 * jnp.maximum(den[k] * b_k, _EPS) ** 2)
                   + self.numer / (2.0 * self.L * jnp.maximum(den[k], _EPS)))
            take = r_k < best_r
            return jnp.where(take, r_k, best_r), jnp.where(take, b_k, best_b)

        init = (jnp.full(s.shape, jnp.inf), jnp.zeros(s.shape))
        return jax.lax.fori_loop(0, self.U, body, init)[1]

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(2,))
    def _worker(self, flat, y, i, key, b, s, h, cw):
        """Worker i's SGD step, its transmit added onto y."""
        X, Y, mask = self.data
        k = jax.random.split(key, 1)[0]
        pri = jax.vmap(lambda j: jax.random.uniform(jax.random.fold_in(k, j)))(
            jnp.arange(mask.shape[1]))
        idx = jnp.argsort(jnp.where(mask[i] > 0, pri, jnp.inf))[:self.k_b]
        with jax.default_matmul_precision("highest"):
            g = jax.grad(cross_entropy)(unflatten(flat, self.cfg),
                                        X[i][idx], Y[i][idx], self.cfg,
                                        self.pr)
        w = flat - self.lr * jnp.concatenate(
            [g[name].reshape(-1) for name, _ in layout(self.cfg)])
        beta = (b <= cw[i] * s * (1.0 + _TOL)).astype(jnp.float32)
        amp = jnp.abs(beta * self.k_eff[i] * b / h[i] * w)
        tx = beta * jnp.sign(w) * jnp.minimum(amp, jnp.sqrt(self.pmax[i]))
        return y + tx * h[i]

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(2,))
    def _descale(self, flat, y, b, s, cw, noise_key):
        y = y + jnp.sqrt(self.sigma2) * jax.random.normal(noise_key,
                                                          flat.shape)
        beta = b[None, :] <= cw[:, None] * s[None, :] * (1.0 + _TOL)
        den = jnp.sum(self.k_eff[:, None] * beta, axis=0) * b
        return jnp.where(den > _EPS, y / jnp.maximum(den, _EPS), flat)

    @functools.partial(jax.jit, static_argnums=0)
    def _stats(self, new, b, s, cw):
        beta = (b[None, :] <= cw[:, None] * s[None, :] * (1.0 + _TOL)
                ).astype(jnp.float32)
        den_ki = jnp.sum(self.k_i[:, None] * beta, axis=0)
        ratio = jnp.sum(self.K / jnp.maximum(den_ki, _EPS) - 1.0)
        inv2 = 1.0 / jnp.maximum(den_ki * b, _EPS) ** 2
        a_t = 1.0 - self.mu / self.L + self.rho2 * ratio
        b_t = (self.rho1 / (2 * self.L) * ratio
               + jnp.sum(inv2) * self.L * self.sigma2 / 2)
        snr = jnp.mean(new ** 2) / jnp.maximum(
            self.sigma2 * jnp.mean(inv2), _EPS)
        return {"selected": jnp.mean(jnp.sum(beta, axis=0)),
                "b": jnp.mean(b), "a_t": a_t, "b_t": b_t, "snr": snr}

    def step(self, state: list):
        """One round.  ``state`` is the list [flat, prev, delta, t, key];
        it is emptied (``prev``'s buffer is donated, so it must not be
        ``flat`` itself).  Returns (the next state list, stats)."""
        flat, prev, delta, t, key = state
        state.clear()
        key_next, k_local, k_chan, _ = jax.random.split(key, 4)
        kg, kn = jax.random.split(jax.random.fold_in(k_chan, t), 2)
        h = jnp.maximum(jax.vmap(lambda i: jax.random.exponential(
            jax.random.fold_in(kg, i), ()))(jnp.arange(self.U)), 1e-3)
        s, eta = self._stat(flat, prev)
        del prev
        cw = jnp.abs(jnp.sqrt(self.pmax) * h / self.k_eff)
        b = self._search(s, cw)
        y = jnp.zeros_like(flat)
        for i in range(self.U):
            y = self._worker(flat, y, i, jax.random.fold_in(k_local, i), b,
                             s, h, cw)
        new = self._descale(flat, y, b, s, cw, kn)
        del y
        stats = self._stats(new, b, s, cw)
        stats["eta"] = eta
        delta = stats["b_t"] + stats["a_t"] * delta
        return [new, flat, delta, t + 1, key_next], stats
