"""The plain reference of OLMoE-1B-7B (arXiv:2409.02060; ``model_type:
olmoe``, ``modeling_olmoe.py``): pre-norm blocks of multi-head attention
with QK-norm and rotary embeddings, and a dropless top-k mixture of SwiGLU
experts with no shared expert; RMSNorm; an untied output head; trained on
cross-entropy + 0.01 x load-balancing loss + 0.001 x router z-loss.

    h  = RMSNorm(x)
    q  = RMSNorm_q(h Wq)   k = RMSNorm_k(h Wk)   v = h Wv   # norm over the WHOLE
    q, k = RoPE(q, k)                                       # projected vector,
    x  = x + CausalAttention(q, k, v) Wo                    # before the heads
    h  = RMSNorm(x)
    p  = softmax(h W_router)                                # [tokens, experts]
    (w_1..w_k, e_1..e_k) = top_k(p)          # NOT renormalised (norm_topk_prob false)
    x  = x + sum_j w_j * ( silu(h Wgate[e_j]) * (h Wup[e_j]) ) Wdown[e_j]

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no sort, no groups,
full [T, T] scores; EVERY token goes through EVERY expert in a loop over
the experts and the result is weighted by the token's top-k weight for that
expert (0 for the experts it did not choose), one sequence at a time. It
reads sizes from the configuration FILE's keys and imports nothing from
the program.

Router losses, per layer and over all the tokens given, then averaged
over layers:
  load balancing  ``E * sum_e f_e * P_e``: ``f_e`` the assignments (over
    all k choices) expert e got divided by the TOKENS, so the f sum to k
    (``load_balancing_loss_func`` of ``modeling_olmoe.py``); ``P_e`` the
    mean router probability of e;
  z-loss  ``mean_t (logsumexp(logits_t)) ** 2`` (the paper's eq. for L_RZ).

Departures from the published description: none of mathematics. Of
layout: the weights are read from the program's parameter tree
(``embedding``, ``layers.{wq,wk,wv,wo,q_norm,k_norm,router,w_gate,w_up,
w_down,attn_norm,mlp_norm}`` stacked over layers and stored [in, out],
experts stacked [E, in, out], ``final_norm``, ``lm_head``). One choice
where descriptions differ: ``modeling_olmoe.py`` concatenates the layers'
router outputs before it takes f and P, the paper takes them per layer;
this file takes them per layer (the two agree at one layer, which is what
the benchmark runs). The loss weights 0.01 / 0.001 are the paper's; the
published config.json carries no key for the z-loss.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
LB_LOSS_COEF = 0.01
Z_LOSS_COEF = 0.001


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, D]; position t rotates pair (i, i + D/2) by t * theta^(-2i/D)."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg, x, p):
    """The attention half's update of one sequence ``x`` [T, hidden]."""
    T = x.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms_norm(x, p["attn_norm"], eps)
    q = _rms_norm(h @ p["wq"], p["q_norm"], eps)     # over all nq * hd
    k = _rms_norm(h @ p["wk"], p["k_norm"], eps)     # over all nkv * hd
    q = _rope(q.reshape(T, nq, hd), theta)
    k = _rope(k.reshape(T, nkv, hd), theta)
    v = (h @ p["wv"]).reshape(T, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return a.reshape(T, nq * hd) @ p["wo"]


def experts(cfg, h, p):
    """The routed half on normed tokens ``h`` [T, hidden]: ``(y, lb, z)``,
    the experts' weighted sum and the two router losses over these
    tokens."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = h @ p["router"]                                    # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    chosen = top_e[:, :, None] == jnp.arange(E)[None, None, :]  # [T, k, E]
    weight = jnp.sum(jnp.where(chosen, top_w[:, :, None], 0.0), axis=1)

    def one_expert(y, ew):
        w_e, wg, wu, wd = ew
        return y + w_e[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd), None

    # checkpoint: a gradient through this loop keeps one expert's products
    # at a time, not all 64 (the mathematics is the same)
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                        (weight.T, p["w_gate"], p["w_up"], p["w_down"]))
    f = jnp.sum(chosen, axis=(0, 1)).astype(F32) / h.shape[0]   # sums to k
    lb = E * jnp.sum(f * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, lb, z


def _block(cfg, x, p):
    x = x + attention(cfg, x, p)
    y, lb, z = experts(cfg, _rms_norm(x, p["mlp_norm"], cfg["rms_norm_eps"]), p)
    return x + y, lb, z


def _backbone_one(cfg, params, tokens):
    """tokens [T] -> (final-normed states [T, hidden], lb [L], z [L]) of one
    sequence, the router losses over this sequence's tokens."""
    x = params["embedding"].astype(F32)[tokens]

    def body(x, p):
        x, lb, z = _block(cfg, x, jax.tree.map(lambda a: a.astype(F32), p))
        return x, (lb, z)

    x, (lb, z) = jax.lax.scan(body, x, params["layers"])
    return _rms_norm(x, params["final_norm"].astype(F32),
                     cfg["rms_norm_eps"]), lb, z


def _head(cfg, params):
    return (params["embedding"].T if cfg["tie_word_embeddings"]
            else params["lm_head"]).astype(F32)


def logits_one(cfg, params, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        x, _, _ = _backbone_one(cfg, params, tokens)
        return x @ _head(cfg, params)


def loss_parts(cfg, params, tokens):
    """tokens [B, T + 1] -> ``{"cross_entropy", "lb_loss", "z_loss",
    "total"}``. The router losses are over ALL B x T tokens of a layer:
    with B > 1 the whole batch goes through each layer together (the
    experts one at a time), so that f and P are the batch's and not a
    mean of the sequences' own."""
    with jax.default_matmul_precision("highest"):
        B, T1 = tokens.shape
        T = T1 - 1
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = params["embedding"].astype(F32)[inputs]             # [B, T, d]

        def body(x, p):
            p = jax.tree.map(lambda a: a.astype(F32), p)
            x = x + jax.lax.map(lambda row: attention(cfg, row, p), x)
            h = _rms_norm(x, p["mlp_norm"], cfg["rms_norm_eps"])
            y, lb, z = experts(cfg, h.reshape(B * T, -1), p)
            return x + y.reshape(x.shape), (lb, z)

        x, (lb, z) = jax.lax.scan(body, x, params["layers"])
        x = _rms_norm(x, params["final_norm"].astype(F32), cfg["rms_norm_eps"])
        head = _head(cfg, params)

        def nll(args):  # one [T, vocab] block of logits alive at a time
            row, tgt = args
            logp = jax.nn.log_softmax(row @ head, axis=-1)
            return -jnp.take_along_axis(logp, tgt[:, None], axis=-1).sum()

        ce = jax.lax.map(nll, (x, targets)).sum() / (B * T)
        lb, z = lb.mean(), z.mean()
        return {"cross_entropy": ce, "lb_loss": lb, "z_loss": z,
                "total": ce + LB_LOSS_COEF * lb + Z_LOSS_COEF * z}


def loss(cfg, params, tokens):
    """What the model is trained on: ``loss_parts(...)["total"]``."""
    return loss_parts(cfg, params, tokens)["total"]
