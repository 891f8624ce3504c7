"""The plain reference of SmallThinker-21BA3B-Instruct's decoder: a stack of
routed blocks whose attention is full and UNROTATED in one layer of four and
windowed and rotated in the other three, whose router reads the attention's
input, and whose 64 experts are ReGLU.

One layer ``l``, ``x`` ``[T, hidden]``, ``N`` RMSNorm, ``W`` =
``sliding_window_size``::

    a      = N(x; attn_norm)
    q,k,v  = a Wq, a Wk, a Wv            (heads of head_dim; GQA; no bias)
    if rope_layout[l]:  q, k rotated (half-split pairs (i, i + D/2),
                                      rope_theta, no scaling)
    scores = q k^T / sqrt(head_dim); position i sees j <= i,
             and j > i - W  if sliding_window_layout[l]
    h      = x + softmax(scores) v Wo
    logits = a Wr                        (the ROUTER reads a, not N(h))
    top-k of logits; weights = softmax over those k logits
    m      = N(h; mlp_norm)
    out    = h + sum_j weight_j (relu(m Wgate_j) * (m Wup_j)) Wdown_j

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no page,
a Python loop over layers that reads ``sliding_window_layout`` and
``rope_layout`` from the configuration FILE's own lists, an explicit band
mask, a loop over experts (every expert's product on every token, weighted
by zero where it was not chosen). It reads sizes from the file's keys and
weights from the program's parameter tree (``layers.block``: the leaves
stacked over layers, stored ``[in, out]``), and imports nothing of
``ray_tpu``.

Departures from the published description, none of the mathematics:

- ``moe_primary_router_apply_softmax`` true with ``norm_topk_prob`` true is
  computed as ONE softmax over the chosen logits: softmax over all experts,
  top-k, renormalised over the k is the same numbers (the common factor
  cancels), and this form has no 1e-20 in it. With ``norm_topk_prob``
  false the weights are the full softmax's values of the chosen.
- Two things are done for room and change no value: a matrix is cut out of
  its stacked leaf and converted to float32 where it is used and not before
  (:func:`_mm`; a whole leaf of experts in float32 is 4 GB at the published
  widths), and queries attend in blocks of ``QUERY_BLOCK`` rows, each
  against ALL keys under its rows of the ``[T, T]`` mask (``lax.map``: one
  compiled body; ``[heads, T, T]`` scores are 2.9 GB at 5,121 positions).
- What the catalog cannot confirm is listed under ``assumed`` in
  ``configs/SmallThinker-21BA3B-Instruct.json``: the router's input, no
  attention bias, the half-split rotation, no part of the "secondary
  experts" in the forward pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _mm(x, w, at=()):
    """``x @ w[at]``, the matrix cut out of its stacked leaf ``w`` (as
    stored) and converted to float32 only once ``x`` has been computed."""
    w, _ = jax.lax.optimization_barrier((w, x))
    return x @ w[at].astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x [T, H, D]; position t rotates pair (i, i + D/2) by t * theta^(-2i/D)."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def band(cfg, T, windowed):
    """The ``[T, T]`` mask: row ``i`` sees ``j <= i``, and only ``j > i -
    sliding_window_size`` in a windowed layer."""
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if windowed:
        seen &= j > i - cfg["sliding_window_size"]
    return seen


def attention(cfg, a, p, l):
    """Layer ``l``'s attention on ``a`` [T, hidden] (normed), before the
    residual add; ``p``: ``layers.block``."""
    T = a.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = _mm(a, p["wq"], l).reshape(T, nq, hd)
    k = _mm(a, p["wk"], l).reshape(T, nkv, hd)
    v = _mm(a, p["wv"], l).reshape(T, nkv, hd)
    if cfg["rope_layout"][l]:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nq // nkv, axis=1)   # query head h reads kv head h // rep
    v = jnp.repeat(v, nq // nkv, axis=1)
    seen = band(cfg, T, cfg["sliding_window_layout"][l])
    # rows in whole blocks: the last block's spare rows see key 0 alone and
    # are cut off again
    spare = -T % QUERY_BLOCK
    q = jnp.pad(q, ((0, spare), (0, 0), (0, 0)))
    seen = jnp.pad(seen, ((0, spare), (0, 0))).at[T:, 0].set(True)

    def block(rows):
        q_b, seen_b = rows
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen_b[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, (q.reshape(-1, QUERY_BLOCK, nq, hd),
                              seen.reshape(-1, QUERY_BLOCK, T)))
    return _mm(out.reshape(-1, nq * hd)[:T], p["wo"], l)


def route(cfg, a, p, l):
    """``[T, experts]``: an expert's weight for a token, 0 where the token
    did not choose it. The router reads ``a``, the ATTENTION's input."""
    E, k = cfg["moe_num_primary_experts"], cfg["moe_num_active_primary_experts"]
    logits = a @ p["router"][l].astype(F32)
    top, chosen = jax.lax.top_k(logits, k)
    if not cfg["moe_primary_router_apply_softmax"]:
        raise ValueError("this reference knows the softmax router")
    if cfg["norm_topk_prob"]:
        w = jax.nn.softmax(top, axis=-1)
    else:
        w = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), chosen, -1)
    hit = chosen[:, :, None] == jnp.arange(E)[None, None, :]    # [T, k, E]
    return jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)


def experts(cfg, m, weight, p, l, gate=jax.nn.relu):
    """The routed sum on ``m`` [T, hidden] (normed): every expert's ReGLU on
    every token, times the token's weight for it."""
    def one(e, y):
        g = gate(_mm(m, p["w_gate"], (l, e))) * _mm(m, p["w_up"], (l, e))
        return y + weight[:, e, None] * _mm(g, p["w_down"], (l, e))

    return jax.lax.fori_loop(0, cfg["moe_num_primary_experts"], one,
                             jnp.zeros_like(m))


def layer(cfg, x, p, l):
    """Layer ``l``; ``p``: ``layers.block``."""
    eps = cfg["rms_norm_eps"]
    a = _rms_norm(x, p["attn_norm"][l], eps)
    h = x + attention(cfg, a, p, l)
    m = _rms_norm(h, p["mlp_norm"][l], eps)
    return h + experts(cfg, m, route(cfg, a, p, l), p, l)


def _states(cfg, params, tokens):
    """tokens [T] -> final-normed states [T, hidden] of one sequence."""
    x = params["embedding"][tokens].astype(F32)
    for l in range(cfg["num_hidden_layers"]):
        x = layer(cfg, x, params["layers"]["block"], l)
    return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


def _head(cfg, params):
    return (params["embedding"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])


def logits_one(cfg, params, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        return _mm(_states(cfg, params, tokens), _head(cfg, params))


def loss(cfg, params, tokens):
    """tokens [B, T + 1] -> mean next-token cross-entropy, one sequence at a
    time so that only one [T, vocab] block of logits is alive. (The
    published config names no router loss; the program's are weighted 0
    where it is held to this.)"""
    with jax.default_matmul_precision("highest"):
        def nll(row):
            logp = jax.nn.log_softmax(
                _mm(_states(cfg, params, row[:-1]), _head(cfg, params)), -1)
            return -jnp.take_along_axis(logp, row[1:, None], axis=-1).sum()

        B, T1 = tokens.shape
        return jax.lax.map(nll, tokens).sum() / (B * (T1 - 1))
