"""The plain reference of command-a-plus-05-2026's decoder (``model_type:
cohere2_moe``): a stack of PARALLEL blocks, in which ONE mean-subtracting
LayerNorm feeds the attention and the routed MLP and both are added to the
stream; 128 query heads on 8 KV heads, windowed and rotated in three layers
of four, full and unrotated in the fourth; a sigmoid router over 128 experts
of which the best eight are renormalised, beside four shared experts whose
outputs are AVERAGED; the embedding tied to the head.

One layer ``l``, ``x`` ``[T, hidden]``, ``W`` = ``sliding_window``::

    a      = LN(x) = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * g
    q,k,v  = a Wq, a Wk, a Wv              (heads of head_dim; no bias, no
                                            QK-norm)
    sliding_attention:  q, k rotated, pairs (2i, 2i + 1), rope_theta;
                        position t sees j with t - W < j <= t
    full_attention:     q, k NOT rotated; t sees every j <= t
    A      = softmax(q k^T / sqrt(head_dim)) v Wo    (query head h reads KV
                                                      head h // 16)
    s      = sigmoid(a Wr) over router_experts; the best
             num_experts_per_tok by s; w_e = s_e / sum of the chosen s
    R      = sum_e w_e (silu(a Wg_e) * (a Wu_e)) Wd_e      over the experts
             HELD here: first_expert .. first_expert + num_experts - 1
    S      = 1 / n sum_j (silu(a Vg_j) * (a Vu_j)) Vd_j    the n =
             num_shared_experts shared experts, averaged
    out    = x + A + R + S
    logits = LN(x_last; final_norm) E^T * logit_scale      (E: the embedding)

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no page,
a Python loop over layers that reads ``layer_types`` from the configuration
FILE's own list, an explicit band mask, keys repeated for their query heads,
a loop over the held experts (every held expert's product on every token,
weighted by zero where it was not chosen), FOUR shared experts computed one
by one and averaged. It reads sizes from the file's keys and weights from the
program's parameter tree (``layers.parallel``: the leaves stacked over
layers, stored ``[in, out]``; the shared experts side by side, expert ``j``
the columns ``j * intermediate_size ..`` of ``shared_gate`` / ``shared_up``
and those rows of ``shared_down``), and imports nothing of ``ray_tpu``.

The share of a deployment (the model-configs guide's section 4): the file's
``num_experts`` is what is HELD here, ``router_experts`` the router's
published width; what the absent experts would add is left out, here as in
the program, and that partial result goes on to the next layer. A sliced
vocabulary is a smaller vocabulary.

Departures from the published description, none of the mathematics:

- Two things are done for room and change no value: a matrix is cut out of
  its stacked leaf and converted to float32 where it is used and not before
  (:func:`_mm`), and queries attend one KV GROUP at a time (``lax.map`` over
  the 8 groups, and inside a group over blocks of ``QUERY_BLOCK`` rows, each
  against ALL the group's keys under its rows of the ``[T, T]`` mask):
  float32 scores of all 128 heads at once are 13.4 GB at 5,121 positions.
- What the catalog cannot confirm is listed under ``assumed`` in
  ``configs/command-a-plus-05-2026.json``: no choice bias and no weight
  scale, the shared experts' mean ADDED to the routed sum, the window's
  edge, ``prefix_dense_*`` naming layers that do not exist.
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


def layer_norm(x, w, eps):
    """The mean subtracted, the variance of the deviations, no bias."""
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x [T, H, D]; position t rotates pair (2i, 2i + 1) by
    t * theta^(-2i/D)."""
    T, H, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(T, H, D)


def band(cfg, T, windowed):
    """The ``[T, T]`` mask: row ``t`` sees ``j <= t``, and only ``j > t -
    sliding_window`` in a windowed layer."""
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if windowed:
        seen &= j > i - cfg["sliding_window"]
    return seen


def attention(cfg, a, p, l):
    """Layer ``l``'s attention on ``a`` [T, hidden] (normed), before the
    residual add; ``p``: ``layers.parallel``."""
    T = a.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    rep = nq // nkv
    windowed = cfg["layer_types"][l] == "sliding_attention"
    q = _mm(a, p["wq"], l).reshape(T, nq, hd)
    k = _mm(a, p["wk"], l).reshape(T, nkv, hd)
    v = _mm(a, p["wv"], l).reshape(T, nkv, hd)
    if windowed:  # a full layer has no position at all
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    seen = band(cfg, T, windowed)
    # rows in whole blocks: the last block's spare rows see key 0 alone and
    # are cut off again
    spare = -T % QUERY_BLOCK
    q = jnp.pad(q, ((0, spare), (0, 0), (0, 0)))
    seen = jnp.pad(seen, ((0, spare), (0, 0))).at[T:, 0].set(True)
    seen = seen.reshape(-1, QUERY_BLOCK, T)

    def group(qkv):
        q_g, k_g, v_g = qkv  # [Tq, rep, hd], [T, hd], [T, hd]
        # the group's ONE key head repeated for its rep query heads
        k_r = jnp.repeat(k_g[:, None, :], rep, axis=1)
        v_r = jnp.repeat(v_g[:, None, :], rep, axis=1)

        def block(rows):
            q_b, seen_b = rows
            s = jnp.einsum("qhd,khd->hqk", q_b, k_r) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen_b[None], s, -jnp.inf),
                                   axis=-1)
            return jnp.einsum("hqk,khd->qhd", probs, v_r)

        return jax.lax.map(block, (q_g.reshape(-1, QUERY_BLOCK, rep, hd),
                                   seen))

    # query head h = g * rep + r reads KV head g
    out = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(-1, nkv, rep, hd), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    out = jnp.moveaxis(out.reshape(nkv, -1, rep, hd), 0, 1)
    return _mm(out.reshape(-1, nq * hd)[:T], p["wo"], l)


def route(cfg, a, p, l):
    """``[T, router_experts]``: an expert's weight for a token, 0 where the
    token did not choose it."""
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    logits = a @ p["router"][l].astype(F32)
    if cfg["expert_selection_fn"] == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif cfg["expert_selection_fn"] == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(cfg["expert_selection_fn"])
    top, chosen = jax.lax.top_k(s, k)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    hit = chosen[:, :, None] == jnp.arange(E)[None, None, :]    # [T, k, E]
    return jnp.sum(jnp.where(hit, top[:, :, None], 0.0), axis=1)


def experts(cfg, a, weight, p, l, first=None):
    """The routed sum on ``a`` [T, hidden] over the experts held here
    (``first`` .. ``first + num_experts - 1`` of the router's; the file's
    ``first_expert`` unless given): every held expert's SwiGLU on every
    token, times the token's weight for it."""
    first = cfg.get("first_expert", 0) if first is None else first

    def one(e, y):
        g = jax.nn.silu(_mm(a, p["w_gate"], (l, e))) * _mm(a, p["w_up"],
                                                           (l, e))
        return y + weight[:, first + e, None] * _mm(g, p["w_down"], (l, e))

    return jax.lax.fori_loop(0, cfg["num_experts"], one, jnp.zeros_like(a))


def shared(cfg, a, p, l):
    """The ``num_shared_experts`` shared experts on ``a`` [T, hidden], one
    by one, combined as ``shared_expert_combination_strategy`` says."""
    n, f = cfg["num_shared_experts"], cfg["intermediate_size"]
    total = jnp.zeros_like(a)
    for j in range(n):
        cols = slice(j * f, (j + 1) * f)
        w_gate, w_up, w_down = (p["shared_gate"][l][:, cols],
                                p["shared_up"][l][:, cols],
                                p["shared_down"][l][cols])
        g = jax.nn.silu(_mm(a, w_gate)) * _mm(a, w_up)
        total = total + _mm(g, w_down)
    how = cfg["shared_expert_combination_strategy"]
    if how == "average":
        return total / n
    if how == "sum":
        return total
    raise ValueError(how)


def layer(cfg, x, p, l, first=None):
    """Layer ``l``; ``p``: ``layers.parallel``. ONE norm; attention, the
    routed sum and the shared experts all read ``a`` and are all added."""
    if not cfg["use_parallel_block"]:
        raise ValueError("this reference knows the parallel block")
    a = layer_norm(x, p["norm"][l], cfg["layer_norm_eps"])
    return (x + attention(cfg, a, p, l)
            + experts(cfg, a, route(cfg, a, p, l), p, l, first)
            + shared(cfg, a, p, l))


def _states(cfg, params, tokens):
    """tokens [T] -> final-normed states [T, hidden] of one sequence."""
    x = params["embedding"][tokens].astype(F32)
    for l in range(cfg["num_hidden_layers"]):
        x = layer(cfg, x, params["layers"]["parallel"], l)
    return layer_norm(x, params["final_norm"], cfg["layer_norm_eps"])


def _head(cfg, params):
    return (params["embedding"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])


def logits_one(cfg, params, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        return _mm(_states(cfg, params, tokens),
                   _head(cfg, params)) * cfg["logit_scale"]


def loss(cfg, params, tokens):
    """tokens [B, T + 1] -> mean next-token cross-entropy, one sequence at a
    time. (A sigmoid router has no router loss.)"""
    with jax.default_matmul_precision("highest"):
        def nll(row):
            logp = jax.nn.log_softmax(
                _mm(_states(cfg, params, row[:-1]), _head(cfg, params))
                * cfg["logit_scale"], -1)
            return -jnp.take_along_axis(logp, row[1:, None], axis=-1).sum()

        B, T1 = tokens.shape
        return jax.lax.map(nll, tokens).sum() / (B * (T1 - 1))
