"""The plain reference of Keye-VL-2.0-30B-A3B's language model: a stack of
routed blocks whose attention reads, for every query, the ``sa_config.topk``
keys that a learned indexer picks (the vision tower is not built: the
published config gives it no widths).

One layer ``l``, ``x`` ``[T, hidden]``, ``N`` RMSNorm, positions ``[3, T]``
(temporal, height, width; text gives the three equal)::

    a       = N(x; attn_norm)
    q, k, v = a Wq, a Wk, a Wv           (heads of head_dim; GQA; no bias)
    q, k    = N over each head's head_dim (one gain for q, one for k), then
              rotated: half-split pairs (i, i + D/2), rope_theta; frequency
              i takes its angle from the component whose mrope_section it
              falls in ([16, 24, 24]: temporal under 16, height under 40)
    qI      = a WqI                      (indexer_num_heads x indexer_head_dim)
    kI      = LayerNorm(a WkI)           (ONE head; scale and bias)
    qI, kI  : the FIRST HALF of the head rotated by the temporal position,
              a rotary of its own (indexer_head_dim / 4 frequencies)
    w       = a Ww                       (a weight a query head)
    I(t, s) = sum_j w_j(t) Hi^-1/2 Di^-1/2 relu(qI_j(t) . kI(s)),   s <= t
    S(t)    = the topk positions s <= t of largest I(t, s) (all of them
              while t + 1 <= topk; a tie goes to the LOWER position)
    h       = x + softmax_{s in S(t)}(q(t) . k(s) / sqrt(head_dim)) v(s) Wo
    m       = N(h; mlp_norm)
    out     = h + sum over the chosen experts HELD HERE of
              weight_j (silu(m Wgate_j) * (m Wup_j)) Wdown_j

The router is a softmax over ``num_local_experts`` outputs, the best
``num_experts_per_tok``, renormalised to sum 1 (``norm_topk_prob``); the
file's ``num_experts`` experts from ``first_expert`` on are held here (one
chip's share of an expert-parallel deployment) and the others' terms are
not in the sum: nothing stands in for the absent chips.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no page,
a Python loop over layers, ``jax.lax.top_k`` on the float32 score (which
returns the lower index first among equals) scattered into a mask, a loop
over the held experts. Sizes from the file's keys, weights from the
program's parameter tree (``layers.index``: leaves stacked over layers,
stored ``[in, out]``); it imports nothing of ``ray_tpu``.

Done for room, changing no value: a matrix is cut out of its stacked leaf
and converted to float32 where it is used (:func:`_mm`), and queries go in
blocks of ``QUERY_BLOCK`` rows (``lax.map``), each against ALL keys, so that
8,193 positions fit one chip. ``q_chunk_size`` / ``kv_chunk_size`` are the
published code's tiling and change no ``S(t)``; FP8 and the Hadamard
rotation of the published indexer are inference-time approximations of
these equations and are not made. What the catalog cannot confirm is listed
under ``assumed`` in ``configs/Keye-VL-2.0-30B-A3B.json``.

The keyword switches (``select=False``, ``relu=False`` ...) compute the
layer a WRONG way: ``sweep/keye_check.py`` measures that the comparison
refuses each.
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


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w.astype(F32) + b.astype(F32)


def text_positions(T):
    """``[3, T]``: a text token's three components are its place."""
    return jnp.tile(jnp.arange(T)[None], (3, 1))


def rope(x, positions, theta, sections=None):
    """``x`` [T, H, D], ``positions`` [3, T]. Pair ``(i, i + D/2)`` turns by
    ``positions[c(i), t] * theta^(-2i/D)``; ``c(i)`` is the section frequency
    ``i`` falls in (0 without sections: the temporal position)."""
    D = x.shape[-1]
    part = []
    for i in range(D // 2):
        c, edge = 0, 0
        for n, width in enumerate(sections or ()):
            edge += width
            if i >= edge:
                c = min(n + 1, len(sections) - 1)
        part.append(c)
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = positions[jnp.asarray(part)].astype(F32).T * inv[None, :]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def indexer(cfg, a, p, l, positions, rotate=True):
    """``(qI [T, Hi, Di], kI [T, Di], w [T, Hi])`` of layer ``l`` on ``a``
    [T, hidden] (normed); ``w`` carries the two constant scales."""
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    T = a.shape[0]
    qi = _mm(a, p["wqi"], l).reshape(T, hi, di)
    ki = _layer_norm(_mm(a, p["wki"], l), p["ki_norm"][l], p["ki_bias"][l],
                     cfg["rms_norm_eps"])
    w = _mm(a, p["ww"], l) / math.sqrt(hi) / math.sqrt(di)

    def first_half_rotated(x):  # [T, H, Di]
        return jnp.concatenate([
            rope(x[..., :di // 2], positions, cfg["rope_theta"]),
            x[..., di // 2:]], -1)

    if rotate:
        qi = first_half_rotated(qi)
        ki = first_half_rotated(ki[:, None])[:, 0]
    return qi, ki, w


def selection(cfg, score, rows):
    """``score`` [R, T] float32, the index scores of the queries at
    positions ``rows`` [R] against every position: the mask ``[R, T]`` of
    ``S(t)``. ``jax.lax.top_k`` over the visible scores (the others at
    -inf), scattered; among equal scores it returns the lower index
    first."""
    T = score.shape[1]
    k = min(cfg["sa_config"]["topk"], T)
    visible = jnp.arange(T)[None, :] <= rows[:, None]
    _, best = jax.lax.top_k(jnp.where(visible, score, -jnp.inf), k)
    chosen = jnp.zeros(score.shape, bool).at[
        jnp.arange(score.shape[0])[:, None], best].set(True)
    return chosen & visible


def attention(cfg, a, p, l, positions=None, *, select=True, relu=True,
              weighted=True, rotate_index=True, qk_norm=True):
    """Layer ``l``'s attention on ``a`` [T, hidden] (normed), before the
    residual add; ``p``: ``layers.index``. The keywords are the wrong ways
    (module docstring)."""
    T = a.shape[0]
    positions = text_positions(T) if positions is None else positions
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _mm(a, p["wq"], l).reshape(T, nq, hd)
    k = _mm(a, p["wk"], l).reshape(T, nkv, hd)
    v = _mm(a, p["wv"], l).reshape(T, nkv, hd)
    if qk_norm:
        q, k = _rms_norm(q, p["q_norm"][l], eps), \
            _rms_norm(k, p["k_norm"][l], eps)
    sections = cfg["rope_scaling"]["mrope_section"]
    q = rope(q, positions, cfg["rope_theta"], sections)
    k = rope(k, positions, cfg["rope_theta"], sections)
    k = jnp.repeat(k, nq // nkv, axis=1)   # query head h reads kv head h // rep
    v = jnp.repeat(v, nq // nkv, axis=1)
    qi, ki, w = indexer(cfg, a, p, l, positions, rotate_index)
    if not weighted:
        w = jnp.ones_like(w)
    # rows in whole blocks: the spare rows are cut off again
    spare = -T % QUERY_BLOCK
    at = jnp.arange(T + spare)

    def padded(x):
        return jnp.pad(x, ((0, spare),) + ((0, 0),) * (x.ndim - 1)).reshape(
            -1, QUERY_BLOCK, *x.shape[1:])

    def block(rows):
        q_b, qi_b, w_b, at_b = rows
        s_i = jnp.einsum("qhd,kd->qhk", qi_b, ki)
        s_i = jax.nn.relu(s_i) if relu else s_i
        score = jnp.sum(w_b[:, :, None] * s_i, axis=1)           # [R, T]
        seen = (selection(cfg, score, at_b) if select
                else jnp.arange(T)[None, :] <= at_b[:, None])
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, (padded(q), padded(qi), padded(w),
                              at.reshape(-1, QUERY_BLOCK)))
    return _mm(out.reshape(-1, nq * hd)[:T], p["wo"], l)


def route(cfg, m, p, l, *, renormalised=None):
    """``[T, num_local_experts]``: an expert's weight for a token, 0 where
    the token did not choose it. Softmax over all, the best
    ``num_experts_per_tok``, renormalised where ``norm_topk_prob``."""
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(m @ p["router"][l].astype(F32), axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"] if renormalised is None else renormalised:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    hit = chosen[:, :, None] == jnp.arange(E)[None, None, :]    # [T, k, E]
    return jnp.sum(jnp.where(hit, top[:, :, None], 0.0), axis=1)


def experts(cfg, m, weight, p, l, first=None, count=None):
    """The routed sum on ``m`` [T, hidden] (normed) over the experts held
    here: expert ``first + e`` of the router is row ``e`` of the stacked
    leaves. Every held expert's SwiGLU on every token, times the token's
    weight for it."""
    first = cfg.get("first_expert", 0) if first is None else first
    count = cfg["num_experts"] if count is None else count

    def one(e, y):
        g = jax.nn.silu(_mm(m, p["w_gate"], (l, e))) * _mm(m, p["w_up"],
                                                          (l, e))
        return y + weight[:, first + e, None] * _mm(g, p["w_down"], (l, e))

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(m))


def layer(cfg, x, p, l, positions=None, **wrong):
    """Layer ``l``; ``p``: ``layers.index``. ``wrong``: :func:`attention`'s
    keywords and ``renormalised``."""
    eps = cfg["rms_norm_eps"]
    renormalised = wrong.pop("renormalised", None)
    a = _rms_norm(x, p["attn_norm"][l], eps)
    h = x + attention(cfg, a, p, l, positions, **wrong)
    m = _rms_norm(h, p["mlp_norm"][l], eps)
    return h + experts(cfg, m, route(cfg, m, p, l, renormalised=renormalised),
                       p, l)


def _states(cfg, params, tokens, **wrong):
    """tokens [T] -> final-normed states [T, hidden] of one sequence."""
    x = params["embedding"][tokens].astype(F32)
    for l in range(cfg["num_hidden_layers"]):
        x = layer(cfg, x, params["layers"]["index"], l, **wrong)
    return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


def _head(cfg, params):
    return (params["embedding"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])


def logits_one(cfg, params, tokens, **wrong):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        return _mm(_states(cfg, params, tokens, **wrong), _head(cfg, params))


def loss(cfg, params, tokens):
    """tokens [B, T + 1] -> mean next-token cross-entropy, one sequence at a
    time. (The published config names no router loss; the program's are
    weighted 0 where it is held to this.)"""
    with jax.default_matmul_precision("highest"):
        def nll(row):
            logp = jax.nn.log_softmax(
                _mm(_states(cfg, params, row[:-1]), _head(cfg, params)), -1)
            return -jnp.take_along_axis(logp, row[1:, None], axis=-1).sum()

        B, T1 = tokens.shape
        return jax.lax.map(nll, tokens).sum() / (B * (T1 - 1))
