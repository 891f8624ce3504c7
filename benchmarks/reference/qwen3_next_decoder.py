"""The plain reference of Qwen3-Next-80B-A3B's language model: periods of
gated delta-rule layers and one gated full-attention layer, every layer
followed by a routed MLP with one gated shared expert (the multi-token
prediction module is not built: the published config has no key for it).

``N`` is the ZERO-CENTRED RMSNorm, ``x / rms(x) * (1 + w)`` (eps
``rms_norm_eps``); the delta rule's output norm alone is plain, ``* w``.
Layer ``l`` (from 0) of the pattern is ``"A"`` where ``(l + 1) %
full_attention_interval == 0`` and ``"D"`` otherwise; ``x`` ``[T, hidden]``::

    delta-rule layer "D"
      a = N(x; attn_norm)
      [q | k | v | z] = a W_qkvz        [b | a'] = a W_ba
      [q | k | v] = silu(causal depthwise conv over time, linear_conv_kernel_dim
                    taps, no bias: row t reads rows t - 3 .. t, zeros before 0)
      q, k: linear_num_key_heads heads of linear_key_head_dim, each
            l2-normalised (eps 1e-6), q times linear_key_head_dim ** -0.5;
            value head i (of linear_num_value_heads) reads q / k head
            i // (value heads / key heads)
      beta_t = sigmoid(b_t)      g_t = -exp(A_log) softplus(a'_t + dt_bias)
      S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - (exp(g_t) S_{t-1})^T k_t)^T
      o_t = S_t^T q_t                    S [key dim, value dim] a value head
      y_t = gate_norm * o_t / rms(o_t) * silu(z_t)     the norm BEFORE the gate
      h = x + y W_out
    gated full attention "A"
      a = N(x; attn_norm)
      [q | gate] = a Wq  a head (head_dim | head_dim);  k, v = a Wk, a Wv
      q, k = N over each head's head_dim; the first partial_rotary_factor *
             head_dim dimensions rotated, half-split pairs (i, i + rot / 2)
      o = softmax(q k^T / sqrt(head_dim), causal) v                   GQA
      h = x + (o * sigmoid(gate)) Wo
    routed MLP (every layer)
      m = N(h; mlp_norm)
      p = softmax over router_experts of m W_r; the num_experts_per_tok
          largest; w_j = p_j / their sum (norm_topk_prob)
      out = h + sum over the chosen experts HELD HERE of w_j SwiGLU_j(m)
              + sigmoid(m . w_sg) SwiGLU_shared(m)
    head: N(x; final_norm) lm_head

The file's ``num_experts`` experts from ``first_expert`` on are held here (one
chip's share of an expert-parallel deployment) and the others' terms are not
in the sum: nothing stands in for the absent chips.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no page, no
chunk: THE RECURRENCE IS RUN TOKEN BY TOKEN as written above (a ``lax.scan``
over positions that carries ``S``), a Python loop over layers, a loop over
the held experts. Sizes from the file's keys, weights from the program's
parameter tree (``layers.delta`` / ``layers.gated``: leaves stacked over a
kind's layers, stored ``[in, out]``); it imports nothing of ``ray_tpu``.

Done for room, changing no value: a matrix is cut out of its stacked leaf and
converted to float32 where it is used (:func:`_mm`), and attention's queries
go in blocks of ``QUERY_BLOCK`` rows (``lax.map``), each against ALL keys, so
that 8,193 positions fit one chip. What the catalog cannot confirm is listed
under ``assumed`` in ``configs/Qwen3-Next-80B-A3B-Instruct.json``.

The keyword switches (``decay=False``, ``l2norm=False`` ...) compute a layer
a WRONG way: ``sweep/qwen3next_check.py`` measures that the comparison
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


def _rms_norm(x, w, eps, zero_centered=True):
    w = w.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w if zero_centered else w)


def kinds_of(cfg) -> str:
    """The built layers' kinds, from ``full_attention_interval``."""
    n = cfg["full_attention_interval"]
    return "".join("A" if (l + 1) % n == 0 else "D"
                   for l in range(cfg["num_hidden_layers"]))


def rope(x, theta):
    """``x`` [T, H, R] turned by its row's position: pair ``(i, i + R/2)``
    by ``t * theta^(-2i/R)``."""
    T, _, R = x.shape
    inv = theta ** (-jnp.arange(0, R, 2, dtype=F32) / R)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token. ``q`` / ``k`` [T, H, K], ``v``
    [T, H, V], ``g`` / ``beta`` [T, H]. Returns ``(o [T, H, V], S [H, K, V])``
    from ``S_0 = 0``."""
    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[:, None, None] * S
        write = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * write[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    H, K, V = q.shape[1], q.shape[2], v.shape[2]
    S, o = jax.lax.scan(step, jnp.zeros((H, K, V), F32), (q, k, v, g, beta))
    return o, S


def delta(cfg, a, p, l, *, decay=True, beta_one=False, l2norm=True,
          conv=True, norm_before_gate=True, **_):
    """Layer ``l``'s delta-rule mixer on ``a`` [T, hidden] (normed), before
    the residual add; ``p``: ``layers.delta``. The keywords are the wrong
    ways (module docstring)."""
    T = a.shape[0]
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    taps, eps = cfg["linear_conv_kernel_dim"], cfg["rms_norm_eps"]
    qkvz = _mm(a, p["w_qkvz"], l)
    qkv, z = qkvz[:, :2 * hk * dk + hv * dv], qkvz[:, 2 * hk * dk + hv * dv:]
    ba = _mm(a, p["w_ba"], l)
    beta = jnp.ones((T, hv), F32) if beta_one else jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"][l].astype(F32)) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"][l].astype(F32))
    if not decay:
        g = jnp.zeros_like(g)
    if conv:
        w = p["conv_w"][l].astype(F32)                       # [taps, width]
        before = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
        qkv = sum(before[j:j + T] * w[j] for j in range(taps))
    qkv = jax.nn.silu(qkv)
    q = qkv[:, :hk * dk].reshape(T, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(T, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(T, hv, dv)
    if l2norm:
        q, k = (y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)
                for y in (q, k))
    q = q * dk ** -0.5
    q, k = (jnp.repeat(y, hv // hk, axis=1) for y in (q, k))
    o, _ = delta_rule(q, k, v, g, beta)
    gate = jax.nn.silu(z).reshape(T, hv, dv)
    w = p["gate_norm"][l].astype(F32)

    def plain_norm(y):
        return y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps) * w

    y = plain_norm(o) * gate if norm_before_gate else plain_norm(o * gate)
    return _mm(y.reshape(T, hv * dv), p["w_out"], l)


def attention(cfg, a, p, l, *, out_gate=True, partial=True,
              zero_centered=True, **_):
    """Layer ``l``'s gated attention on ``a`` [T, hidden] (normed), before
    the residual add; ``p``: ``layers.gated``."""
    T = a.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qg = _mm(a, p["wq"], l).reshape(T, nq, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _mm(a, p["wk"], l).reshape(T, nkv, hd)
    v = _mm(a, p["wv"], l).reshape(T, nkv, hd)
    q = _rms_norm(q, p["q_norm"][l], eps, zero_centered)
    k = _rms_norm(k, p["k_norm"][l], eps, zero_centered)
    rot = round(hd * cfg["partial_rotary_factor"]) if partial else hd
    q, k = (jnp.concatenate([rope(y[..., :rot], cfg["rope_theta"]),
                             y[..., rot:]], -1) for y in (q, k))
    k = jnp.repeat(k, nq // nkv, axis=1)   # query head h reads kv head h // rep
    v = jnp.repeat(v, nq // nkv, axis=1)
    spare = -T % QUERY_BLOCK               # rows in whole blocks, cut off again
    at = jnp.arange(T + spare).reshape(-1, QUERY_BLOCK)
    q_blocks = jnp.pad(q, ((0, spare), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, nq, hd)

    def block(rows):
        q_b, at_b = rows
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(hd)
        seen = jnp.arange(T)[None, :] <= at_b[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    o = jax.lax.map(block, (q_blocks, at)).reshape(-1, nq, hd)[:T]
    if out_gate:
        o = o * jax.nn.sigmoid(gate)
    return _mm(o.reshape(T, nq * hd), p["wo"], l)


def route(cfg, m, p, l, *, renormalised=None):
    """``[T, router_experts]``: an expert's weight for a token, 0 where the
    token did not choose it."""
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(m @ p["router"][l].astype(F32), axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"] if renormalised is None else renormalised:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    hit = chosen[:, :, None] == jnp.arange(E)[None, None, :]    # [T, k, E]
    return jnp.sum(jnp.where(hit, top[:, :, None], 0.0), axis=1)


def experts(cfg, m, weight, p, l, first=None, count=None):
    """The routed sum on ``m`` [T, hidden] (normed) over the experts held
    here: expert ``first + e`` of the router is row ``e`` of the stacked
    leaves."""
    first = cfg.get("first_expert", 0) if first is None else first
    count = cfg["num_experts"] if count is None else count

    def one(e, y):
        g = jax.nn.silu(_mm(m, p["w_gate"], (l, e))) * _mm(m, p["w_up"],
                                                          (l, e))
        return y + weight[:, first + e, None] * _mm(g, p["w_down"], (l, e))

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(m))


def shared_expert(cfg, m, p, l, *, shared_gate=True):
    """The ONE shared SwiGLU expert behind the token's sigmoid gate."""
    y = _mm(jax.nn.silu(_mm(m, p["ws_gate"], l)) * _mm(m, p["ws_up"], l),
            p["ws_down"], l)
    if shared_gate:
        y = jax.nn.sigmoid(m @ p["w_sg"][l].astype(F32))[:, None] * y
    return y


def mlp(cfg, h, p, l, *, renormalised=None, shared_gate=True,
        zero_centered=True, **_):
    """The routed MLP on the stream ``h``, before the residual add."""
    m = _rms_norm(h, p["mlp_norm"][l], cfg["rms_norm_eps"], zero_centered)
    return experts(cfg, m, route(cfg, m, p, l, renormalised=renormalised),
                   p, l) + shared_expert(cfg, m, p, l,
                                         shared_gate=shared_gate)


# a kind's mixer and the name of its stacked weights
LAYER = {"D": (delta, "delta"), "A": (attention, "gated")}


def layer(cfg, x, layers, kind, l, **wrong):
    """The ``l``-th layer of ``kind`` (``l`` counts that kind's layers);
    ``layers``: the parameter tree's ``layers``. ``wrong``: the mixers' and
    the MLP's keywords."""
    mixer, name = LAYER[kind]
    p = layers[name]
    a = _rms_norm(x, p["attn_norm"][l], cfg["rms_norm_eps"],
                  wrong.get("zero_centered", True))
    h = x + mixer(cfg, a, p, l, **wrong)
    return h + mlp(cfg, h, p, l, **wrong)


def _states(cfg, params, tokens, **wrong):
    """tokens [T] -> final-normed states [T, hidden] of one sequence."""
    x = params["embedding"][tokens].astype(F32)
    met = {"D": 0, "A": 0}
    for kind in kinds_of(cfg):
        x = layer(cfg, x, params["layers"], kind, met[kind], **wrong)
        met[kind] += 1
    return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"],
                     wrong.get("zero_centered", True))


def _head(cfg, params):
    return (params["embedding"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])


def logits_one(cfg, params, tokens, **wrong):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        return _mm(_states(cfg, params, tokens, **wrong), _head(cfg, params))
