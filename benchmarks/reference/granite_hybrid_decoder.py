"""The plain reference of granite-4.0-h-micro (``granitemoehybrid`` with no
expert: "Granite 4.0-H Micro 3B"): a stack of WHOLE blocks, a mixer (Mamba-2
or unrotated grouped-query attention, as ``layer_types`` says) then a dense
SwiGLU, under four fixed multipliers. ``N`` is RMSNorm, ``x / sqrt(mean(x^2) +
rms_norm_eps) * w``; ``x`` ``[T, hidden]``::

    x0 = embedding_multiplier * E[token]
    every layer
      a = N(x; attn_norm)
      "mamba"
        [z | xBC | dt] = a W_in         widths d_inner | d_inner + 2 G N | H
                                        (d_inner = mamba_n_heads x mamba_d_head)
        xBC = silu(causal depthwise conv over time, mamba_d_conv taps, WITH
              bias: row t reads rows t - 3 .. t, zeros before 0)
        x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)   head i reads
                                                             group i // (H / G)
        dt = softplus(dt + dt_bias)      A = -exp(A_log)     a scalar a head
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t         S [H, P, N], S_0 = 0
        y_t = S_t C_t + D x_t
        m   = (N_group(y * silu(z)) * gate_norm) W_out       the norm AFTER the
                                        gate, over a group's d_inner / G
      "attention"
        q = a Wq [heads x d], k = a Wk, v = a Wv [kv heads x d]   d = hidden /
                                        heads; no bias, NO rotation, no QK-norm
        m = softmax(attention_multiplier * q k^T, causal) v Wo    query head h
                                        reads KV head h // (heads / kv heads)
      h   = x + residual_multiplier * m
      u   = N(h; mlp_norm)
      out = h + residual_multiplier * (silu(u W_gate) * (u W_up)) W_down
    logits = N(x; final_norm) E^T / logits_scaling          the head is tied

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no page, no
chunk, no decay matrix: THE STATE-SPACE LAYER IS THE RECURRENCE, run token by
token as written above (a ``lax.scan`` over positions that carries ``S``),
the convolution four shifted adds. Sizes from the file's keys, weights from
the program's parameter tree (``layers.hybrid_mamba`` / ``layers.hybrid_attn``:
leaves stacked over a kind's layers, stored ``[in, out]``); it imports nothing
of ``ray_tpu``.

Departures, none of which changes a value. LAYOUT: the published
``shared_mlp.input_linear`` holds gate and up side by side and is split in
halves; the tree keeps them as two leaves (``w_gate``, ``w_up``). FOR ROOM:
a matrix is cut out of its stacked leaf and converted to float32 where it is
used (:func:`_mm`); the layers are walked in a ``lax.scan`` over the
pattern's whole periods (ONE compiled period, ONE layer's float32 copies
alive: at published sizes 305 MB where all forty at once would be 12.8 GB),
what a last period cut short leaves in line; attention's queries go in blocks
of ``QUERY_BLOCK`` rows (``lax.map``), each against ALL keys. What the catalog
cannot confirm is listed under ``assumed`` in
``configs/granite-4.0-h-micro.json``.

The keyword switches (``conv_bias=False``, ``rotated=True`` ...) compute a
layer a WRONG way: ``sweep/granite4h_check.py`` measures that the comparison
refuses each.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512

# a layer type's stack in the program's tree
STACK = {"mamba": "hybrid_mamba", "attention": "hybrid_attn"}


def _mm(x, w, at=()):
    """``x @ w[at]``, the matrix cut out of its stacked leaf ``w`` (as
    stored) and converted to float32 only once ``x`` has been computed."""
    w, _ = jax.lax.optimization_barrier((w, x))
    return x @ w[at].astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def kinds_of(cfg) -> list:
    """The built layers' types, from ``layer_types``."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def recurrence(x, dt, a, b_in, c_in):
    """The state-space recurrence, token by token. ``x`` [T, H, P], ``dt``
    [T, H] (positive), ``a`` [H] (negative), ``b_in`` / ``c_in`` [T, H, N] (a
    group's row repeated for its heads). Returns ``(y [T, H, P], S [H, P,
    N])`` from ``S_0 = 0``, ``y`` without the skip ``D x``."""
    def step(S, row):
        x_t, dt_t, b_t, c_t = row
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    H, P, N = x.shape[1], x.shape[2], b_in.shape[2]
    S, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, dt, b_in, c_in))
    return y, S


def rope(x, theta):
    """``x`` [T, H, D] turned by its row's position, half-split pairs: what
    this model does NOT do (``position_embedding_type: nope``)."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mamba(cfg, a, p, l, *, conv_bias=True, skip=True, **_):
    """Layer ``l``'s Mamba-2 mixer on ``a`` [T, hidden] (normed), before the
    residual add; ``p``: ``layers.hybrid_mamba``. The keywords are the wrong
    ways (module docstring)."""
    T = a.shape[0]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N, K = (cfg["mamba_n_groups"], cfg["mamba_d_state"],
               cfg["mamba_d_conv"])
    d_inner = H * P
    mixed = _mm(a, p["w_in"], l)
    z, xbc, dt = (mixed[:, :d_inner], mixed[:, d_inner:-H], mixed[:, -H:])
    w = p["conv_w"][l].astype(F32)                           # [taps, width]
    before = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = sum(before[j:j + T] * w[j] for j in range(K))
    if conv_bias:
        xbc = xbc + p["conv_b"][l].astype(F32)
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d_inner].reshape(T, H, P)
    b_in, c_in = (jnp.repeat(v.reshape(T, G, N), H // G, axis=1)
                  for v in (xbc[:, d_inner:d_inner + G * N],
                            xbc[:, d_inner + G * N:]))
    dt = jax.nn.softplus(dt + p["dt_bias"][l].astype(F32))
    y, _ = recurrence(x, dt, -jnp.exp(p["A_log"][l].astype(F32)), b_in, c_in)
    if skip:
        y = y + p["D"][l].astype(F32)[:, None] * x
    y = (y.reshape(T, d_inner) * jax.nn.silu(z)).reshape(T, G, d_inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    return _mm(y.reshape(T, d_inner) * p["gate_norm"][l].astype(F32),
               p["w_out"], l)


def attention(cfg, a, p, l, *, attention_multiplier=None, rotated=False, **_):
    """Layer ``l``'s attention on ``a`` [T, hidden] (normed), before the
    residual add; ``p``: ``layers.hybrid_attn``."""
    T = a.shape[0]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nq
    scale = (cfg["attention_multiplier"] if attention_multiplier is None
             else attention_multiplier)
    q = _mm(a, p["wq"], l).reshape(T, nq, hd)
    k = _mm(a, p["wk"], l).reshape(T, nkv, hd)
    v = _mm(a, p["wv"], l).reshape(T, nkv, hd)
    if rotated:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nq // nkv, axis=1)   # query head h reads kv head h // rep
    v = jnp.repeat(v, nq // nkv, axis=1)
    spare = -T % QUERY_BLOCK               # rows in whole blocks, cut off again
    at = jnp.arange(T + spare).reshape(-1, QUERY_BLOCK)
    q_blocks = jnp.pad(q, ((0, spare), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, nq, hd)

    def block(rows):
        q_b, at_b = rows
        s = jnp.einsum("qhd,khd->hqk", q_b, k) * scale
        seen = jnp.arange(T)[None, :] <= at_b[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    o = jax.lax.map(block, (q_blocks, at)).reshape(-1, nq, hd)[:T]
    return _mm(o.reshape(T, nq * hd), p["wo"], l)


def mlp(cfg, u, p, l):
    """The dense SwiGLU on ``u`` [T, hidden] (normed)."""
    return _mm(jax.nn.silu(_mm(u, p["w_gate"], l)) * _mm(u, p["w_up"], l),
               p["w_down"], l)


MIXER = {"mamba": mamba, "attention": attention}


def layer(cfg, x, layers, kind, l, *, residual_multiplier=None, **wrong):
    """The ``l``-th layer of type ``kind`` (``l`` counts that type's layers,
    a number or traced); ``layers``: the parameter tree's ``layers``."""
    p, eps = layers[STACK[kind]], cfg["rms_norm_eps"]
    r = (cfg["residual_multiplier"] if residual_multiplier is None
         else residual_multiplier)
    a = _rms_norm(x, p["attn_norm"][l], eps)
    h = x + r * MIXER[kind](cfg, a, p, l, **wrong)
    return h + r * mlp(cfg, _rms_norm(h, p["mlp_norm"][l], eps), p, l)


def _period(kinds):
    """``(unit, times)``: the shortest ``unit`` whose repetition ``kinds``
    is a prefix of, and how many whole units ``kinds`` holds."""
    for n in range(1, len(kinds) + 1):
        if all(kind == kinds[i % n] for i, kind in enumerate(kinds)):
            return kinds[:n], len(kinds) // n
    return [], 0


def _states(cfg, params, tokens, *, embedding_multiplier=None, **wrong):
    """tokens [T] -> final-normed states [T, hidden] of one sequence."""
    e = (cfg["embedding_multiplier"] if embedding_multiplier is None
         else embedding_multiplier)
    x = e * params["embedding"][tokens].astype(F32)
    kinds = kinds_of(cfg)
    unit, times = _period(kinds)
    per = {kind: unit.count(kind) for kind in STACK}

    def run(x, some, first):
        """Layers of types ``some`` in line, the first of a type its
        ``first[type]``-th."""
        met = dict(first)
        for kind in some:
            x = layer(cfg, x, params["layers"], kind, met[kind], **wrong)
            met[kind] += 1
        return x

    if times:  # for room: one compiled period, one layer's float32 copies
        x, _ = jax.lax.scan(
            lambda x, n: (run(x, unit, {k: n * per[k] for k in per}), None),
            x, jnp.arange(times, dtype=jnp.int32))
    x = run(x, kinds[times * len(unit):], {k: times * per[k] for k in per})
    return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


def _head(cfg, params):
    return (params["embedding"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])


def logits_one(cfg, params, tokens, *, logits_scaling=None, **wrong):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    by = cfg["logits_scaling"] if logits_scaling is None else logits_scaling
    with jax.default_matmul_precision("highest"):
        return _mm(_states(cfg, params, tokens, **wrong),
                   _head(cfg, params)) / by
