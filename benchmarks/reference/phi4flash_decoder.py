"""The plain reference of Phi-4-mini-flash-reasoning (``phi4flash``; the
decoder-hybrid-decoder family of arXiv:2507.06607): 32 WHOLE blocks, a mixer
then a dense SwiGLU, ``x`` ``[T, hidden]``, ``LN`` the mean-subtracting
LayerNorm with gain AND bias (``layer_norm_eps``), the final norm too::

    every layer   a = LN(x; attn_norm);  h = x + Mix(a)
                  out = h + (silu(u Wg) * (u Wu)) Wd,  u = LN(h; mlp_norm)
    "mamba"       [u | z] = a W_in                  widths D | D, D = mamba_expand x hidden
                  u = silu(causal depthwise conv over time, mamba_d_conv taps,
                      WITH bias: row t reads rows t - 3 .. t, zeros before 0)
                  [r | B | C] = u W_x               widths mamba_dt_rank | N | N
                  dt = softplus(r W_dt + dt_bias)   [D]
                  A = -exp(A_log)                   [D, N]
                  S_t = exp(dt_t A) * S_(t-1) + (dt_t u_t) (x) B_t    S [D, N], S_0 = 0
                  Y_t = S_t C_t + D * u_t
                  Mix = (Y * silu(z)) W_out.  THE NEWEST Y IS THE MEMORY
    "gated_memory"    Mix = (silu(a Wg_in) * memory) Wg_out     memory of THIS position
    attention     q = a Wq + bq: heads in PAIRS (q1, q2), num_attention_heads / 2 of them
      "sliding_attention" / "full_attention": [k1 | k2 | v] = a Wkv + bkv:
                  num_key_value_heads / 2 key pairs, a pair's values ONE head
                  2 d wide; "cross_attention" READS THE FULL LAYER'S k, v
      "cross_attention": no key or value projection: the full layer's k, v
                  P_j = softmax(q_j k_j^T / sqrt(d)) over the visible keys
                        (sliding: t - sliding_window < s <= t; else s <= t);
                        query pair p reads key pair p // (pairs / key pairs)
                  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
                  lam0 = 0.8 - 0.6 exp(-0.3 i),  i the layer's index
                  o = RMSNorm_2d((P1 - lam P2) v) * sub_norm * (1 - lam0)
                  Mix = concat(o) Wo + bo           NO rotation, no position term
    logits = LN(x; final_norm) E^T                  the head is tied

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no page, no
cut: ALL layers over ALL positions (the served prefill runs the layers behind
the full attention layer on ONE position; here every position goes through
every layer), the state-space layer THE RECURRENCE, a ``lax.scan`` a position,
the two softmax maps written out. Sizes from the file's keys, weights from the
program's parameter tree (``layers.memory_mamba`` / ``memory_attn`` /
``memory_gate`` / ``memory_cross``: leaves stacked over a stack's layers,
stored ``[in, out]``); it imports nothing of ``ray_tpu``.

Departures, none of which changes a value. LAYOUT: the published in-projection
of the MLP holds gate and up side by side; the tree keeps two leaves. The
published attention takes the heads of a pair from neighbouring columns and
the pair's two value heads side by side; the tree's ``wq`` is ``[first heads |
second heads]`` and ``wkv`` ``[k first | k second | v]``: with seeded weights
any consistent pairing is one model. FOR ROOM: a matrix is cut out of its
stacked leaf and converted to float32 where it is used (:func:`_mm`); the
layers are walked in ``lax.scan`` over the stretches that repeat (ONE compiled
body a stretch, ONE layer's float32 copies alive); attention's queries go in
blocks of ``QUERY_BLOCK`` rows, each against ALL keys; the head is computed
``HEAD_BLOCKS`` blocks of vocabulary rows at a time into one array. What the
catalog cannot confirm is listed under ``assumed`` in
``configs/Phi-4-mini-flash-reasoning.json``.

The keyword switches (``skip=False``, ``window=511`` ...) compute a layer a
WRONG way: ``sweep/phi4flash_check.py`` measures that the comparison refuses
each.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
HEAD_BLOCKS = 16

# a layer type's stack in the program's tree
STACK = {"mamba": "memory_mamba", "sliding_attention": "memory_attn",
         "full_attention": "memory_attn", "gated_memory": "memory_gate",
         "cross_attention": "memory_cross"}


def _mm(x, w, at=()):
    """``x @ w[at]``, the matrix cut out of its stacked leaf ``w`` (as
    stored) and converted to float32 only once ``x`` has been computed."""
    w, _ = jax.lax.optimization_barrier((w, x))
    return x @ w[at].astype(F32)


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32) + b.astype(F32)


def kinds_of(cfg) -> list:
    """The built layers' types, from ``layer_types``."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def recurrence(u, dt, a, b_in, c_in):
    """The selective scan, position by position. ``u`` / ``dt`` [T, D]
    (``dt`` positive), ``a`` [D, N] (negative), ``b_in`` / ``c_in`` [T, N].
    Returns ``(y [T, D], S [D, N])`` from ``S_0 = 0``, ``y`` without the
    skip ``D u``."""
    def step(S, row):
        u_t, dt_t, b_t, c_t = row
        S = jnp.exp(dt_t[:, None] * a) * S \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return S, jnp.sum(S * c_t[None, :], axis=-1)

    S, y = jax.lax.scan(step, jnp.zeros(a.shape, F32), (u, dt, b_in, c_in))
    return y, S


def mamba(cfg, a, p, l, *, skip=True, **_):
    """Layer ``l``'s Mamba-1 mixer on ``a`` [T, hidden] (normed): ``(Mix, Y)``;
    ``p``: ``layers.memory_mamba``."""
    T = a.shape[0]
    N, K = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    u, z = jnp.split(_mm(a, p["w_in"], l), 2, axis=-1)
    w = p["conv_w"][l].astype(F32)                           # [taps, D]
    before = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(before[j:j + T] * w[j] for j in range(K))
                    + p["conv_b"][l].astype(F32))
    rbc = _mm(u, p["w_x"], l)
    r, b_in, c_in = rbc[:, :-2 * N], rbc[:, -2 * N:-N], rbc[:, -N:]
    dt = jax.nn.softplus(_mm(r, p["w_dt"], l) + p["dt_bias"][l].astype(F32))
    y, _ = recurrence(u, dt, -jnp.exp(p["A_log"][l].astype(F32)), b_in, c_in)
    if skip:
        y = y + p["D"][l].astype(F32) * u
    return _mm(y * jax.nn.silu(z), p["w_out"], l), y


def gated_memory(cfg, a, p, l, memory):
    """Layer ``l``'s gated memory unit: the memory AT THE SAME POSITION."""
    return _mm(jax.nn.silu(_mm(a, p["wg_in"], l)) * memory, p["wg_out"], l)


def attention(cfg, a, p, l, depth, keys, *, window=0, lam=None, depth_off=0,
              sub_norm=True, shift=0, **_):
    """Layer ``l``'s differential attention on ``a`` [T, hidden] (normed),
    before the residual add, its own keys and values where it has a
    projection for them and ``keys`` (k [T, kv heads, d], v) where not.
    Returns ``(Mix, (k, v))``. ``depth``: the layer's index in the stack (a
    number or traced). ``shift``: row ``t`` sees the keys ``s <= t - shift``
    (a row that stands for an earlier position)."""
    T = a.shape[0]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nq
    q = (_mm(a, p["wq"], l) + p["bq"][l].astype(F32)).reshape(T, nq, hd)
    if "wkv" in p:
        kv = _mm(a, p["wkv"], l) + p["bkv"][l].astype(F32)
        keys = tuple(half.reshape(T, nkv, hd)
                     for half in jnp.split(kv, 2, axis=-1))
    k, v = keys
    v = jnp.repeat(v.reshape(T, nkv // 2, 2 * hd), nq // nkv, axis=1)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * (depth + depth_off).astype(F32))
    lq1, lk1, lq2, lk2 = p["lam"][l].astype(F32)
    if lam is None:
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    spare = -T % QUERY_BLOCK               # rows in whole blocks, cut off again
    at = jnp.arange(T + spare).reshape(-1, QUERY_BLOCK)

    def softmax_map(q_j, k_j):
        """``softmax(q_j k_j^T / sqrt(d)) v`` over the visible keys: a query
        pair reads key pair ``p // (pairs / key pairs)``."""
        k_j = jnp.repeat(k_j, nq // nkv, axis=1)
        q_blocks = jnp.pad(q_j, ((0, spare), (0, 0), (0, 0))).reshape(
            -1, QUERY_BLOCK, nq // 2, hd)

        def block(rows):
            q_b, at_b = rows
            s = jnp.einsum("qhd,khd->hqk", q_b, k_j) / math.sqrt(hd)
            ahead = at_b[:, None] - shift - jnp.arange(T)[None, :]
            seen = ahead >= 0
            if window:
                seen &= ahead < window
            probs = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            # a row that sees nothing (a shifted first row) adds nothing
            probs = jnp.where(seen.any(-1)[None, :, None], probs, 0.0)
            return jnp.einsum("hqk,khe->qhe", probs, v)

        return jax.lax.map(block, (q_blocks, at)).reshape(
            -1, nq // 2, 2 * hd)[:T]

    o = softmax_map(q[:, :nq // 2], k[:, :nkv // 2]) \
        - lam * softmax_map(q[:, nq // 2:], k[:, nkv // 2:])
    if sub_norm:
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg["layer_norm_eps"])
    o = o * p["sub_norm"][l].astype(F32) * (1.0 - lam0)
    return (_mm(o.reshape(T, nq * hd), p["wo"], l)
            + p["bo"][l].astype(F32)), keys


def mlp(cfg, u, p, l):
    """The dense SwiGLU on ``u`` [T, hidden] (normed)."""
    return _mm(jax.nn.silu(_mm(u, p["w_gate"], l)) * _mm(u, p["w_up"], l),
               p["w_down"], l)


def layer(cfg, carry, layers, kind, l, depth, **wrong):
    """The ``l``-th layer of its STACK (``l`` counts the stack's layers, a
    number or traced), the stack's ``depth``-th layer overall; ``carry``:
    ``(x, memory, (k, v))``, the stream and what the newest state-space and
    attention layers handed on; ``layers``: the parameter tree's
    ``layers``."""
    x, memory, keys = carry
    p, eps = layers[STACK[kind]], cfg["layer_norm_eps"]
    a = _layer_norm(x, p["attn_norm"][l], p["attn_norm_b"][l], eps)
    if kind == "mamba":
        mix, memory = mamba(cfg, a, p, l, **wrong)
    elif kind == "gated_memory":
        mix = gated_memory(cfg, a, p, l, memory)
    else:
        sliding = kind == "sliding_attention"
        if not sliding:
            wrong = dict(wrong, window=0)
        elif not wrong.get("window"):
            wrong = dict(wrong, window=cfg["sliding_window"])
        if kind != "cross_attention":
            wrong = dict(wrong, shift=0)
        mix, new = attention(cfg, a, p, l, depth, keys, **wrong)
        # the FULL layer's keys and values are what the cross layers read
        if kind != "cross_attention" and sliding == bool(
                wrong.get("cross_reads_window_keys")):
            keys = new
    h = x + mix
    u = _layer_norm(h, p["mlp_norm"][l], p["mlp_norm_b"][l], eps)
    return h + mlp(cfg, u, p, l), memory, keys


def _stretches(kinds) -> list:
    """``[(unit, times), ...]``: from each layer on the shortest unit that
    stands there twice or more in a row, the layers between such stretches
    one at a time."""
    out, at = [], 0
    while at < len(kinds):
        for n in range(1, (len(kinds) - at) // 2 + 1):
            unit, reps = kinds[at:at + n], 1
            while kinds[at + reps * n:at + (reps + 1) * n] == unit:
                reps += 1
            if reps > 1:
                break
        else:
            unit, reps = kinds[at:at + 1], 1
        out.append((unit, reps))
        at += reps * len(unit)
    return out


def _states(cfg, params, tokens, *, memory_back=0, cross_reads_window_keys=False,
            shift=0, **wrong):
    """tokens [T] -> final-normed states [T, hidden] of one sequence.
    ``memory_back``: the gated memory units read the memory of the
    state-space layer that many IN FRONT of the newest; ``cross_reads_window_
    keys``: the cross layers read the newest SLIDING layer's keys and values;
    ``shift``: the layers behind the full attention layer run row ``t`` on
    position ``t - shift``'s stream and memory (all three: wrong ways)."""
    T = tokens.shape[0]
    nkv = cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    x = params["embedding"][tokens].astype(F32)
    kinds = kinds_of(cfg)
    wrong = dict(wrong, cross_reads_window_keys=cross_reads_window_keys,
                 shift=shift)
    carry = (x, jnp.zeros((T, cfg["mamba_expand"] * cfg["hidden_size"]), F32),
             (jnp.zeros((T, nkv, hd), F32),) * 2)
    met = dict.fromkeys(STACK.values(), 0)
    depth = 0

    for unit, times in _stretches(kinds):
        per = {s: sum(STACK[k] == s for k in unit) for s in met}
        first, at = dict(met), depth

        def run(carry, n, unit=unit, per=per, first=first, at=at):
            seen = dict.fromkeys(per, 0)
            for j, kind in enumerate(unit):
                s = STACK[kind]
                carry = layer(cfg, carry, params["layers"], kind,
                              first[s] + n * per[s] + seen[s],
                              jnp.asarray(at + n * len(unit) + j), **wrong)
                seen[s] += 1
            return carry

        if times > 1:  # for room: one compiled body, one layer's copies
            carry, _ = jax.lax.scan(lambda c, n: (run(c, n), None), carry,
                                    jnp.arange(times, dtype=jnp.int32))
        else:
            before = carry[1]
            carry = run(carry, 0)
            if "mamba" in unit and memory_back:  # the memory in front
                carry = (carry[0], before, carry[2])
            if "full_attention" in unit and shift:
                x, memory, keys = carry
                carry = (jnp.roll(x, shift, 0), jnp.roll(memory, shift, 0),
                         keys)
        for s in met:
            met[s] += times * per[s]
        depth += times * len(unit)
    return _layer_norm(carry[0], params["final_norm"], params["final_norm_b"],
                       cfg["layer_norm_eps"])


def _head_logits(cfg, params, x):
    """``x E^T`` (the tied head; ``x W`` of ``lm_head`` otherwise),
    ``HEAD_BLOCKS`` blocks of vocabulary rows at a time, each converted to
    float32 where it is used and written into ONE ``[T, vocab]`` array."""
    tied = cfg["tie_word_embeddings"]
    table = params["embedding"] if tied else params["lm_head"].T
    V = table.shape[0]
    n = max(b for b in range(1, HEAD_BLOCKS + 1) if V % b == 0)
    rows = V // n

    def block(i, out):
        w = jax.lax.dynamic_slice_in_dim(table, i * rows, rows, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ w.astype(F32).T, i * rows, 1)

    return jax.lax.fori_loop(0, n, block, jnp.zeros((x.shape[0], V), F32))


def logits_one(cfg, params, tokens, **wrong):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        return _head_logits(cfg, params, _states(cfg, params, tokens, **wrong))


def loss(cfg, params, tokens):
    """tokens [B, T + 1] -> mean next-token cross-entropy, one sequence at a
    time."""
    with jax.default_matmul_precision("highest"):
        def nll(row):
            logp = jax.nn.log_softmax(_head_logits(
                cfg, params, _states(cfg, params, row[:-1])), -1)
            return -jnp.take_along_axis(logp, row[1:, None], axis=-1).sum()

        B, T1 = tokens.shape
        return jax.lax.map(nll, tokens).sum() / (B * (T1 - 1))
