"""The plain reference of Nemotron-H (``model_type: nemotron_h``,
``modeling_nemotron_h.py`` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): a stack
whose layers are given by a pattern over ``M`` (a Mamba-2 mixer), ``E`` (a
routed feed-forward with one shared expert) and ``*`` (attention). Every
layer is ONE of them, ``x <- x + f(RMSNorm(x))``, eps 1e-5; then a final
RMSNorm, an untied head and cross-entropy.

``M``, with H heads of P channels (d_inner = H P), G groups, state N,
kernel K:
    [z | xBC | dt] = h W_in                  # widths d_inner | d_inner + 2 G N | H
    xBC = silu(conv1d_causal_depthwise(xBC, w) + b)
    x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)   # head i reads group i // (H / G)
    dt = softplus(dt + dt_bias)      A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t         # S [H, P, N], S_0 = 0
    y_t = S_t C_t + D x_t
    out = (GroupRMSNorm_G(y * silu(z)) * w) W_out        # the norm AFTER the gate
``E``, with a router over ``router_experts`` experts, top-k:
    s = sigmoid(h W_r)                       # float32
    chosen = top_k(s + b)                    # b: for the CHOICE only, no gradient
    w_j = s_{e_j} / (sum_j s_{e_j} + 1e-20) * routed_scaling_factor
    e(h) = relu(h W_up,e) ** 2 W_down,e      # two matrices, no gate
    out = sum_j w_j e_j(h) + shared(h)       # shared: the same form, wider
``*``: q / k / v / o projections without bias, grouped-query causal
attention, NO rotary embedding (the published model code applies none;
position comes from the Mamba layers).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time, reading
its sizes from the configuration FILE's keys and importing nothing from the
program. The state-space layer is the RECURRENCE, one step a token (no
chunks, no decay matrices); the convolution is K shifted adds; every token
goes through every HELD expert in a loop and the result is weighted by the
token's top-k weight for that expert (0 where it was not chosen).

Departures from the published description, none of mathematics:

- The file describes ONE CHIP'S SHARE of a deployment in which
  ``router_experts / n_routed_experts`` chips share each layer: this chip
  holds experts ``first_expert .. first_expert + n_routed_experts - 1``. The
  router and the top-k run over all ``router_experts``; an expert held
  elsewhere adds nothing here, in the program and in this file alike, and
  the partial sum (with the shared expert, which every chip computes) goes
  on to the next layer. ``routed(..., first, count)`` takes any range, so a
  test can add the shares up to the uncut layer.
- The vocabulary is the slice the file's ``vocab_size`` gives: logits,
  softmax and loss are over the slice.
- No router loss is trained (config.json has no key for one) and ``b``
  is not updated.
- Layout: weights are read from the program's parameter tree,
  ``layers.{mamba,moe,attn}.<leaf>`` stacked per KIND in the pattern's
  order and stored [in, out] (experts [held, in, out]; the conv [K,
  channels], ``w[K - 1]`` on the position itself).
- For memory only: the recurrence's scan over time is rematerialised in
  segments, attention takes its queries in blocks (8,192 x 8,192 x 32
  float32 scores are 8.6 GB whole), the experts' loop is rematerialised an
  expert. The values are those of the whole computation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _blocks(T, size):
    return size if T % size == 0 else T


def mamba(cfg, h, p):
    """The mixer's update of one normed sequence ``h`` [T, hidden]."""
    T = h.shape[0]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    di, gn = H * P, G * N
    zxbcdt = h @ p["w_in"]
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * gn],
                  zxbcdt[:, 2 * di + 2 * gn:])
    conv = p["conv_b"][None, :]
    for back in range(K):  # w[K - 1 - back] on the position ``back`` back
        shifted = jnp.concatenate(
            [jnp.zeros((back, xbc.shape[1]), F32), xbc[:T - back]], axis=0)
        conv = conv + shifted * p["conv_w"][K - 1 - back]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(T, H, P)
    b_in = jnp.repeat(xbc[:, di:di + gn].reshape(T, G, N), H // G, axis=1)
    c_in = jnp.repeat(xbc[:, di + gn:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # [T, H]
    a = -jnp.exp(p["A_log"])                                  # [H]

    def step(state, now):
        x_t, b_t, c_t, dt_t = now
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    seg = _blocks(T, 128)

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(step, state, xs)

    _, y = jax.lax.scan(
        segment, jnp.zeros((H, P, N), F32),
        jax.tree.map(lambda v: v.reshape(T // seg, seg, *v.shape[1:]),
                     (x, b_in, c_in, dt)))
    y = y.reshape(T, H, P) + p["D"][:, None] * x
    y = y.reshape(T, G, di // G) * jax.nn.silu(z).reshape(T, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return (y.reshape(T, di) * p["gate_norm"]) @ p["w_out"]


def route(cfg, h, p):
    """``weight`` [T, router_experts]: a token's weight for every expert of
    the router's width, 0 for those it did not choose."""
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ p["router"])
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]), k)
    top_w = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg["routed_scaling_factor"]
    chosen = top_e[:, :, None] == jnp.arange(E)[None, None, :]   # [T, k, E]
    return jnp.sum(jnp.where(chosen, top_w[:, :, None], 0.0), axis=1)


def routed(cfg, h, p, first, count):
    """The weighted sum over experts ``first .. first + count - 1`` (the
    rows of ``p["w_up"]`` / ``p["w_down"]``) of normed tokens ``h`` [T,
    hidden]: every token through every one of them."""
    weight = route(cfg, h, p)[:, first:first + count]

    def one_expert(y, ew):
        w_e, wu, wd = ew
        return y + w_e[:, None] * (_relu2(h @ wu) @ wd), None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                        (weight.T, p["w_up"], p["w_down"]))
    return y


def shared(h, p):
    return _relu2(h @ p["shared_up"]) @ p["shared_down"]


def moe(cfg, h, p):
    """This chip's routed layer: its held experts' part plus the shared
    expert."""
    return routed(cfg, h, p, cfg["first_expert"],
                  cfg["n_routed_experts"]) + shared(h, p)


def attention(cfg, h, p):
    """Causal grouped-query attention of one normed sequence, no rotation,
    the queries a block at a time."""
    T = h.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = (h @ p["wq"]).reshape(T, nq, hd)
    k = jnp.repeat((h @ p["wk"]).reshape(T, nkv, hd), nq // nkv, axis=1)
    v = jnp.repeat((h @ p["wv"]).reshape(T, nkv, hd), nq // nkv, axis=1)
    qb = _blocks(T, 512)

    @jax.checkpoint
    def block(args):
        q_b, at = args                                   # [qb, nq, hd], [qb]
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(hd)
        s = jnp.where(at[None, :, None] >= jnp.arange(T)[None, None, :],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    a = jax.lax.map(block, (q.reshape(T // qb, qb, nq, hd),
                            jnp.arange(T).reshape(T // qb, qb)))
    return a.reshape(T, nq * hd) @ p["wo"]


LAYER = {"M": mamba, "E": moe, "*": attention}


def hidden_one(cfg, params, tokens):
    """tokens [T] -> final-normed states [T, hidden] of one sequence."""
    eps = cfg["layer_norm_epsilon"]
    x = params["embedding"].astype(F32)[tokens]
    met = dict.fromkeys(KINDS, 0)
    for kind in cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]:
        p = jax.tree.map(lambda a: a[met[kind]].astype(F32),
                         params["layers"][KINDS[kind]])
        met[kind] += 1
        x = x + LAYER[kind](cfg, _rms_norm(x, p["norm"], eps), p)
    return _rms_norm(x, params["final_norm"].astype(F32), eps)


def logits_one(cfg, params, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        return hidden_one(cfg, params, tokens) @ params["lm_head"].astype(F32)


def loss(cfg, params, tokens):
    """tokens [B, T + 1] -> the mean next-token cross-entropy, what the
    model is trained on (no router loss)."""
    with jax.default_matmul_precision("highest"):
        head = params["lm_head"].astype(F32)

        def nll(row):  # one sequence, one [T, vocab] block of logits
            logp = jax.nn.log_softmax(
                hidden_one(cfg, params, row[:-1]) @ head, axis=-1)
            return -jnp.take_along_axis(logp, row[1:, None], axis=-1).sum()

        B, T1 = tokens.shape
        return jax.lax.map(nll, tokens).sum() / (B * (T1 - 1))
