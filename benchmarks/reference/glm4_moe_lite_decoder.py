"""The plain reference of GLM-4.7-Flash (``model_type: glm4_moe_lite``): a
stack of blocks ``x <- x + MLA(N(x)); x <- x + MLP(N(x))`` (``N``: RMSNorm,
eps 1e-5) whose first ``first_k_dense_replace`` MLPs are dense SwiGLUs and
whose others are routed, a final RMSNorm, an untied head, and ONE
multi-token-prediction module with a second cross-entropy. The public
description of the block and of the module is DeepSeek-V3's technical report
(arXiv:2412.19437, sections 2.1-2.2).

Latent attention (``MLA``), H heads, on a normed sequence ``h`` [T, hidden]:
    q = N(h Wq_a) Wq_b                       # a head: [nope (192) | rope (64)]
    [c | k_r] = h Wkv_a                      # kv_lora_rank | rope, ONE row a position
    [k_nope | v] = N(c) Wkv_b                # a head: 192 | 256
    q_rope, k_r rotated: pair (2i, 2i + 1) by t * theta ** (-2i / 64)
    k = [k_nope | k_r]                       # k_r the same for every head
    o = softmax(q k^T / sqrt(192 + 64), causal) v
    out = o Wo                               # H x 256 -> hidden
    (no sqrt(hidden / rank) factor on N(h Wq_a) or N(c): config.json has no
    mla_scale_* key; the one other latent model in this benchmark sets both)
The routed MLP, a router over ``router_experts`` experts, top-k:
    s = sigmoid(h W_r)                       # float32
    chosen = top_k(s + b)                    # b: e_score_correction_bias, for
                                             # the CHOICE only, no gradient;
                                             # n_group = topk_group = 1
    w_j = s_{e_j} / (sum_j s_{e_j} + 1e-20) * routed_scaling_factor
    e(h) = (silu(h W_gate,e) * (h W_up,e)) W_down,e
    out = sum_j w_j e_j(h) + shared(h)       # shared: ONE more SwiGLU of the
                                             # experts' width, NO gate before it
The prediction module, for tokens t_0 .. t_T and the main model's
final-normed states x_i (i = 0 .. T - 1):
    h'_i = [N_e(Emb(t_{i+1})) ; N_h(x_i)] W_eh          # 2 hidden -> hidden
    y = one more routed block over h'; logits = N(y) head
    L_mtp = mean_{i = 0 .. T-2} -log p(t_{i+2})          # T - 1 has no target
    loss = L_main + mtp_loss_weight * L_mtp
    Emb and head are the MAIN model's (shared, not copied).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time, reading
its sizes from the configuration FILE's keys and importing nothing from the
program. Every token goes through every HELD expert in a loop and the result
is weighted by the token's top-k weight for that expert (0 where it was not
chosen).

Departures from the published description, none of mathematics:

- The file describes ONE CHIP'S SHARE of a deployment in which
  ``router_experts / n_routed_experts`` chips share each layer: this chip
  holds experts ``first_expert .. first_expert + n_routed_experts - 1``. The
  router and the top-k run over all ``router_experts``; an expert held
  elsewhere adds nothing here, in the program and in this file alike, and
  the partial sum (with the shared expert, which every chip computes) goes
  on to the next layer. ``experts(..., first, count)`` takes any range, so a
  test can add the shares up to the uncut layer.
- The vocabulary is the slice the file's ``vocab_size`` gives: logits,
  softmax and both losses are over the slice.
- No router loss is trained (config.json has no key for one) and ``b`` is
  not updated.
- The module reads the main model's states AFTER its final norm, and the
  concatenation is ``[embedding ; hidden]``: the public inference
  implementations' reading of the report's equation 21.
  ``mtp_loss_weight`` is the file's (config.json has none).
- The module's block runs over all T positions (its last input is
  ``Emb(t_T)``) and the last position is dropped before the loss: causal
  attention lets no earlier position see it.
- Layout: weights are read from the program's parameter tree: the leading
  dense blocks stacked under ``layers.latent_dense``, the routed ones under
  ``layers.latent``, the module under ``mtp`` (``enorm``, ``hnorm``,
  ``eh_proj`` [2 hidden, hidden] with the embedding's half first,
  ``layers.latent`` a stack of one, ``final_norm``); matrices stored [in,
  out], experts [held, in, out].
- For memory only: attention takes its queries in blocks (8,192 x 8,192 x
  20 float32 scores are 5.4 GB whole), a block and the experts' loop are
  rematerialised. The values are those of the whole computation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
STACK = {"dense": "latent_dense", "routed": "latent"}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, D]; position t rotates pair (2i, 2i + 1) by t * theta^(-2i/D)."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def qkv(cfg, h, p):
    """Per-head ``q``, ``k`` [T, H, nope + rope] and ``v`` [T, H, v] of one
    normed sequence, rotated."""
    T = h.shape[0]
    H, eps, theta = (cfg["num_attention_heads"], cfg["rms_norm_eps"],
                     cfg["rope_theta"])
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    q = (_rms_norm(h @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]).reshape(
        T, H, dn + dr)
    ckr = h @ p["wkv_a"]
    kv = (_rms_norm(ckr[:, :r], p["kv_norm"], eps) @ p["wkv_b"]).reshape(
        T, H, dn + dv)
    k_r = _rope(ckr[:, None, r:], theta)                        # [T, 1, dr]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (T, H, dr))],
                        axis=-1)
    return q, k, kv[..., dn:]


def attention(cfg, h, p):
    """Latent attention of one normed sequence ``h`` [T, hidden], the
    queries a block at a time."""
    T = h.shape[0]
    q, k, v = qkv(cfg, h, p)
    H, width = q.shape[1], q.shape[2]
    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    @jax.checkpoint
    def block(args):
        q_b, at = args                                   # [qb, H, w], [qb]
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(width)
        s = jnp.where(at[None, :, None] >= jnp.arange(T)[None, None, :],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(T // qb, qb, H, width),
                            jnp.arange(T).reshape(T // qb, qb)))
    return o.reshape(T, -1) @ p["wo"]


def route(cfg, h, p):
    """``weight`` [T, router_experts]: a token's weight for every expert of
    the router's width, 0 for those it did not choose."""
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("this reference knows no group-limited choice")
    s = jax.nn.sigmoid(h @ p["router"])
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]), k)
    top_w = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg["routed_scaling_factor"]
    chosen = top_e[:, :, None] == jnp.arange(E)[None, None, :]   # [T, k, E]
    return jnp.sum(jnp.where(chosen, top_w[:, :, None], 0.0), axis=1)


def experts(cfg, h, p, first, count):
    """The weighted sum over experts ``first .. first + count - 1`` (the
    rows of ``p["w_gate"]`` / ``p["w_up"]`` / ``p["w_down"]``) of normed
    tokens ``h`` [T, hidden]: every token through every one of them."""
    weight = route(cfg, h, p)[:, first:first + count]

    def one_expert(y, ew):
        w_e, wg, wu, wd = ew
        return y + w_e[:, None] * _swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                        (weight.T, p["w_gate"], p["w_up"], p["w_down"]))
    return y


def shared_expert(h, p):
    """n_shared_experts = 1: one SwiGLU every token runs, ungated."""
    return _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])


def moe(cfg, h, p):
    """This chip's routed MLP: its held experts' part plus the shared
    expert."""
    if cfg["n_shared_experts"] != 1:
        raise ValueError("this reference knows ONE shared expert")
    return experts(cfg, h, p, cfg["first_expert"],
                   cfg["n_routed_experts"]) + shared_expert(h, p)


def dense(cfg, h, p):
    """A leading layer's MLP: a SwiGLU of width intermediate_size."""
    return _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


LAYER = {"dense": dense, "routed": moe}


def kinds_of(cfg):
    """The blocks' MLPs in order, from the published keys."""
    return ["dense" if i < cfg["first_k_dense_replace"] else "routed"
            for i in range(cfg["num_hidden_layers"])]


def block(cfg, x, p, kind):
    """One block on the stream ``x`` [T, hidden]; ``p``: its leaves."""
    eps = cfg["rms_norm_eps"]

    def run(x, p):
        x = x + attention(cfg, _rms_norm(x, p["attn_norm"], eps), p)
        return x + LAYER[kind](cfg, _rms_norm(x, p["mlp_norm"], eps), p)

    return jax.checkpoint(run)(x, p)


def hidden_one(cfg, params, tokens):
    """tokens [T] -> final-normed states [T, hidden] of one sequence."""
    x = params["embedding"].astype(F32)[tokens]
    met = dict.fromkeys(STACK, 0)
    for kind in kinds_of(cfg):
        p = jax.tree.map(lambda a: a[met[kind]].astype(F32),
                         params["layers"][STACK[kind]])
        met[kind] += 1
        x = block(cfg, x, p, kind)
    return _rms_norm(x, params["final_norm"].astype(F32), cfg["rms_norm_eps"])


def mtp(cfg, params, hidden, tokens):
    """The prediction module's final-normed states [T, hidden]: ``hidden``
    [T, hidden] the main model's final-normed states over ``tokens[:-1]``,
    ``tokens`` [T + 1]. Row ``i`` predicts ``tokens[i + 2]``; row ``T - 1``
    has no target."""
    if cfg["num_nextn_predict_layers"] != 1:
        raise ValueError("this reference knows ONE prediction module")
    eps = cfg["rms_norm_eps"]
    m = jax.tree.map(lambda a: a.astype(F32), params["mtp"])
    merged = jnp.concatenate(
        [_rms_norm(params["embedding"].astype(F32)[tokens[1:]], m["enorm"],
                   eps),
         _rms_norm(hidden, m["hnorm"], eps)], axis=-1) @ m["eh_proj"]
    y = block(cfg, merged, jax.tree.map(lambda a: a[0], m["layers"]["latent"]),
              "routed")
    return _rms_norm(y, m["final_norm"], eps)


def logits_one(cfg, params, tokens):
    """tokens [T] int32 -> the MAIN model's logits [T, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        return hidden_one(cfg, params, tokens) @ params["lm_head"].astype(F32)


def _nll_sum(states, head, targets):
    logp = jax.nn.log_softmax(states @ head, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).sum()


def losses(cfg, params, tokens):
    """tokens [B, T + 1] -> ``(L_main, L_mtp)``: the mean next-token
    cross-entropy over B x T positions and the module's over B x (T - 1)."""
    with jax.default_matmul_precision("highest"):
        head = params["lm_head"].astype(F32)

        def both(row):  # one sequence
            hidden = hidden_one(cfg, params, row[:-1])
            main = _nll_sum(hidden, head, row[1:])
            if not cfg["num_nextn_predict_layers"]:
                return main, jnp.zeros((), F32)
            ahead = mtp(cfg, params, hidden, row)[:-1]
            return main, _nll_sum(ahead, head, row[2:])

        B, T1 = tokens.shape
        main, ahead = jax.lax.map(both, tokens)
        return main.sum() / (B * (T1 - 1)), ahead.sum() / (B * (T1 - 2))


def loss(cfg, params, tokens):
    """tokens [B, T + 1] -> what the model is trained on: ``L_main +
    mtp_loss_weight * L_mtp`` (no router loss)."""
    main, ahead = losses(cfg, params, tokens)
    return main + cfg["mtp_loss_weight"] * ahead
