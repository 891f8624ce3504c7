"""The plain reference of Xing4.0-29B-A4B (``model_type: xing4_0``): a stack
of blocks whose residual stream is ``hc_mult`` = 4 rows a token, mixed around
every sublayer by manifold-constrained hyper-connections ("mHC",
arXiv:2512.24880, over "Hyper-Connections", arXiv:2409.19606), latent (MLA)
attention under a YaRN rotation in every block, a dense SwiGLU in the leading
blocks and sigmoid-routed experts beside one shared expert in the others
(DeepSeek-V3's, arXiv:2412.19437 sections 2.1-2.2), a final RMSNorm and an
untied head.

The stream: a token's state is ``X`` [n, hidden], ``X_0`` the token's
embedding in all n rows. ONE sublayer ``F`` (its own ``phi`` [n hidden, 2n +
n n], ``b`` [2n + n n], ``alpha`` [3]) on it, eps = ``hc_eps``:
    x~ = vec(X)                                       # row j at [j hidden ..]
    m = (x~ phi) / sqrt(mean(x~ ** 2) + eps)          # phi carries the gain
    H_pre  = sigmoid(alpha_0 m[:n] + b[:n])
    H_post = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n])
    R = clip(alpha_2 m[2n:] + b[2n:], mhc_h_res_clamp_min, .._max)  # [n, n]
    H_res = sinkhorn(exp(R)): hc_sinkhorn_iters times, every ROW over its
            sum + eps, then every COLUMN over its sum + eps
    h = sum_j H_pre[j] X[j];  y = F(N(h))             # N: the usual RMSNorm
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y
After the last block the rows are SUMMED, then the final norm and the head.

Latent attention, H heads, on a normed sequence ``h`` [T, hidden]:
    q = N(h Wq_a) Wq_b                       # a head: [nope (128) | rope (64)]
    [c | k_r] = h Wkv_a                      # kv_lora_rank | rope, ONE row a position
    [k_nope | v] = N(c) Wkv_b                # a head: 128 | 128
    q_rope, k_r rotated: pair (2i, 2i + 1) by t * f_i, f_i YaRN's
    (:func:`yarn_frequencies`), cosines and sines times mscale /
    mscale_all_dim = 1
    o = softmax(q k^T * (128 + 64) ** -0.5 * (0.1 mscale_all_dim ln(factor)
        + 1) ** 2, causal) v                 # k_r the same for every head
    out = o Wo                               # H x 128 -> hidden
    (no sqrt(hidden / rank) factor: config.json has no key for one)
The routed MLP, a router over ``n_routed_experts``, top-k:
    s = sigmoid(h W_r); chosen = top_k(s + b)         # b for the choice only
    w_j = s_{e_j} / (sum_j s_{e_j} + 1e-20) * routed_scaling_factor
    out = sum_j w_j swiglu_{e_j}(h) + shared(h)       # shared: ungated

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, one sequence at a time, reading
its sizes from the configuration FILE's keys and importing nothing from the
program. Every token goes through every expert in a loop and the result is
weighted by the token's top-k weight for that expert (0 where not chosen).

Departures from the published description, none of mathematics:

- The file is a CUT in depth: its ``layer_pattern`` ("G" a dense block, "L" a
  routed one) names the blocks built, published layers 1-5, so ONE of the
  ``first_k_dense_replace`` = 2 dense blocks stands in front of four routed
  ones; :func:`kinds_of` reads the pattern, not ``first_k_dense_replace``.
- The multi-token-prediction module (``num_nextn_predict_layers`` 1) is not
  here: it drafts decoded tokens and changes no logit of the main model.
- The router's bias is whatever the tree holds (zero) and is not updated.
- Where config.json has no key (the file's ``assumed`` lists each): the
  stream's start (n copies) and end (the rows' sum); rows normalised before
  columns, eps in every divisor; the clamp in front of the exponential;
  the rotation's pairing.
- Layout: weights are read from the program's parameter tree (matrices
  stored [in, out], experts [experts, in, out], whatever type they are held
  in, widened where they are used): the dense blocks stacked under
  ``layers.latent_dense``, the routed ones under ``layers.latent``; a
  block's hyper-connections ``hc_phi`` [2, n hidden, 2n + n n], ``hc_b``
  [2, 2n + n n], ``hc_alpha`` [2, 3], the attention's sublayer first.
- For memory only: attention takes its queries in blocks, a block and the
  experts' loop are rematerialised. The values are those of the whole
  computation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
STACK = {"G": "latent_dense", "L": "latent"}


def _w(a):
    return a.astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(w)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _w(gate)) * (h @ _w(up))) @ _w(down)


# --- the stream -------------------------------------------------------------


def sinkhorn(M, iters, eps):
    """``M`` [T, n, n] positive -> doubly stochastic to within the
    iteration's error: rows first, then columns, ``iters`` times."""
    for _ in range(iters):
        M = M / (M.sum(axis=2, keepdims=True) + eps)   # every row
        M = M / (M.sum(axis=1, keepdims=True) + eps)   # every column
    return M


def hyper_mix(cfg, X, phi, b, alpha):
    """``X`` [T, n, hidden] -> ``(H_pre [T, n], H_post [T, n], H_res [T, n,
    n])`` of one sublayer."""
    T, n, _ = X.shape
    eps = cfg["hc_eps"]
    flat = X.reshape(T, -1)
    m = (flat @ _w(phi)) * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    b, alpha = _w(b), _w(alpha)
    H_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    H_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    R = jnp.clip(alpha[2] * m[:, 2 * n:] + b[2 * n:],
                 cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    H_res = sinkhorn(jnp.exp(R).reshape(T, n, n), cfg["hc_sinkhorn_iters"],
                     eps)
    return H_pre, H_post, H_res


def read_out(H_pre, X):
    """The ONE row a sublayer reads: ``sum_j H_pre[j] X[j]`` [T, hidden]."""
    return jnp.einsum("tj,tjd->td", H_pre, X)


def write_back(H_res, H_post, X, y):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``."""
    return jnp.einsum("tij,tjd->tid", H_res, X) \
        + H_post[:, :, None] * y[:, None, :]


def sublayer(cfg, X, hc, f):
    """One hyper-connected sublayer: ``f`` reads the mixed row (and norms it
    itself); ``hc = (phi, b, alpha)``."""
    H_pre, H_post, H_res = hyper_mix(cfg, X, *hc)
    return write_back(H_res, H_post, X, f(read_out(H_pre, X)))


# --- latent attention -------------------------------------------------------


def yarn_frequencies(cfg):
    """The rope slice's ``qk_rope_head_dim / 2`` frequencies under the
    file's ``rope_scaling`` (type yarn)."""
    y, D, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    if y["type"] != "yarn" or y["mscale"] != y["mscale_all_dim"]:
        raise ValueError("this reference knows YaRN at amplitude 1")
    f = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)

    def turns(beta):
        return (D * math.log(y["original_max_position_embeddings"]
                             / (beta * 2 * math.pi)) / (2 * math.log(theta)))

    low = min(max(math.floor(turns(y["beta_fast"])), 0), D - 1)
    high = min(max(math.ceil(turns(y["beta_slow"])), 0), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / y["factor"] * ramp


def softmax_scale(cfg):
    y = cfg["rope_scaling"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return width ** -0.5 * (
        0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0) ** 2


def _rope(x, inv):
    """x [T, H, D]; position t rotates pair (2i, 2i + 1) by t * inv[i]."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def qkv(cfg, h, p):
    """Per-head ``q``, ``k`` [T, H, nope + rope] and ``v`` [T, H, v] of one
    normed sequence, rotated: keys and values made from the latent row."""
    T = h.shape[0]
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    inv = yarn_frequencies(cfg)
    q = (_rms_norm(h @ _w(p["wq_a"]), p["q_norm"], eps)
         @ _w(p["wq_b"])).reshape(T, H, dn + dr)
    ckr = h @ _w(p["wkv_a"])
    kv = (_rms_norm(ckr[:, :r], p["kv_norm"], eps)
          @ _w(p["wkv_b"])).reshape(T, H, dn + dv)
    k_r = _rope(ckr[:, None, r:], inv)                          # [T, 1, dr]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv)], axis=-1)
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
    scale = softmax_scale(cfg)

    @jax.checkpoint
    def block(args):
        q_b, at = args                                   # [qb, H, w], [qb]
        s = jnp.einsum("qhd,khd->hqk", q_b, k) * scale
        s = jnp.where(at[None, :, None] >= jnp.arange(T)[None, None, :],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(T // qb, qb, H, width),
                            jnp.arange(T).reshape(T // qb, qb)))
    return o.reshape(T, -1) @ _w(p["wo"])


# --- the MLPs ---------------------------------------------------------------


def route(cfg, h, p):
    """``weight`` [T, n_routed_experts]: a token's weight for every expert,
    0 for those it did not choose."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["scoring_func"] != "sigmoid":
        raise ValueError("this reference knows sigmoid scores and no "
                         "group-limited choice")
    s = jax.nn.sigmoid(h @ _w(p["router"]))
    _, top_e = jax.lax.top_k(s + _w(p["router_bias"]), k)
    top_w = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg["routed_scaling_factor"]
    chosen = top_e[:, :, None] == jnp.arange(E)[None, None, :]   # [T, k, E]
    return jnp.sum(jnp.where(chosen, top_w[:, :, None], 0.0), axis=1)


def experts(cfg, h, p):
    """The weighted sum over all experts of normed tokens ``h`` [T, hidden]:
    every token through every one of them."""
    weight = route(cfg, h, p)

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
    if cfg["n_shared_experts"] != 1:
        raise ValueError("this reference knows ONE shared expert")
    return experts(cfg, h, p) + shared_expert(h, p)


def dense(cfg, h, p):
    """A leading block's MLP: a SwiGLU of width intermediate_size."""
    return _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


LAYER = {"G": dense, "L": moe}


def kinds_of(cfg):
    """The built blocks' kinds in order, from the file's pattern."""
    return list(cfg["layer_pattern"][:cfg["num_hidden_layers"]])


def block(cfg, X, p, kind):
    """One block on the stream ``X`` [T, n, hidden]; ``p``: its leaves."""
    eps = cfg["rms_norm_eps"]

    def run(X, p):
        hc = [(p["hc_phi"][j], p["hc_b"][j], p["hc_alpha"][j])
              for j in (0, 1)]
        X = sublayer(cfg, X, hc[0], lambda h: attention(
            cfg, _rms_norm(h, p["attn_norm"], eps), p))
        return sublayer(cfg, X, hc[1], lambda h: LAYER[kind](
            cfg, _rms_norm(h, p["mlp_norm"], eps), p))

    return jax.checkpoint(run)(X, p)


def hidden_one(cfg, params, tokens):
    """tokens [T] -> final-normed states [T, hidden] of one sequence."""
    n = cfg["hc_mult"]
    x = _w(params["embedding"][tokens])
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
    met = dict.fromkeys(STACK, 0)
    for kind in kinds_of(cfg):
        p = jax.tree.map(lambda a: a[met[kind]],
                         params["layers"][STACK[kind]])
        met[kind] += 1
        X = block(cfg, X, p, kind)
    return _rms_norm(X.sum(axis=1), params["final_norm"], cfg["rms_norm_eps"])


def logits_one(cfg, params, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        return hidden_one(cfg, params, tokens) @ _w(params["lm_head"])
