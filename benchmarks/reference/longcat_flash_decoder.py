"""The plain reference of LongCat-Flash-Chat's decoder, as one chip's share
of an expert-parallel pool holds it: shortcut-connected double layers of
latent (MLA) attention, dense SwiGLU feed-forwards and a softmax top-k
router over real and identity ("zero-computation") experts.

One layer, ``x`` ``[T, hidden]``, ``N`` RMSNorm::

    a0  = x  + MLA_0(N(x))
    h0  = N(a0);  m = MoE(h0)          # the shortcut: m waits
    b0  = a0 + FFN_0(h0)
    a1  = b0 + MLA_1(N(b0))
    b1  = a1 + FFN_1(N(a1))
    out = b1 + m                       # attention 1 and FFN 1 never see m

``MLA(h)``: ``q = N(h Wqa) Wqb`` a head ``[nope | rope]``, times
``sqrt(hidden / q_lora_rank)`` where ``mla_scale_q_lora``; ``h Wkva = [c |
kr]``, ``c = N(c)`` times ``sqrt(hidden / kv_lora_rank)`` where
``mla_scale_kv_lora``; ``c Wkvb`` a head ``[k_nope | v]``; RoPE over pairs
``(2i, 2i + 1)`` of ``q``'s rope slice and of ``kr``, which all heads share;
scores ``(q_nope . k_nope + q_rope . kr) / sqrt(nope + rope)``, causal,
softmax; ``concat_h(P v) Wo``. ``MoE(h)``: ``p = softmax(h Wr)`` over
``n_routed + zero_expert_num`` outputs, the choice ``top_k(p + bias)``, the
weights ``routed_scaling_factor * p_j`` of the chosen, not renormalised; a
real expert is a SwiGLU, an identity expert returns its input. Only the
experts ``first_expert .. first_expert + n_routed_experts`` of the router's
``router_experts`` real ones are held here: what the others would add is
left out, as in the program.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, keys and
values expanded for every position, a loop over layers and one over a
layer's held experts (``lax.fori_loop``, so that each body is compiled once:
unrolled, the 300 float32 products at "highest" take the TPU's compiler 200
s). Two things are done for room and change no value: a matrix is cut out
of its stacked leaf and converted to float32 where it is used and not
before (:func:`_mm` ties both to the product's other operand with an
optimization barrier: beside an engine's 10 GB the compiler otherwise cuts
out a whole layer's matrices up front), and a query attends in
blocks of ``QUERY_BLOCK`` rows against the keys up to the block's end (full
``[heads, T, T]`` scores are 17 GB at 8,192 positions). It reads sizes from
the configuration FILE's keys and weights from the program's parameter tree
(``layers.scmoe``: the two sublayers' leaves stacked ``[L, 2, ...]``, stored
``[in, out]``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 1024


def _mm(x, w, at=()):
    """``x @ w[at]``, the matrix cut out of its stacked leaf ``w`` (as
    stored) and converted to float32 only once ``x`` has been computed."""
    w, _ = jax.lax.optimization_barrier((w, x))
    return x @ w[at].astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x [T, H, D]; position t rotates pair (2i, 2i + 1) by t * theta^(-2i/D)."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def mla(cfg, h, p, at):
    """One latent attention on ``h`` [T, hidden] (normed); ``p``:
    ``layers.scmoe``, ``at``: ``(layer, sublayer)``."""
    T, d = h.shape
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    q = _mm(_rms_norm(_mm(h, p["wq_a"], at), p["q_norm"][at], eps),
            p["wq_b"], at)
    if cfg["mla_scale_q_lora"]:
        q = q * math.sqrt(d / cfg["q_lora_rank"])
    q = q.reshape(T, H, dn + dr)
    ckr = _mm(h, p["wkv_a"], at)
    c = _rms_norm(ckr[:, :r], p["kv_norm"][at], eps)
    if cfg["mla_scale_kv_lora"]:
        c = c * math.sqrt(d / r)
    kv = _mm(c, p["wkv_b"], at).reshape(T, H, dn + dv)
    kr = _rope(ckr[:, None, r:], cfg["rope_theta"])             # [T, 1, dr]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg["rope_theta"])],
                        axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(kr, (T, H, dr))],
                        axis=-1)
    v = kv[..., dn:]
    out = []
    for lo in range(0, T, QUERY_BLOCK):
        hi = min(T, lo + QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / math.sqrt(dn + dr)
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v[:hi]))
    return _mm(jnp.concatenate(out).reshape(T, H * dv), p["wo"], at)


def ffn(h, gate, up, down, at):
    """SwiGLU over the matrices at ``at`` of three stacked leaves."""
    return _mm(jax.nn.silu(_mm(h, gate, at)) * _mm(h, up, at), down, at)


def moe(cfg, h, p, i):
    """The routed branch of layer ``i`` on ``h`` [T, hidden] (normed): the
    held experts' part of the sum and every identity expert's."""
    real, zero = cfg["router_experts"], cfg["zero_expert_num"]
    first, held = cfg["first_expert"], cfg["n_routed_experts"]
    probs = jax.nn.softmax(h @ p["router"][i].astype(F32), axis=-1)
    _, chosen = jax.lax.top_k(probs + p["router_bias"][i].astype(F32),
                              cfg["moe_topk"])
    w = jnp.take_along_axis(probs, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]

    def weight_of(e):  # [T]: the weight of expert e where it was chosen
        return jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)[:, None]

    m = jax.lax.fori_loop(
        0, held, lambda e, m: m + weight_of(first + e) * ffn(
            h, p["w_gate"], p["w_up"], p["w_down"], (i, e)),
        jnp.zeros_like(h))
    # zero_expert_type "identity": experts real .. real + zero - 1 return
    # their input, so together they add (the sum of their weights) x h
    identity = (chosen >= real) & (chosen < real + zero)
    return m + jnp.sum(jnp.where(identity, w, 0.0), axis=-1)[:, None] * h


def layer(cfg, x, p, i):
    """Double layer ``i``; ``p``: ``layers.scmoe``."""
    eps = cfg["rms_norm_eps"]
    dense = lambda j, h: ffn(h, p["ffn_gate"], p["ffn_up"],  # noqa: E731
                             p["ffn_down"], (i, j))
    a0 = x + mla(cfg, _rms_norm(x, p["attn_norm"][i, 0], eps), p, (i, 0))
    h0 = _rms_norm(a0, p["mlp_norm"][i, 0], eps)
    m = moe(cfg, h0, p, i)
    b0 = a0 + dense(0, h0)
    a1 = b0 + mla(cfg, _rms_norm(b0, p["attn_norm"][i, 1], eps), p, (i, 1))
    b1 = a1 + dense(1, _rms_norm(a1, p["mlp_norm"][i, 1], eps))
    return b1 + m


def logits_one(cfg, params, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    if cfg["zero_expert_type"] != "identity" or cfg["attention_method"] != "MLA":
        raise ValueError("this reference knows identity experts and MLA")
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][tokens].astype(F32)
        x = jax.lax.fori_loop(
            0, cfg["num_layers"],
            lambda i, x: layer(cfg, x, params["layers"]["scmoe"], i), x)
        x = _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        head = (params["embedding"].T if cfg["tie_word_embeddings"]
                else params["lm_head"])
        return _mm(x, head)
