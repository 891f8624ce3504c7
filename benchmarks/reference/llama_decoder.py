"""The plain reference of the Llama-shaped decoder that Mistral-7B-v0.3 and
InternLM2-1.8B share: pre-norm blocks of grouped-query attention with
rotary embeddings (half-split "rotate_half" convention, as both models'
published code) and a SwiGLU feed-forward, RMSNorm, an untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (a TPU would otherwise run a
float32 matmul in bfloat16 passes): no kernel, no cache, no batching tricks,
full [T, T] scores. It reads sizes from the configuration FILE's keys, not
from the program's config object.

Departures from the published code, both of layout and not of mathematics:
the weights are read from the program's parameter tree (``embedding``,
``layers.{wq,wk,wv,wo,w_gate,w_up,w_down,attn_norm,mlp_norm}`` stacked over
layers and stored [in, out], ``final_norm``, ``lm_head``), and InternLM2's
packed ``wqkv`` is taken as the three matrices it packs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, D]; position t rotates pair (i, i + D/2) by t * theta^(-2i/D)."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(cfg, x, p):
    T = x.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms_norm(x, p["attn_norm"], eps)
    q = _rope((h @ p["wq"]).reshape(T, nq, hd), theta)
    k = _rope((h @ p["wk"]).reshape(T, nkv, hd), theta)
    v = (h @ p["wv"]).reshape(T, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=1)   # query head h reads kv head h // rep
    v = jnp.repeat(v, nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(T, nq * hd) @ p["wo"]
    h = _rms_norm(x, p["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def logits_one(cfg, params, tokens):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        x = params["embedding"].astype(F32)[tokens]

        def body(x, p):
            return _block(cfg, x, jax.tree.map(lambda a: a.astype(F32), p)), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = _rms_norm(x, params["final_norm"].astype(F32), cfg["rms_norm_eps"])
        head = (params["embedding"].T if cfg["tie_word_embeddings"]
                else params["lm_head"])
        return x @ head.astype(F32)


def loss(cfg, params, tokens):
    """tokens [B, T + 1] -> mean next-token cross-entropy, one sequence at a
    time so that only one [T, vocab] block of logits is alive."""

    def one(row):
        lg = logits_one(cfg, params, row[:-1])
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1)[:, 0].sum()

    total = jax.lax.map(one, tokens).sum()
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
