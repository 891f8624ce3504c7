"""MoE decoder (Mixtral-shaped): Llama attention + expert-parallel FFN.

Same functional-pytree style as :mod:`ray_tpu.models.llama` — stacked
layers under ``lax.scan``, logical-axis shardings, bf16 compute — with the
dense MLP replaced by :func:`ray_tpu.ops.moe.moe_ffn`. Expert weights carry
the logical ``expert`` axis so a mesh with an ``expert`` dimension runs
expert parallelism (GSPMD all-to-all dispatch); ``tensor`` additionally
shards within each expert. The reference reaches MoE only through
DeepSpeed-MoE (SURVEY.md §2.3); this is the in-framework TPU equivalent.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import (LlamaConfig, _attention, _head,
                                  embed_tokens, head_logits, positions_of)
from ray_tpu.ops.layers import rms_norm, rotary_embedding
from ray_tpu.ops.moe import moe_ffn
from ray_tpu.parallel.sharding import DEFAULT_RULES, logical_sharding


@dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    """The capacity-based GSPMD variant (``ops/moe.moe_ffn``: drops tokens
    over capacity, renormalises the kept gates; ``top_k`` here, not
    ``experts_per_token``). It is NOT the measured path: the benchmark's
    routed model (cell ``train-olmoe-1chip``) is a plain ``LlamaConfig``
    with ``num_experts`` set, through ``models/llama.py`` and
    ``make_spmd_train_step`` (dropless ``ops/moe.routed_mlp``). This class
    and its step stay for the live ``expert`` mesh axis until the
    four-chip expert-parallel cell replaces them (ROADMAP Design 1, 2)."""

    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01

    @staticmethod
    def debug() -> "MoEConfig":
        return MoEConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, mlp_dim=128, max_seq_len=128,
                         remat=False, num_experts=4, top_k=2)

    @staticmethod
    def small(vocab_size: int = 32000) -> "MoEConfig":
        return MoEConfig(vocab_size=vocab_size, dim=768, n_layers=12,
                         n_heads=12, n_kv_heads=4, mlp_dim=1024,
                         max_seq_len=2048, num_experts=8, top_k=2)

    def num_params(self) -> int:
        d, v, L, E = self.dim, self.vocab_size, self.n_layers, self.num_experts
        attn = d * d + 2 * d * (self.n_kv_heads * self.head_dim) + d * d
        moe = d * E + 3 * E * d * self.mlp_dim
        emb = v * d * (1 if self.tie_embeddings else 2)
        return emb + L * (attn + moe + 2 * d) + d


def param_logical_axes(cfg: MoEConfig) -> Dict[str, Any]:
    layer = {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "router": ("layers", "embed", None),
        "w_gate": ("layers", "expert", "embed", "mlp"),
        "w_up": ("layers", "expert", "embed", "mlp"),
        "w_down": ("layers", "expert", "mlp", "embed"),
        "attn_norm": ("layers", None),
        "mlp_norm": ("layers", None),
    }
    out = {"embedding": ("vocab", "embed"), "layers": layer,
           "final_norm": (None,)}
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    return out


def init_params(cfg: MoEConfig, key) -> Dict[str, Any]:
    d, hd = cfg.dim, cfg.head_dim
    nq, nkv, L, E = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers, cfg.num_experts
    k = iter(jax.random.split(key, 16))

    def dense(rng, shape, fan_in):
        return (jax.random.normal(rng, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in)))

    params = {
        "embedding": dense(next(k), (cfg.vocab_size, d), d),
        "layers": {
            "wq": dense(next(k), (L, d, nq * hd), d),
            "wk": dense(next(k), (L, d, nkv * hd), d),
            "wv": dense(next(k), (L, d, nkv * hd), d),
            "wo": dense(next(k), (L, nq * hd, d), nq * hd),
            "router": dense(next(k), (L, d, E), d),
            "w_gate": dense(next(k), (L, E, d, cfg.mlp_dim), d),
            "w_up": dense(next(k), (L, E, d, cfg.mlp_dim), d),
            "w_down": dense(next(k), (L, E, cfg.mlp_dim, d), cfg.mlp_dim),
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "mlp_norm": jnp.ones((L, d), jnp.float32),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), (d, cfg.vocab_size), d)
    return params


def _layer(cfg: MoEConfig, mesh, x, p, positions):
    cd = cfg.dtype
    B, T, d = x.shape
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps).astype(cd)
    q = (h @ p["wq"].astype(cd)).reshape(B, T, cfg.n_heads, cfg.head_dim)
    kk = (h @ p["wk"].astype(cd)).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    vv = (h @ p["wv"].astype(cd)).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    q, kk = rotary_embedding(q, kk, positions, cfg.rope_theta)
    attn = _attention(cfg, q, kk, vv, mesh)
    attn = attn.reshape(B, T, cfg.n_heads * cfg.head_dim)
    x = x + (attn @ p["wo"].astype(cd)).astype(x.dtype)
    h = rms_norm(x, p["mlp_norm"], cfg.norm_eps).astype(cd)
    y, aux = moe_ffn(h, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                     top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                     compute_dtype=cd, mesh=mesh)
    return x + y.astype(x.dtype), aux


def forward_with_aux(cfg: MoEConfig, params, tokens, mesh=None):
    """tokens [B,T] -> (logits [B,T,V] float32, total aux loss)."""
    x = embed_tokens(cfg, params, tokens, mesh)
    positions = positions_of(*tokens.shape)

    layer_fn = partial(_layer, cfg, mesh)
    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn)

    def body(carry, lp):
        x, aux = carry
        x, a = layer_fn(x, lp, positions)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    logits = head_logits(cfg, x, params["final_norm"], _head(cfg, params))
    return logits, aux / cfg.n_layers


def loss_fn(cfg: MoEConfig, params, tokens, mesh=None):
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = forward_with_aux(cfg, params, inputs, mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean() + cfg.aux_loss_coef * aux


def make_train_step(cfg: MoEConfig, mesh, optimizer=None, rules=None):
    """(init_jit, train_step, data_sharding, state_shardings) over the mesh
    — same contract as :func:`ray_tpu.models.llama.make_train_step`."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rules = rules or DEFAULT_RULES
    optimizer = optimizer or optax.adamw(3e-4, b1=0.9, b2=0.95,
                                         weight_decay=0.1)
    axes = param_logical_axes(cfg)
    param_shardings = jax.tree.map(
        lambda ax: logical_sharding(ax, mesh, rules), axes,
        is_leaf=lambda x: isinstance(x, tuple))
    repl = NamedSharding(mesh, P())
    batch_axes = tuple(a for a in ("slice", "data", "fsdp")
                       if a in mesh.axis_names)
    data_sharding = NamedSharding(mesh, P(batch_axes if batch_axes else None))

    def init_state(key):
        params = init_params(cfg, key)
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    from ray_tpu.parallel.sharding import opt_state_shardings

    sample = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    state_shardings = {
        "params": param_shardings,
        "opt_state": opt_state_shardings(
            optimizer, sample["params"], param_shardings, repl),
        "step": repl,
    }
    init_jit = jax.jit(init_state, out_shardings=state_shardings)

    def step_fn(state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, mesh))(state["params"])
        updates, new_opt = optimizer.update(grads, state["opt_state"],
                                            state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return ({"params": new_params, "opt_state": new_opt,
                 "step": state["step"] + 1}, loss)

    train_step = jax.jit(
        step_fn,
        in_shardings=(state_shardings, data_sharding),
        out_shardings=(state_shardings, repl),
        donate_argnums=(0,),
    )
    return init_jit, train_step, data_sharding, state_shardings
