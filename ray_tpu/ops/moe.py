"""Mixture-of-Experts feed-forward layers, TPU-native. Two paths:

- :func:`routed_mlp` is the MEASURED one (``models/llama.py _mlp_half``,
  the trainer's step, the cell ``train-olmoe-1chip``): dropless top-k. The
  ``tokens x k`` assignments are sorted by expert, their rows gathered, the
  three expert products run as grouped products over the sorted rows
  (``jax.lax.ragged_dot``), rows go back to token order. Memory grows with
  ``tokens x k``; every choice is computed whatever the imbalance; every
  shape is static; every index map is a gather in both directions (no
  scatter, forward or backward).
- :func:`moe_ffn` / :func:`top_k_routing` are the older GShard/Switch
  *dense dispatch*: one-hot ``[G, S, E, C]`` dispatch/combine tensors with a
  static capacity that DROPS tokens and always renormalises the gates. It
  is the only path with a live ``expert`` mesh axis (GSPMD all-to-all,
  ``models/moe_llama.py``) and runs in no benchmark cell; it goes when the
  four-chip expert-parallel cell is built on ``routed_mlp`` (ROADMAP
  Design 1 and 2).
"""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp


def top_k_routing(gate_logits, num_experts: int, top_k: int,
                  capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compute dispatch/combine tensors.

    gate_logits: [G, S, E] router scores (G = groups, S = tokens/group).
    Returns (dispatch [G,S,E,C] bool-ish float, combine [G,S,E,C] float,
    aux_loss scalar). Tokens beyond an expert's capacity C are dropped
    (their combine weight is 0 → they pass through the residual only).
    """
    G, S, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    masks = []          # [G,S,E] one-hot per choice (after capacity)
    gate_vals = []      # [G,S] gate prob per choice
    positions = []      # [G,S] slot index within the chosen expert
    remaining = probs
    # tokens claim expert slots choice-major, then in token order: choice 0
    # of every token outranks choice 1 of any token (t5x/flax convention)
    counts = jnp.zeros((G, 1, E), jnp.float32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=jnp.float32)          # [G,S,E]
        gate_vals.append(jnp.sum(remaining * m, axis=-1))      # [G,S]
        remaining = remaining * (1.0 - m)
        pos_e = jnp.cumsum(m, axis=1) - m + counts             # [G,S,E]
        counts = counts + jnp.sum(m, axis=1, keepdims=True)
        within = (pos_e < capacity).astype(jnp.float32) * m
        masks.append(within)
        positions.append(jnp.sum(pos_e * within, axis=-1))     # [G,S]

    # renormalize surviving gate weights to sum to 1 per token (Mixtral)
    kept = [jnp.sum(m, axis=-1) for m in masks]                # [G,S] 0/1
    denom = sum(g * k for g, k in zip(gate_vals, kept)) + 1e-9
    dispatch = jnp.zeros((G, S, E, capacity), jnp.float32)
    combine = jnp.zeros((G, S, E, capacity), jnp.float32)
    for m, g, p in zip(masks, gate_vals, positions):
        slot = jax.nn.one_hot(p.astype(jnp.int32), capacity,
                              dtype=jnp.float32)               # [G,S,C]
        d = m[..., None] * slot[:, :, None, :]                 # [G,S,E,C]
        dispatch = dispatch + d
        combine = combine + d * (g / denom)[:, :, None, None]

    # Switch aux loss: E * sum_e fraction_tokens_e * mean_prob_e
    # (fractions from choice-0 assignment, pre-capacity)
    first = jax.nn.one_hot(jnp.argmax(probs, -1), E, dtype=jnp.float32)
    frac = jnp.mean(first, axis=(0, 1))
    mean_p = jnp.mean(probs, axis=(0, 1))
    aux = num_experts * jnp.sum(frac * mean_p)
    return dispatch, combine, aux


def expert_capacity(tokens_per_group: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    c = int(math.ceil(top_k * tokens_per_group / num_experts
                      * capacity_factor))
    return max(c, 1)


def moe_ffn(x, router_w, w_gate, w_up, w_down, *, top_k: int = 2,
            capacity_factor: float = 1.25, compute_dtype=jnp.bfloat16,
            mesh=None, rules=None):
    """MoE SwiGLU FFN.  x: [B, S, d].

    router_w: [d, E];  w_gate/w_up: [E, d, f];  w_down: [E, f, d].
    Returns (y [B, S, d] in x.dtype, aux_loss scalar fp32).

    Sharding: expert weights carry logical axis ``expert`` (mesh axis
    ``expert``); the dispatched activations [E, B, C, d] get an explicit
    constraint on E so the dispatch einsum becomes an all-to-all over ICI.
    """
    B, S, d = x.shape
    E = router_w.shape[-1]
    C = expert_capacity(S, E, top_k, capacity_factor)
    cd = compute_dtype

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    dispatch, combine, aux = top_k_routing(logits, E, top_k, C)

    ex_in = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(cd), x.astype(cd))
    if mesh is not None and "expert" in mesh.axis_names:
        from ray_tpu.parallel.sharding import constraint

        ex_in = constraint(ex_in, ("expert", "batch", None, None),
                           mesh, rules)
    g = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", ex_in, w_gate.astype(cd)))
    u = jnp.einsum("ebcd,edf->ebcf", ex_in, w_up.astype(cd))
    ex_out = jnp.einsum("ebcf,efd->ebcd", g * u, w_down.astype(cd))
    if mesh is not None and "expert" in mesh.axis_names:
        from ray_tpu.parallel.sharding import constraint

        ex_out = constraint(ex_out, ("expert", "batch", None, None),
                            mesh, rules)
    y = jnp.einsum("bsec,ebcd->bsd", combine.astype(cd), ex_out)
    return y.astype(x.dtype), aux


# --------------------------------------------------------------------------- #
# Dropless routed SwiGLU (assignments sorted by expert, grouped products)
# --------------------------------------------------------------------------- #

_GROUPS_BY_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _float0(x):
    return np.zeros(x.shape, jax.dtypes.float0)


@jax.custom_vjp
def _grouped_dot(x, w, group_sizes):
    """``x`` [A, k] (rows sorted by group, compute type) times the group's
    own ``w[g]`` of ``w`` [G, k, n] (as stored): [A, n] float32. Products
    in ``x``'s type, accumulated in float32, forward and backward: the
    backward's cotangent is brought to ``x``'s type first, and a group's
    weight gradient is accumulated over its rows in float32. On the TPU
    ``ragged_dot`` is XLA's own grouped-matmul kernel (512-row tiles; a
    tile that straddles a group boundary is visited once a group), not a
    product over every group."""
    return jax.lax.ragged_dot(x, w.astype(x.dtype), group_sizes,
                              preferred_element_type=jnp.float32)


def _grouped_dot_fwd(x, w, group_sizes):
    return _grouped_dot(x, w, group_sizes), (x, w, group_sizes)


def _grouped_dot_bwd(res, ct):
    x, w, group_sizes = res
    ct = ct.astype(x.dtype)
    # the weights transposed in memory: contracting their last dimension
    # in place compiles, on the TPU, to a product over EVERY group
    dx = jax.lax.ragged_dot(ct, jnp.swapaxes(w.astype(x.dtype), 1, 2),
                            group_sizes, preferred_element_type=jnp.float32)
    dw = jax.lax.ragged_dot_general(
        x, ct, group_sizes, _GROUPS_BY_ROWS,
        preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype), _float0(group_sizes)


_grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


@jax.custom_vjp
def _dispatch(h, order, inverse):
    """``h`` [N, d] -> the assignments' rows in sorted order [A, d]:
    ``order[i]`` is the assignment (token ``order[i] // k``) in sorted
    place ``i``, ``inverse[a]`` the sorted place of assignment ``a``. Both
    directions are gathers."""
    return h[order // (order.shape[0] // h.shape[0])]


def _dispatch_fwd(h, order, inverse):
    return _dispatch(h, order, inverse), (h.shape[0], order, inverse)


def _dispatch_bwd(res, ct):
    n, order, inverse = res
    with jax.named_scope("moe.dispatch"):
        dh = ct[inverse.reshape(n, -1)].astype(jnp.float32).sum(1)
    return dh.astype(ct.dtype), _float0(order), _float0(inverse)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, order, inverse):
    """``y[n] = sum_j w[n, j] * ys[inverse[n * k + j]]`` in float32."""
    return jnp.einsum("nk,nkd->nd", w, ys[inverse.reshape(w.shape)],
                      preferred_element_type=jnp.float32)


def _combine_fwd(ys, w, order, inverse):
    return _combine(ys, w, order, inverse), (ys, w, order, inverse)


def _combine_bwd(res, ct):
    ys, w, order, inverse = res
    with jax.named_scope("moe.combine"):
        d_ys = (w.reshape(-1)[order][:, None]
                * ct[order // w.shape[1]]).astype(ys.dtype)
        d_w = jnp.einsum("nkd,nd->nk", ys[inverse.reshape(w.shape)], ct,
                         preferred_element_type=jnp.float32)
    return d_ys, d_w.astype(w.dtype), _float0(order), _float0(inverse)


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_mlp(h, router_w, w_gate, w_up, w_down, *, top_k: int,
               norm_topk_prob: bool = False,
               stat_axes: Sequence[str] = ()
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Dropless top-k routed SwiGLU. ``h`` [..., d] in the compute type.

    ``router_w`` [d, E]; ``w_gate`` / ``w_up`` [E, d, f]; ``w_down``
    [E, f, d], as stored. Router product and softmax in float32; the
    ``top_k`` weights stay as the softmax gave them unless
    ``norm_topk_prob``; the expert products in ``h``'s type with float32
    accumulation. Returns ``(y [..., d] float32, stats)``; ``stats`` are
    float32 scalars:

    - ``lb_loss``: ``E * sum_e fraction_e * mean_prob_e``, ``fraction_e``
      the assignments expert ``e`` got over the TOKENS (the fractions sum
      to ``top_k``), no gradient through it;
    - ``z_loss``: ``mean(logsumexp(logits) ** 2)``;
    - ``max_load_ratio``: the heaviest expert's assignments over the mean;
    - ``dropped``: assignments that no group holds (0 by construction).

    ``stat_axes``: inside a ``shard_map`` whose axes split the tokens, the
    axis names to average ``fraction_e`` over, so that ``lb_loss``, averaged
    over those axes by the caller, is the loss of the whole batch and its
    gradient the whole batch's (the fractions carry no gradient, so no
    collective is differentiated)."""
    cd = h.dtype
    lead, d = h.shape[:-1], h.shape[-1]
    E, K = router_w.shape[-1], top_k
    hf = h.reshape(-1, d)
    N = hf.shape[0]
    A = N * K
    with jax.named_scope("moe.route"):
        logits = jnp.dot(hf.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, K)
        if norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    with jax.named_scope("moe.dispatch"):
        ids = jnp.arange(A, dtype=jnp.int32)
        sorted_e, order = jax.lax.sort_key_val(
            top_e.reshape(A).astype(jnp.int32), ids)  # stable
        _, inverse = jax.lax.sort_key_val(order, ids)
        starts = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=jnp.int32))
        counts = jnp.diff(starts.astype(jnp.int32), append=jnp.int32(A))
        xs = _dispatch(hf, order, inverse)
    with jax.named_scope("moe.experts"):
        g = _grouped_dot(xs, w_gate, counts).astype(cd)
        u = _grouped_dot(xs, w_up, counts).astype(cd)
        a = (jax.nn.silu(g.astype(jnp.float32)) * u).astype(cd)
        ys = _grouped_dot(a, w_down, counts).astype(cd)
    with jax.named_scope("moe.combine"):
        y = _combine(ys, top_w, order, inverse)
    with jax.named_scope("moe.route"):
        fraction = jax.lax.stop_gradient(counts.astype(jnp.float32) / N)
        for ax in stat_axes:
            fraction = jax.lax.pmean(fraction, ax)
        stats = {
            "lb_loss": E * jnp.sum(fraction * jnp.mean(probs, axis=0)),
            "z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "max_load_ratio": jnp.max(fraction) * (E / K),
            "dropped": (A - jnp.sum(counts)).astype(jnp.float32),
        }
    return y.reshape(*lead, d), stats
