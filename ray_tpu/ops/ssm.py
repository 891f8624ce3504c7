"""The Mamba-2 mixer (state-space duality, arXiv:2405.21060), plain
``jax.numpy`` / ``lax``: the half of a layer that ``models/llama.py
pattern_layer`` runs where the layer pattern says ``M``.

    [z | xBC | dt] = h W_in                       # widths d_inner | d_inner + 2 G N | H
    xBC = silu(conv1d_causal_depthwise(xBC) + b)  # kernel K, over time
    x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)   # head i reads group i // (H / G)
    dt = softplus(dt + dt_bias)      A = -exp(A_log)      # one scalar a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t          # state [H, P, N]
    y_t = S_t C_t + D x_t
    y   = GroupRMSNorm_G(y * silu(z)) * w         # the norm AFTER the gate
    out = y W_out

The recurrence is run in chunks of ``chunk`` positions (:func:`ssd_scan`):
inside a chunk the decay-masked ``C B^T`` scores times ``dt x`` (two
products, as attention over the chunk), across chunks a ``lax.scan`` that
carries the ``[H, P, N]`` states. The backward pass is autodiff through it.
Products run in the compute type with float32 accumulation; ``dt``, the
decays, the carried state and the norm are float32. No kernel HERE: this
scan is ``train-nemotron3nano-1chip``'s step, and the benchmark's
``flash_roofline`` (the train cells' alone) tells Pallas kernels apart by
result type, so one more ``tpu_custom_call`` in a train step would be counted
as ``flash_dq``: a Pallas scan waits for **kernels by name** (ROADMAP Reach
B1(a)). The serving half does not wait: no serving cell reports
``flash_roofline``, and the delta rule's prefill, this scan's sibling, is a
kernel since PR 46 (``ops/gdn_prefill.py``). :func:`causal_conv` stays the
delta rule's XLA path's too (``models/llama.py _delta_chunks``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_conv(x, w, b):
    """Depthwise causal convolution over time. ``x`` [B, T, C]; ``w``
    [K, C] (``w[K - 1]`` multiplies the position itself, ``w[0]`` the one
    ``K - 1`` back); ``b`` [C]. ``K`` shifted adds."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, k:k + T] * w[k] for k in range(K)) + b


def ssd_scan(x, dt, a, b_in, c_in, chunk: int):
    """``y_t = C_t S_t`` of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x)
    B_t``, ``S_0 = 0``, in chunks of ``chunk`` positions.

    ``x`` [B, T, H, P] and ``b_in`` / ``c_in`` [B, T, G, N] in the compute
    type (head ``i`` reads group ``i // (H / G)``); ``dt`` [B, T, H]
    float32, positive; ``a`` [H] float32, negative. Returns ``y``
    [B, T, H, P] float32. A ``T`` that is no multiple of the chunk is
    padded with ``dt = 0`` positions (no decay, no input), a ``T`` shorter
    than the chunk is one chunk of ``T``."""
    cd = x.dtype
    bsz, T, H, P = x.shape
    G, N = b_in.shape[2:]
    R = H // G
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, b_in, c_in = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b_in, c_in))
    nc = (T + pad) // Q
    x = x.reshape(bsz, nc, Q, G, R, P)
    dt = dt.reshape(bsz, nc, Q, G, R)
    b_in = b_in.reshape(bsz, nc, Q, G, N)
    c_in = c_in.reshape(bsz, nc, Q, G, N)
    # log-decay from the chunk's start up to and including each position
    cs = jnp.cumsum(dt * a.reshape(G, R), axis=2)            # [b,c,Q,G,R]
    dtx = dt[..., None] * x.astype(F32)                      # [b,c,Q,G,R,P]

    # inside a chunk: position i reads j <= i through exp(cs_i - cs_j)
    scores = jnp.einsum("bcign,bcjgn->bcgij", c_in, b_in,
                        preferred_element_type=F32)
    cs_t = jnp.moveaxis(cs, 2, -1)                           # [b,c,G,R,Q]
    seg = cs_t[..., :, None] - cs_t[..., None, :]            # [b,c,G,R,i,j]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    # masked BEFORE the exponential: above the diagonal the difference is
    # positive and its exponential may overflow
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp",
                   (scores[:, :, :, None] * decay).astype(cd),
                   dtx.astype(cd), preferred_element_type=F32)

    # what each chunk adds to the state by its end, and the chunk's decay
    to_end = jnp.exp(cs[:, :, -1:] - cs)                     # [b,c,Q,G,R]
    added = jnp.einsum("bcjgn,bcjgrp->bcgrpn", b_in,
                       (to_end[..., None] * dtx).astype(cd),
                       preferred_element_type=F32)
    chunk_decay = jnp.exp(cs[:, :, -1])                      # [b,c,G,R]

    def carry_state(state, chunk_in):
        add_c, decay_c = chunk_in
        return decay_c[..., None, None] * state + add_c, state

    _, before = jax.lax.scan(
        carry_state, jnp.zeros((bsz, G, R, P, N), F32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                      # [b,c,G,R,P,N]
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bcign,bcgrpn->bcigrp", c_in, before.astype(cd),
        preferred_element_type=F32)
    return y.reshape(bsz, T + pad, H, P)[:, :T]


def mamba2_mixer(h, p, *, heads: int, head_dim: int, groups: int,
                 state: int, chunk: int, eps: float):
    """The mixer on ``h`` [B, T, dim] (normed, compute type) with one
    layer's weights ``p``: ``w_in`` [dim, 2 d_inner + 2 G N + H], ``conv_w``
    [K, d_inner + 2 G N], ``conv_b``, ``dt_bias`` / ``A_log`` / ``D`` [H],
    ``gate_norm`` [d_inner], ``w_out`` [d_inner, dim]. Returns [B, T, dim]
    in the compute type, before the residual add."""
    cd = h.dtype
    bsz, T, _ = h.shape
    d_inner, gn = heads * head_dim, groups * state
    with jax.named_scope("ssm.in_proj"):
        # one stored matrix, two products: z and xBC leave in the compute
        # type as every other projection does, the H columns of dt in float32
        w_in = p["w_in"].astype(cd)
        z, xbc = jnp.split(h @ w_in[:, :-heads], [d_inner], -1)
        dt = jnp.dot(h, w_in[:, -heads:], preferred_element_type=F32)
    with jax.named_scope("ssm.conv"):
        xbc = jax.nn.silu(causal_conv(xbc.astype(F32), p["conv_w"],
                                      p["conv_b"])).astype(cd)
        x, b_in, c_in = jnp.split(xbc, [d_inner, d_inner + gn], -1)
        x = x.reshape(bsz, T, heads, head_dim)
    with jax.named_scope("ssm.scan"):
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = ssd_scan(x, dt, -jnp.exp(p["A_log"]),
                     b_in.reshape(bsz, T, groups, state),
                     c_in.reshape(bsz, T, groups, state), chunk)
        y = y + p["D"][:, None] * x.astype(F32)
    with jax.named_scope("ssm.gate_norm"):
        y = y.reshape(bsz, T, groups, d_inner // groups) * jax.nn.silu(
            z.astype(F32)).reshape(bsz, T, groups, d_inner // groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y.reshape(bsz, T, d_inner) * p["gate_norm"]
    with jax.named_scope("ssm.out_proj"):
        return y.astype(cd) @ p["w_out"].astype(cd)


def init_mamba2(key, layers: int, dim: int, *, heads: int, head_dim: int,
                groups: int, state: int, conv: int, dt_min: float,
                dt_max: float, dt_floor: float):
    """``layers`` mixers' weights stacked, float32, initialised as the
    published Mamba-2 code does (a random ``A`` gives no stable
    recurrence): ``A_log = log U[1, 16]``, ``D = 1``, ``dt`` log-uniform in
    ``[dt_min, dt_max]`` floored at ``dt_floor`` and ``dt_bias`` its
    inverse softplus; the products and the conv are random normals over
    the square root of their fan-in, the conv's bias zero."""
    d_inner, gn = heads * head_dim, groups * state
    k_in, k_out, k_conv, k_dt, k_a = jax.random.split(key, 5)

    def dense(rng, shape, fan_in):
        return jax.random.normal(rng, shape, F32) / math.sqrt(fan_in)

    dt = jnp.exp(jax.random.uniform(k_dt, (layers, heads), F32)
                 * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
    dt = jnp.maximum(dt, dt_floor)
    return {
        "norm": jnp.ones((layers, dim), F32),
        "w_in": dense(k_in, (layers, dim, 2 * d_inner + 2 * gn + heads), dim),
        "conv_w": dense(k_conv, (layers, conv, d_inner + 2 * gn), conv),
        "conv_b": jnp.zeros((layers, d_inner + 2 * gn), F32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k_a, (layers, heads), F32,
                                            1.0, 16.0)),
        "D": jnp.ones((layers, heads), F32),
        "gate_norm": jnp.ones((layers, d_inner), F32),
        "w_out": dense(k_out, (layers, d_inner, dim), d_inner),
    }
