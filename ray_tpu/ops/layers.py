"""Elementwise / normalization / positional ops.

These are deliberately plain jnp: XLA fuses them into surrounding matmuls on
TPU (HBM-bandwidth-optimal), so Pallas here would be counterproductive.
fp32 accumulation where it matters (norm statistics, rope trig).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps: float = 1e-5):
    """RMSNorm with fp32 statistics (llama-family norm)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * rms) * weight.astype(jnp.float32)).astype(dtype)


def rotary_embedding(q, k, positions, theta: float = 500000.0,
                     interleaved: bool = False, inv_freq=None):
    """Apply RoPE to q,k of shape [B, T, H, D]; positions [B, T] or [T].

    theta=500000 is the Llama-3 base frequency. Frequency ``i`` turns the
    pair ``(i, i + D/2)`` (the half-split "rotate_half" convention), or,
    ``interleaved``, the pair ``(2i, 2i + 1)``; the result keeps the
    layout it was given. ``inv_freq`` ``[D / 2]``: the frequencies
    themselves where they are not ``theta``'s powers (a scaled rotation's
    blend, YaRN's).
    """
    dtype = q.dtype
    D = q.shape[-1]
    if positions.ndim == 1:
        positions = positions[None, :]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32)
                                    / D))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,T,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]

    def rot(x):
        x32 = x.astype(jnp.float32)
        if interleaved:
            x1, x2 = x32[..., 0::2], x32[..., 1::2]
            return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                             axis=-1).reshape(x.shape).astype(dtype)
        x1, x2 = jnp.split(x32, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1).astype(dtype)

    return rot(q), rot(k)


def swiglu(x, w_gate, w_up, w_down, compute_dtype=jnp.bfloat16):
    """SwiGLU MLP: down( silu(x@gate) * (x@up) ). Matmuls in bf16 for MXU."""
    xc = x.astype(compute_dtype)
    g = jax.nn.silu(xc @ w_gate.astype(compute_dtype))
    u = xc @ w_up.astype(compute_dtype)
    return ((g * u) @ w_down.astype(compute_dtype)).astype(x.dtype)


def layer_norm(x, weight, eps: float = 1e-5):
    """LayerNorm without a bias, fp32 statistics: ``(x - mean(x)) /
    sqrt(var(x) + eps) * weight`` over the last dimension (the variance is
    the mean of the squared DEVIATIONS; :func:`rms_norm` subtracts no
    mean)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * scale) * weight.astype(jnp.float32)).astype(dtype)


def rotate_pairs(x, positions, theta: float):
    """RoPE of the pairs ``(2i, 2i + 1)`` of ``x`` [B, T, H, D] (what
    :func:`rotary_embedding` computes with ``interleaved``) with no strided
    slice and no stack: a pair's partner comes by ONE product with the
    signed permutation ``[D, D]`` (exact in any float type: every output is
    one input or its negative), ``out[2i] = x[2i] cos_i - x[2i+1] sin_i``,
    ``out[2i+1] = x[2i+1] cos_i + x[2i] sin_i``, then one elementwise pass.
    At 128 heads and 16,384 positions the sliced form leaves a float32 copy
    of ``x`` (1.07 GB), its two halves and the stacked result in a prefill
    program's memory, a layer; a rotation of the lanes (``jnp.roll``) leaves
    five such copies (compiled for a described v5e, PR 54)."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angles = jnp.repeat(positions[..., None].astype(jnp.float32) * inv_freq,
                        2, axis=-1)[:, :, None, :]  # [B,T,1,D]: i, i, ...
    at = jnp.arange(D)
    # column 2i takes -x[2i + 1], column 2i + 1 takes x[2i]
    swap = (jnp.where(at % 2 == 0, -1.0, 1.0)[None, :]
            * (at[:, None] == (at ^ 1)[None, :])).astype(x.dtype)
    partner = jnp.dot(x, swap, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * jnp.cos(angles)
            + partner * jnp.sin(angles)).astype(x.dtype)
