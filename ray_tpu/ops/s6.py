"""The Mamba-1 mixer (the selective scan S6, arXiv:2312.00752), plain
``jax.numpy`` / ``lax``: what ``models/llama.py memory_block`` runs in its
``"m"`` layers (the decoder-hybrid-decoder family of arXiv:2507.06607).

    [u | z] = a W_in                              # widths D | D, D = expand x dim
    u = silu(conv1d_causal_depthwise(u) + b)      # kernel K, over time
    [r | B | C] = u W_x                           # widths dt_rank | N | N
    dt = softplus(r W_dt + dt_bias)               # [D]
    A = -exp(A_log)                               # [D, N]: a decay a channel AND a state
    S_t = exp(dt_t A) * S_(t-1) + (dt_t u_t) (x) B_t     # S [D, N], float32
    Y_t = S_t C_t + D * u_t
    out = (Y * silu(z)) W_out

NOT ``ops/ssm.py`` (Mamba-2) at other sizes: there ``A`` is one scalar a
head, a chunk of positions is two matrix products and the mixer ends in a
gated norm; here the update is elementwise on ``[D, N]``, nothing in it is a
matrix product, ``dt`` comes through a rank-``dt_rank`` bottleneck and there
is no norm behind the gate. The two share the causal convolution and its
tail (``ops/ssm.py causal_conv`` / ``conv_tail``) and nothing else.

Products run in the compute type with float32 accumulation; ``u`` behind the
convolution, ``dt``, ``B``, ``C``, the decays, the state and ``Y`` are
float32. :func:`scan` walks the positions one by one (a ``lax.scan``): the
oracle of ``ops/s6_prefill.py`` (the served prefill's Pallas call on a TPU
backend: ``models/llama.py attend_s6`` says which path and why), every other
backend's path and the path of what the kernel does not take. :func:`step`
is the decode engine's one token on from a kept state and tail. Device
scopes: ``s6.in_proj``, ``s6.conv``, ``s6.x_proj``, ``s6.scan`` /
``s6.step``, ``s6.out_proj``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.ssm import causal_conv

F32 = jnp.float32


def project_in(a, w_in):
    """``[u | z] = a W_in`` on ``a`` [B, T, dim] (normed, compute type):
    both halves leave in the compute type."""
    with jax.named_scope("s6.in_proj"):
        return jnp.split(a @ w_in.astype(a.dtype), 2, -1)


def convolve(u, p, before=None):
    """``silu(conv(u) + b)`` over the call's positions, float32: ``u`` [B,
    T, D] as :func:`project_in` leaves it, ``before`` [B, K - 1, D] the rows
    in front of the first (None: a sequence's start)."""
    with jax.named_scope("s6.conv"):
        return jax.nn.silu(causal_conv(u.astype(F32), p["conv_w"],
                                       p["conv_b"], before))


def select(u, p, cd):
    """What the scan reads of ``u`` [B, T, D] float32 (behind the
    convolution): ``[r | B | C] = u W_x`` and ``r W_dt``, the products in
    ``cd`` with float32 results. Returns ``(r W_dt [B, T, D], B [B, T, N], C
    [B, T, N])``, float32; ``dt`` is ``softplus`` of the first plus its
    bias, which the scan applies."""
    with jax.named_scope("s6.x_proj"):
        n = p["A_log"].shape[-1]
        rbc = jnp.dot(u.astype(cd), p["w_x"].astype(cd),
                      preferred_element_type=F32)
        r, b_in, c_in = jnp.split(rbc, [rbc.shape[-1] - 2 * n,
                                        rbc.shape[-1] - n], -1)
        r = jnp.dot(r.astype(cd), p["w_dt"].astype(cd),
                    preferred_element_type=F32)
    return r, b_in, c_in


def scan(u, r, b_in, c_in, p, start=None, last=None):
    """The recurrence as it is written, a position a step: ``u`` / ``r`` [B,
    T, D] and ``b_in`` / ``c_in`` [B, T, N] float32 (:func:`select`), ``p``
    ONE layer's ``A_log`` [D, N], ``dt_bias`` and ``D`` [D]; ``start`` [B,
    D, N] float32 (None: zeros). Positions behind ``last`` (a number, traced
    or not; None: the last) are IDENTITY updates, ``dt = 0``. Returns ``(Y
    [B, T, D] float32, the state after ``last`` [B, D, N] float32)``."""
    bsz, T, D = u.shape
    a = -jnp.exp(p["A_log"].astype(F32))
    dt = jax.nn.softplus(r + p["dt_bias"])
    if last is not None:
        dt = jnp.where((jnp.arange(T) <= last)[None, :, None], dt, 0.0)

    def position(state, at):
        dt_t, u_t, b_t, c_t = at
        state = (jnp.exp(dt_t[..., None] * a) * state
                 + (dt_t * u_t)[..., None] * b_t[:, None, :])
        # a sum of products and no dot: float32 as written on every backend
        return state, jnp.sum(state * c_t[:, None, :], -1) + p["D"] * u_t

    state, y = jax.lax.scan(
        position, jnp.zeros((bsz, D, a.shape[-1]), F32) if start is None
        else start.astype(F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (dt, u, b_in, c_in)))
    return jnp.moveaxis(y, 0, 1), state


def step(u, p, state, tail, cd):
    """ONE token on from a kept state and tail: ``u`` [B, 1, D] as
    :func:`project_in` leaves it, ``state`` [B, D, N] and ``tail`` [B, K -
    1, D] float32 (the tail holds the compute type's values: rows of ``u``
    BEFORE the convolution). Returns ``(Y [B, 1, D] float32, state, tail)``,
    both as the next token finds them."""
    rows = jnp.concatenate([tail.astype(F32), u.astype(F32)], axis=1)
    with jax.named_scope("s6.conv"):
        mixed = jax.nn.silu(jnp.sum(rows * p["conv_w"], axis=1,
                                    keepdims=True) + p["conv_b"])
    r, b_in, c_in = select(mixed, p, cd)
    with jax.named_scope("s6.step"):
        y, state = scan(mixed, r, b_in, c_in, p, state)
    return y, state, rows[:, 1:]


def gate_out(y, z, w_out):
    """``(Y * silu(z)) W_out``: ``y`` [B, T, D] float32, ``z`` in the compute
    type, which the result [B, T, dim] has too."""
    with jax.named_scope("s6.out_proj"):
        cd = z.dtype
        return (y * jax.nn.silu(z.astype(F32))).astype(cd) @ w_out.astype(cd)


def init_s6(key, layers: int, dim: int, *, inner: int, state: int, conv: int,
            dt_rank: int, dt, decay, conv_b: float):
    """``layers`` mixers' weights stacked, float32: the products and the
    convolution random normals over the square root of their fan-in, ``D``
    at one; ``dt_bias`` the inverse softplus of a log-uniform step in ``dt``
    (so that ``softplus(r W_dt + dt_bias)`` scatters around it), ``A``
    uniform in ``decay`` (``A_log`` its logarithm; the published start,
    ``A[:, n] = n + 1``, forgets a token in less than one step at these time
    steps), the convolution's bias normal, ``conv_b`` wide."""
    k_in, k_conv, k_b, k_x, k_dtw, k_dt, k_a, k_out = jax.random.split(key, 8)

    def dense(rng, shape, fan_in):
        return jax.random.normal(rng, shape, F32) / math.sqrt(fan_in)

    step_ = jnp.exp(jax.random.uniform(k_dt, (layers, inner), F32)
                    * (math.log(dt[1]) - math.log(dt[0])) + math.log(dt[0]))
    return {
        "w_in": dense(k_in, (layers, dim, 2 * inner), dim),
        "conv_w": dense(k_conv, (layers, conv, inner), conv),
        "conv_b": conv_b * jax.random.normal(k_b, (layers, inner), F32),
        "w_x": dense(k_x, (layers, inner, dt_rank + 2 * state), inner),
        "w_dt": dense(k_dtw, (layers, dt_rank, inner), dt_rank),
        "dt_bias": step_ + jnp.log(-jnp.expm1(-step_)),
        "A_log": jnp.log(jax.random.uniform(
            k_a, (layers, inner, state), F32, *decay)),
        "D": jnp.ones((layers, inner), F32),
        "w_out": dense(k_out, (layers, inner, dim), inner),
    }
