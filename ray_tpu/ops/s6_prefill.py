"""Pallas TPU kernel for the Mamba-1 selective scan (S6, arXiv:2312.00752) of
the decode engine's prefill: forward only, the prompt one piece.

    dt_t = softplus(r_t Wdt + dt_bias)                 # [D], 0 behind ``last``
    S_t  = exp(dt_t A) * S_(t-1) + (dt_t u_t) (x) B_t  # S [D, N] float32
    y_t  = S_t C_t + D u_t

NOT ``ops/ssm_prefill.py`` at other sizes. There (Mamba-2) ``A`` is one
scalar a head and a chunk of positions is two matrix products; here ``A`` is
``[D, N]``, a decay a channel AND a state, so the update is elementwise on
``[channels, N]`` and the MXU has nothing to do. XLA either walks the
positions one by one (``ops/s6.py scan``: 16,000 steps of a few small
operations a layer) or materialises ``[T, D, N]`` float32 (5.4 GB a layer at
16,384 positions, 5,120 channels and 16 states). This kernel keeps ``S`` in
registers over a block of 1,024 channels (ONE vector register a state, the
channels ``[8, 128]``) and walks the positions of a row tile: a position is
``N`` exponentials and some ``6 N`` multiply-adds on whole registers, and
``B_t``, ``C_t`` are SCALARS read from SMEM (a scalar times a register needs
no broadcast through the lanes). EXACT: no ``A`` tied across states, no
truncated decay, float32 throughout.

WHAT IT DOES NOT FUSE, and why: the causal convolution and its ``silu``. The
projection ``[r | B | C] = u Wx`` stands BETWEEN the convolution and the scan
and contracts ALL channels, where the kernel holds a block of them: ``u`` is
XLA's (``ops/ssm.py causal_conv``), as the convolution's tail is. ``softplus``,
the bias, the mask behind ``last``, the decay's exponential, the update, the
read-out and the skip ``D u`` are the kernel's.

Layout: ``u``, ``r Wdt`` and ``y`` are ``[B, T, D]`` float32 seen as ``[B, T,
D / 128, 128]`` (the same bytes), a block ``(1, rows, 8, 128)``: a position's
1,024 channels are one register, found by a LEADING index. Grid ``(sequence,
channel block, row tile)``, the row tile sequential; the state crosses tiles
in VMEM scratch, set from a START state at tile 0 (two calls in sequence
equal one: a chunked prefill can use it later).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANE, _SUB = 128, 8
CHANNELS = _LANE * _SUB  # a channel block: one register a state
_UNROLL = 8              # positions a loop step, written out


def pick_rows(t: int) -> Optional[int]:
    """The positions a row tile holds, or None where 128 does not divide
    ``t``: the largest of 512, 256, 128 that does (three float32 blocks of
    ``rows x 1,024``, each twice: 12 MB at 512)."""
    for rows in (512, 256, 128):
        if t % rows == 0:
            return rows
    return None


def _kernel(last_ref, bc_ref, u_ref, r_ref, a_ref, bias_ref, skip_ref,
            start_ref, y_ref, after_ref, state_ref, *, rows, n_state):
    """bc (1, 1, 1, rows * 2 N) SMEM: a position's B then its C; u, r, y (1,
    rows, 8, 128); a, start, after (.., N, 8, 128); bias, skip (8, 128);
    scratch: the state (N, 8, 128)."""
    tile = pl.program_id(2)

    @pl.when(tile == 0)
    def _():
        state_ref[...] = start_ref[0]

    last = last_ref[0]
    a = [a_ref[n] for n in range(n_state)]
    bias, skip = bias_ref[...], skip_ref[...]

    def position(t, state):
        u = u_ref[0, t]
        dt = jnp.where(tile * rows + t <= last,
                       jax.nn.softplus(r_ref[0, t] + bias), 0.0)
        dtu = dt * u
        y = skip * u
        new = []
        for n in range(n_state):
            s = jnp.exp(dt * a[n]) * state[n] \
                + dtu * bc_ref[0, 0, 0, t * 2 * n_state + n]
            y = y + s * bc_ref[0, 0, 0, (t * 2 + 1) * n_state + n]
            new.append(s)
        y_ref[0, t] = y
        return tuple(new)

    def step(i, state):
        for j in range(_UNROLL):
            state = position(i * _UNROLL + j, state)
        return state

    state = jax.lax.fori_loop(
        0, rows // _UNROLL, step,
        tuple(state_ref[n] for n in range(n_state)))
    for n in range(n_state):
        state_ref[n] = state[n]

    @pl.when(tile == pl.num_programs(2) - 1)
    def _():
        after_ref[0] = state_ref[...]


def s6_prefill(u, r, b_in, c_in, p, start, last, *, rows: Optional[int] = None,
               interpret: bool = False):
    """``u`` [B, T, D] (behind the convolution and ``silu``), ``r`` [B, T, D]
    (``r Wdt``, before its bias and ``softplus``), ``b_in`` / ``c_in`` [B, T,
    N], all float32; ``p``: ONE layer's ``A_log`` [D, N], ``dt_bias`` and
    ``D`` [D]; ``start`` [B, D, N] float32, the state in front of the first
    position; ``last`` an int32 scalar (traced or not): the positions behind
    it are identity updates. ``D`` a multiple of 1,024, ``T`` of ``rows``
    (:func:`pick_rows`). Returns ``(y [B, T, D] float32, the state after
    ``last`` [B, D, N] float32)``; ``y`` behind ``last`` reads the unchanged
    state and means nothing."""
    B, T, D = u.shape
    N = b_in.shape[-1]
    rows = rows or pick_rows(T)
    if rows is None or T % rows or rows % _UNROLL or D % CHANNELS:
        raise ValueError(f"{T} positions in row tiles of {rows}, {D} channels "
                         f"in blocks of {CHANNELS}")
    blocks, tiles = D // CHANNELS, T // rows

    def channels(v):  # [.., D] -> [.., D / 128, 128]: the same bytes
        return v.astype(F32).reshape(*v.shape[:-1], D // _LANE, _LANE)

    def states(v):  # [.., D, N] -> [.., N, D / 128, 128]
        return channels(jnp.swapaxes(v, -1, -2))

    bc = jnp.concatenate([b_in, c_in], axis=-1).astype(F32).reshape(
        B, tiles, 1, rows * 2 * N)
    wide = pl.BlockSpec((1, rows, _SUB, _LANE),
                        lambda b, c, t, last: (b, t, c, 0))
    vector = pl.BlockSpec((_SUB, _LANE), lambda b, c, t, last: (c, 0))
    kept = pl.BlockSpec((1, N, _SUB, _LANE),
                        lambda b, c, t, last: (b, 0, c, 0))
    y, after = pl.pallas_call(
        functools.partial(_kernel, rows=rows, n_state=N),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, blocks, tiles),
            in_specs=[
                pl.BlockSpec((1, 1, 1, rows * 2 * N),
                             lambda b, c, t, last: (b, t, 0, 0),
                             memory_space=pltpu.SMEM),
                wide, wide,
                pl.BlockSpec((N, _SUB, _LANE),
                             lambda b, c, t, last: (0, c, 0)),
                vector, vector, kept],
            out_specs=[wide, kept],
            scratch_shapes=[pltpu.VMEM((N, _SUB, _LANE), F32)]),
        out_shape=[jax.ShapeDtypeStruct((B, T, D // _LANE, _LANE), F32),
                   jax.ShapeDtypeStruct((B, N, D // _LANE, _LANE), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="s6_prefill",
    )(jnp.asarray(last, jnp.int32).reshape(1), bc, channels(u), channels(r),
      states(-jnp.exp(p["A_log"].astype(F32))), channels(p["dt_bias"]),
      channels(p["D"]), states(start))
    return (y.reshape(B, T, D),
            jnp.swapaxes(after.reshape(B, N, D), -1, -2))
