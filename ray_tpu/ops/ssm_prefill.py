"""Pallas TPU Mamba-2 mixer core for the decode engine's prefill: forward
only, the causal convolution, ``silu``, the split, ``softplus(dt + dt_bias)``,
the chunked state-space scan and ``+ D x`` in ONE call, the state in VMEM.

What ``ops/ssm.py scan_positions`` computes in XLA (``causal_conv`` over
float32 ``[T, width]`` rows, ``ssd_scan``: three float32 ``[chunks, heads,
chunk, chunk]`` tensors through HBM, a ``lax.scan`` over the chunks and a
second pass for what the state answers, a stretch of ``SEGMENT`` positions at
a time) computed inside one kernel, with that form's arithmetic and its
rounding points: the convolution, ``silu``, ``dt``, the cumulative
log-decays, the decay masks (masked BEFORE the exponential) and the carried
state in float32; the convolved rows rounded to the operands' type where
``_piece`` rounds them; products in that type with float32 accumulation; the
state rounded to it only where a chunk's product reads it. ``ops/ssm.py``'s
docstring has the algebra; that module is this one's oracle.

- The rows are read as the in-projection wrote them, ``xbc`` ``[B, T,
  d_inner + 2 N]`` (ONE group: ``B`` and ``C`` are read once a row tile and
  shared by every head, ``C B^T`` is one product a chunk), never copied,
  padded, repeated or transposed in HBM; ``y`` leaves as ``[B, T, d_inner]``
  float32, the layout the gated norm reads.
- Grid ``(sequence, row tile)``, the row tile ONE chunk of the recurrence and
  the sequential axis: the ``[H, P, N]`` float32 state and the convolution's
  last raw rows stay in VMEM scratch across it, set from the start state and
  tail at tile 0, the state written at the last tile. The prompt is ONE piece
  whatever its length.
- In a tile, first what every head shares: ``dt = softplus(dt + dt_bias)``
  (0 behind ``last``: identity updates), its log-decays summed from the
  chunk's start, and both turned so that a head's positions lie along a row
  (sums and transposes of float32 as products of its three bfloat16 parts
  with ones: EXACT under the float32 sum, :func:`_rows_of`); ``B`` and ``C``
  through the convolution; ``C B^T``. Then a loop over the heads, eight a
  step (a sublane tile of those rows), inside it a 128-lane column block at a
  time (two heads of 64 side by side, or a head of 128): the block's
  convolution and ``silu`` (a v5e's VPU has no bfloat16 arithmetic: what the
  kernel saves is passes over HBM), ``dt x``, a head's decay mask times ``C
  B^T`` against ``dt x`` (two heads of a block: each against the block with
  the other's lanes zeroed, so that the two products add up in place), what
  the state before the chunk answers (``C S^T``), ``D x``, and the block's
  state carried on (``B^T`` against the decayed ``dt x``).
- The kernel's text is one group's: it is traced in every serving process's
  set-up, a prefill program a page count.

Operations and bytes, for the roofline this kernel has not got yet (``Q`` the
chunk, ``N`` the state's width, ``d`` = ``d_inner``). What the recurrence
NEEDS a chunk: ``2 Q^2 N`` (``C B^T``), ``2 Q^2 d`` (the masked scores
against ``dt x``), ``4 Q N d`` (``C S^T`` and the state's update): 1.09
GFLOP at 256 / 128 / 4,096, 70 GFLOP a layer at 16,384 positions (0.35 ms at
a v5e's peak; the kernel's products of two heads a block do the masked one
twice). Bytes: a position's ``d + 2 N`` compute-type columns read once, ``d``
float32 columns written once, 4 bytes of ``dt`` a head: 0.42 GB there (0.5
ms at 819 GB/s). Neither bounds it: the VPU does. The call takes 1.75 ms
there, 27 us a chunk, and 0.88 ms at 8,192 (my chip runs, PR 60; XLA's
chunks took 7.8 and 4.1). Of those 0.88 ms, by what the kernel alone loses
without each part: the state's two products and their roundings 0.26, a
head's three per-position numbers spread over its lanes (``wide``) 0.23, the
masked products 0.19 (the masks alone 0.15, their exponentials 0.01: the EUP
is free), ``silu`` 0.09, the convolution's shifted rows 0.02; the parts
overlap (all of them together 0.57), and a third is left when they go (the
rows turned into columns, 64 transposes a chunk; the stores of ``y``; the
pipeline). Skipping a mask's block above the diagonal gave 2%: not kept.

No backward kernel: :func:`ssm_prefill` gives ``jax.grad`` the XLA form's
transpose (``ops/ssm.py _piece``). ``models/llama.py attend_ssm`` imports
this module inside the call and nothing else does: a train process never
imports it.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32, BF16 = jnp.float32, jnp.bfloat16
_LANE = 128
_HALO = 8   # rows before a tile's own in the convolution's staging rows
_GROUP = 8  # heads a step of the kernel's loop: a sublane tile of their rows
_SPARE_VMEM = 16 * 2 ** 20  # room for a block's values beside the buffers

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NN = (((1,), (0,)), ((), ()))  # a @ b


def pick_rows(t: int, chunk: int) -> Optional[int]:
    """Rows a grid step, or None: ONE chunk of the recurrence (128 or 256
    positions: a head's positions lie along whole lane tiles) where it
    divides ``t`` positions. 256 divides every page count of the engine's."""
    return chunk if chunk in (128, 256) and t % chunk == 0 else None


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


def _rows_of(v, m):
    """``v.T @ m`` to float32 precision: ``v`` [Q, H] float32, ``m`` [Q, Q]
    of zeros and ones (bfloat16). ``v`` goes as its three bfloat16 parts,
    which hold every bit of it, side by side: their products with ones are
    EXACT under a float32 sum, in one pass of the MXU each."""
    H, parts = v.shape[1], []
    for _ in range(3):
        parts.append(v.astype(BF16))
        v = v - parts[-1].astype(F32)
    out = _dot(jnp.concatenate(parts, axis=1), m, _TN)       # [3 H, Q]
    return out[:H] + out[H:2 * H] + out[2 * H:]


def _kernel(last_ref, x_ref, dt_ref, w_ref, b_ref, hb_ref, d_ref, s0_ref,
            tail_ref, y_ref, s_ref,
            halo_ref, stage_ref, st_ref, bc_ref, sc_ref, state_ref,
            *, taps, heads, head_dim, n):
    """Blocks: x (1, Q, width) of ``xbc``; dt (1, Q, H); the convolution's w
    (taps, width) and b (1, width); hb (2, H): ``dt_bias`` and ``a``; d (1,
    d_inner): ``D`` a lane; s0 (1, d_inner / 128, 128, N); the start tail's
    last ``_HALO`` rows (1, _HALO, width). Out: y (1, Q, d_inner), s as s0.
    Scratch: every column's last ``_HALO`` raw rows, float32; a column
    block's raw rows behind them; the heads' four rows (log-decay from the
    chunk's start, ``dt``, the decay to the chunk's end, the decay from its
    start), positions along a row; ``[B | C]`` in the operands' type; ``C
    B^T``; the states, a 128-lane block of ``d_inner`` each."""
    t = pl.program_id(1)
    Q, cd = x_ref.shape[1], x_ref.dtype
    H, P, d_inner = heads, head_dim, heads * head_dim
    per, span = max(1, _LANE // P), max(1, P // _LANE)
    blocks = _GROUP * P // _LANE   # lane blocks a group of heads

    @pl.when(t == 0)
    def _():
        state_ref[...] = s0_ref[0]
        halo_ref[...] = tail_ref[0].astype(F32)

    def mixed(at):
        """silu(causal convolution + bias) of the tile's rows at the 128
        columns ``at``, float32; the columns' last rows kept for the next
        tile."""
        stage_ref[0:_HALO, :] = halo_ref[:, at]
        stage_ref[_HALO:, :] = x_ref[0, :, at].astype(F32)
        halo_ref[:, at] = stage_ref[Q:Q + _HALO, :]
        w = w_ref[:, at]
        y = sum(stage_ref[pl.ds(_HALO - (taps - 1) + j, Q), :] * w[j:j + 1]
                for j in range(taps)) + b_ref[:, at]
        return y * jax.nn.sigmoid(y)

    # --- what every head shares ------------------------------------------- #
    at_row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    at_col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = at_row >= at_col
    position = t * Q + jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    dt = jax.nn.softplus(dt_ref[0] + hb_ref[0:1, :])
    dt = jnp.where(position <= last_ref[0], dt, 0.0)
    # positions along a row: the log-decay from the chunk's start up to and
    # including each position, and dt itself
    cs = _rows_of(dt * hb_ref[1:2, :],
                  jnp.where(at_row <= at_col, 1.0, 0.0).astype(BF16))
    st_ref[0] = cs
    st_ref[1] = _rows_of(dt, jnp.where(at_row == at_col, 1.0,
                                       0.0).astype(BF16))
    st_ref[2] = jnp.exp(cs[:, Q - 1:Q] - cs)
    st_ref[3] = jnp.exp(cs)
    for i in range(2 * n // _LANE):
        bc_ref[:, i * _LANE:(i + 1) * _LANE] = mixed(
            pl.ds(d_inner + i * _LANE, _LANE)).astype(cd)
    sc_ref[...] = _dot(bc_ref[:, n:], bc_ref[:, :n], _NT)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_LANE, 1), 0)

    # --- the heads, eight a step ------------------------------------------ #
    def group(g, carry):
        mine = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        along = [st_ref[q, mine, :] for q in range(4)]        # [8, Q]
        down = [a.T for a in along]                           # [Q, 8]
        for j in range(blocks):
            k = g * blocks + j
            at = pl.ds(pl.multiple_of(k * _LANE, _LANE), _LANE)
            hs = ([j * per + i for i in range(per)] if span == 1
                  else [j // span])

            def wide(q):
                """A head's column across the head's lanes of the block."""
                cols = [down[q][:, h:h + 1] for h in hs]
                return (cols[0] if len(cols) == 1
                        else jnp.where(lane < P, cols[0], cols[1]))

            x = mixed(at).astype(cd)  # rounded where ``_piece`` rounds
            x32 = x.astype(F32)
            dtx32 = wide(1) * x32
            dtx = dtx32.astype(cd)
            y = None
            for i, h in enumerate(hs):
                # masked BEFORE the exponential (ops/ssm.py)
                decay = jnp.exp(jnp.where(
                    lower, down[0][:, h:h + 1] - along[0][h:h + 1, :],
                    -jnp.inf))
                part = _dot((sc_ref[...] * decay).astype(cd),
                            dtx if len(hs) == 1 else jnp.where(
                                lane // P == i, dtx, jnp.zeros_like(dtx)))
                y = part if y is None else y + part
            y = y + wide(3) * _dot(bc_ref[:, n:], state_ref[k].astype(cd),
                                   _NT)
            y_ref[0, :, at] = y + d_ref[:, at] * x32
            # the chunk's whole decay down the block's rows: the
            # exponential BEHIND the rows' broadcast, or the two broadcasts
            # meet (Mosaic has none along sublanes and lanes at once)
            ends = [jnp.exp(jnp.broadcast_to(down[0][Q - 1:Q, h:h + 1],
                                             (_LANE, 1))) for h in hs]
            end = (ends[0] if len(ends) == 1
                   else jnp.where(row < P, ends[0], ends[1]))
            state_ref[k] = end * state_ref[k] + _dot(
                (wide(2) * dtx32).astype(cd), bc_ref[:, :n], _TN)
        return carry

    jax.lax.fori_loop(0, H // _GROUP, group, 0)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        s_ref[0] = state_ref[...]


# jitted: a program that runs it in several layers, or whose scan traces its
# body more than once, traces and lowers the kernel ONCE (ops/gdn_prefill.py)
@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "chunk",
                                             "interpret"))
def _call(xbc, dt, conv_w, conv_b, dt_bias, a_log, d, state, tail, last, *,
          heads: int, head_dim: int, chunk: int, interpret: bool):
    B, T, width = xbc.shape
    taps, n = conv_w.shape[0], state.shape[3]
    H, P, d_inner = heads, head_dim, heads * head_dim
    rows = pick_rows(T, chunk)
    if (rows is None or width != d_inner + 2 * n or n % _LANE
            or not (P == _LANE // 2 or P % _LANE == 0) or H % _GROUP
            or taps - 1 > _HALO or state.shape != (B, H, P, n)
            or dt.shape != (B, T, H)):
        raise ValueError(f"xbc {xbc.shape}, dt {dt.shape}, {H} heads of {P},"
                         f" state {state.shape}, {taps} taps, chunks of "
                         f"{chunk}")
    cd, nb = xbc.dtype, d_inner // _LANE
    tail = jnp.pad(tail.astype(cd), [(0, 0), (_HALO - (taps - 1), 0), (0, 0)])
    hb = jnp.stack([dt_bias.astype(F32), -jnp.exp(a_log.astype(F32))])

    def whole(*shape):
        return pl.BlockSpec(shape, lambda b, t, last: (0,) * len(shape))

    states = pl.BlockSpec((1, nb, _LANE, n), lambda b, t, last: (b, 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, rows, width), lambda b, t, last: (b, t, 0)),
        pl.BlockSpec((1, rows, H), lambda b, t, last: (b, t, 0)),
        whole(taps, width), whole(1, width), whole(2, H), whole(1, d_inner),
        states,
        pl.BlockSpec((1, _HALO, width), lambda b, t, last: (b, 0, 0))]
    out_specs = [pl.BlockSpec((1, rows, d_inner),
                              lambda b, t, last: (b, t, 0)), states]
    scratch = [((_HALO, width), F32), ((_HALO + rows, _LANE), F32),
               ((4, H, rows), F32), ((rows, 2 * n), cd), ((rows, rows), F32),
               ((nb, _LANE, n), F32)]

    def held(shape, dtype):  # bytes in VMEM: whole (8, 128) tiles
        return math.prod(shape[:-2]) * -(-shape[-2] // 8) * 8 \
            * -(-shape[-1] // _LANE) * _LANE * jnp.dtype(dtype).itemsize

    # every block twice (the pipeline's double buffering; each counted as
    # float32, the widest of them) and the scratch once
    buffers = 2 * sum(held(spec.block_shape, F32)
                      for spec in in_specs + out_specs) \
        + sum(held(*one) for one in scratch)
    y, state = pl.pallas_call(
        functools.partial(_kernel, taps=taps, heads=H, head_dim=P, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, T // rows), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(*one) for one in scratch]),
        out_shape=[jax.ShapeDtypeStruct((B, T, d_inner), F32),
                   jax.ShapeDtypeStruct((B, nb, _LANE, n), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=buffers + _SPARE_VMEM),
        interpret=interpret,
        name="ssm_prefill",
    )(last.reshape(1), xbc, dt.astype(F32), conv_w.astype(F32),
      conv_b.astype(F32)[None], hb, jnp.repeat(d.astype(F32), P)[None],
      state.astype(F32).reshape(B, nb, _LANE, n), tail)
    return y.reshape(B, T, H, P), state.reshape(B, H, P, n)


def ssm_prefill(xbc, dt, p, state, tail, last=None, *, heads: int,
                head_dim: int, chunk: int, interpret: bool = False):
    """The Mamba-2 mixer between its in-projection and its gated norm, over
    ``T`` positions of ``B`` sequences. ``xbc`` [B, T, d_inner + 2 N] and
    ``dt`` [B, T, H] float32 as ``ops/ssm.py project_in`` leaves them; ``p``:
    ``conv_w`` [taps, width], ``conv_b``, ``dt_bias``, ``A_log``, ``D`` of
    ONE layer; ``state`` [B, H, P, N] float32 and ``tail`` [B, taps - 1,
    width] (the rows before the first): where the sequence stands. ``last``
    (a number, traced or not; None: the last position): positions behind it
    are identity updates. ``T`` must be a multiple of ``chunk``
    (:func:`pick_rows`), ONE group, ``H`` a multiple of 8, heads 64 or a
    multiple of 128 wide. Returns ``(y [B, T, H, P] float32, the state after
    ``last`` [B, H, P, N] float32)``: ``ops/ssm.py _piece``'s, whose
    transpose ``jax.grad`` gets (forward only: the kernel keeps nothing for a
    backward pass)."""
    dims = dict(heads=heads, head_dim=head_dim, chunk=chunk)
    names = ("conv_w", "conv_b", "dt_bias", "A_log", "D")

    @jax.custom_vjp
    def run(xbc, dt, weights, state, tail, last):
        return _call(xbc, dt, *weights, state, tail, last,
                     interpret=interpret, **dims)

    def fwd(*operands):
        return run(*operands), operands

    def bwd(operands, cotangent):
        from ray_tpu.ops.ssm import _piece

        *most, last = operands

        def xla(xbc, dt, weights, state, tail):
            return _piece(xbc, dt, dict(zip(names, weights)), groups=1,
                          state=state.shape[3], start=state, before=tail,
                          last=last, **dims)

        return (*jax.vjp(xla, *most)[1](cotangent), None)

    run.defvjp(fwd, bwd)
    return run(xbc, dt, tuple(p[w] for w in names), state, tail,
               jnp.asarray(xbc.shape[1] - 1 if last is None else last,
                           jnp.int32))
