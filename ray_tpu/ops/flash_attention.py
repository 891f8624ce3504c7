"""Pallas TPU flash attention: blockwise causal attention, GQA-aware.

The MXU-friendly replacement for ``plain_attention``'s [B, H, T, T] fp32
score materialization (the round-1 MFU bottleneck). Design:

- forward: grid over (batch, q_head, q_block); K/V for the head group live
  in VMEM once (Pallas skips the re-DMA when the block index is unchanged
  across consecutive grid steps); inner ``fori_loop`` over K/V steps with
  online-softmax (max/sum) carries, so HBM traffic is O(T) not O(T^2).
  Causal skips future blocks entirely via a dynamic loop bound.
- backward: two kernels, dQ (grid over q blocks, loop over past K/V steps)
  and dK/dV (grid over kv blocks, loop over future Q steps), recomputing
  probabilities from the saved logsumexp, flash-attention-2 style. GQA
  head-group reduction for dK/dV happens outside the kernel (one
  reshape-sum).
- GQA: q heads map to kv head ``h // (Hq // Hkv)`` in the BlockSpec index
  map, no ``jnp.repeat`` of K/V through HBM.
- head_dim is zero-padded to a lane multiple (128) when needed; padding
  contributes nothing to scores and is sliced off outputs/grads.

What a loop step does beside its products (PR 53; prefill's kernel,
``ops/flash_prefill.py``, has the same form):

- operands go into the MXU in the type they come in, with float32
  accumulation; ``p`` and ``ds`` are rounded to that type for their second
  product; the running maximum, sum, log-sum-exp, ``delta``, the ``exp`` and
  every accumulator stay float32. The MXU took float32 operands in ONE
  bfloat16 pass before (on float32 inputs the parent's forward read 2.2e-3
  against plain attention at 'highest', XLA's default precision 3.5e-3; with
  the widening casts taken out the results were the same to the bit), so
  the casts bought no digit.
- a mask only on the block the diagonal cuts (:func:`_walk`): the ``blk``
  positions opposite the grid block itself, where the mask is the constant
  ``row >= column``; every other pair of blocks runs no iota, compare or
  select.
- the score's scale on no tile: the operand that stays for the whole grid
  step takes it once (``q`` in the forward and dQ, ``k`` in dK/dV; exact at
  width 256, where it is 1/16, and at width 128 the rounding that the MXU
  gave the parent's float32 ``q * scale``), and ``dq`` / ``dk`` take theirs
  once after the loop.
- the forward's running sum is kept lane by lane and summed across lanes
  once a grid step; its running maximum has to be a row's own each step.
- dK/dV's tile lies keys by queries, so that no product contracts its
  operands' rows (the parent's ``p.T @ do`` and ``ds.T @ q`` transposed a
  whole tile each on the way in: 6.05 -> 4.88 ms at [8, 2048, 32 / 8,
  128]), and lse and delta come to it as rows, which also takes their
  128-lane padding (16 MiB at 8,192 positions) out of VMEM.
- the loop step is read from the sequence (:func:`pick_blocks`, with the
  measured table).

Which paid, alone on a v5e (my chip runs, PR 53; forward / dQ / dK/dV device
ms at [2, 8192, 20 / 20, 256] and at [8, 2048, 32 / 8, 128]): the parent 10.62
/ 12.87 / 18.77 and 3.41 / 3.34 / 6.05; operands as they come, the mask on
the cut block, the scale off the tiles, together 10.22 / 12.61 / 18.10 and
3.09 / 3.25 / 6.12 (no single one of the three moved a kernel by 1%: the
``exp`` and the elementwise passes hide behind the MXU's pushes and pops, the
reductions do not); the sum lane by lane 9.98 and 3.03 (forward); the tile
keys by queries 17.88 and 4.96 (dK/dV); the step of 2,048 at 8,192 positions
9.23 / 12.01 / 17.00. The forward with its softmax taken out altogether runs
8.46 and 2.34: what is left above the products is the row maximum, the
subtraction, the sum's adds and the rounding of ``p``.

A call whose resident blocks and tiles pass the compiler's default scoped
VMEM limit asks for its limit (:func:`_vmem_params`): at 8,192 positions all
three calls at either width.

Reference behavior being replaced: ray.util's delegation of attention math
to torch (reference has no in-repo attention kernel; SURVEY.md §5
long-context row names Pallas flash/splash attention as the TPU design).
"""

from __future__ import annotations

import functools
import logging
import math
import threading
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

logger = logging.getLogger(__name__)

NEG_INF = -1e30
_LANE = 128
_SCOPED_VMEM_DEFAULT = 16 * 2 ** 20  # Mosaic's limit for a kernel that asks none

# the three things flash_attention can run; which one a call got is
# recorded (paths_taken) and logged, never left to be guessed
PATH_PALLAS = "pallas"                      # the Mosaic-compiled kernel
PATH_PALLAS_INTERPRET = "pallas_interpret"  # the kernel, interpreted
PATH_REFERENCE = "reference"                # plain_attention, [B,H,T,T] f32


def pick_blocks(t: int, itemsize: int = 2) -> Optional[Tuple[int, int]]:
    """``(grid block, loop step)`` for ``t`` positions of ``itemsize``-byte
    operands, or None where 64 does not divide the positions. Float32
    operands (the builders' checks; no cell) keep the step at the grid block:
    their resident blocks are twice the bytes, and inside GLM-4.7-Flash's
    whole float32 program XLA refused the dK/dV call the 77 MB of VMEM that
    a step of 2,048 asks for at [1, 8192, 20, 256] (it gives the parent's 71;
    my chip run, PR 53). For the rest: the grid block (queries a grid step
    of the forward and dQ, keys a grid step of dK/dV) is the largest of 512,
    256, 128, 64 that divides ``t``; the loop step (keys, or queries, a ``fori_loop`` step) is
    the largest of 4, 2, 1 grid blocks that divides ``t`` and is no more than
    a quarter of it: 512 x 512 at 2,048 positions, 512 x 1,024 at 4,096 and
    5,120, 512 x 2,048 at 8,192, 512 x 512 at 1,536 and 4,608 (1,024 does
    not divide them). What a loop step costs beside its products is paid a
    ROW of the tile (the running maximum's and sum's reductions, the value
    accumulator's rescaling, the pipeline's fill and drain), so a long step
    wins where a grid block has whole steps to run, and loses at 2,048, where
    most of a block's pairs lie in the pieces beside the diagonal. Measured
    alone on a v5e, device ms of forward / dQ / dK/dV (my chip runs, PR 53;
    the parent's bodies ran 512 x 512):

    =======================  ================  ================  ================  ================
    q [B, T, Hq / Hkv, D]    parent            512 x 512         512 x 1,024       512 x 2,048
    =======================  ================  ================  ================  ================
    [8, 2048, 32 / 8, 128]   3.41 3.33 6.05    3.02 3.25 4.88    3.16 3.20 4.91    3.26 3.27 4.99
    [4, 4096, 16 / 16, 128]  2.75 2.84 4.78    2.53 2.77 3.98    2.48 2.69 3.91    2.46 2.69 3.90
    [2, 8192, 32 / 2, 128]   9.75 10.4 17.0    9.17 10.3 14.4    8.56 9.84 13.8    8.15 9.71 13.6
    [2, 8192, 20 / 20, 256]  10.6 12.9 18.8    10.0 12.6 17.9    9.37 12.2 17.2    9.23 12.0 17.0
    =======================  ================  ================  ================  ================

    A grid block of 1,024 is within 1% of 512 everywhere (better for dK/dV,
    worse for dQ), 256 is 4-17% slower, a step of 4,096 2-4% slower than
    2,048; the head's width does not move the choice. (The table's forward
    added its lane sums 128 lanes at a time; as :func:`_lane_sums` stands,
    four groups at a time first, the forward at 8,192 positions reads 9.38
    and 8.50 where the table has 9.23 and 8.15, the rest as it is.)"""
    for blk in (512, 256, 128, 64):
        if t % blk == 0:
            return blk, max(step for step in (blk, 2 * blk, 4 * blk)
                            if step == blk or (t % step == 0 and 4 * step <= t
                                               and itemsize <= 2))
    return None


# a caller's ``block``: None (the rule's), one number (grid block and loop
# step alike) or the pair
Block = Union[None, int, Tuple[int, int]]


def _as_blocks(block: Block) -> Optional[Tuple[int, int]]:
    if block is None or isinstance(block, tuple):
        return block
    return int(block), int(block)


def _unsupported_reason(q_shape, k_shape, blocks: Optional[Tuple[int, int]]
                        ) -> Optional[str]:
    """Why the kernel cannot take these shapes, or None if it can."""
    _, T, Hq, _ = q_shape
    Tk, Hkv = k_shape[1], k_shape[2]
    if T != Tk:
        return f"q length {T} != k length {Tk} (self-attention only)"
    if blocks is None:
        return (f"sequence length {T} is not a multiple of any block in "
                f"(512, 256, 128, 64)")
    blk, step = blocks
    if T % step or step % blk:
        return (f"sequence length {T} is not a multiple of a loop step of "
                f"{step}, or that of a block of {blk}")
    if Hq % Hkv != 0:
        return f"{Hq} q heads not a multiple of {Hkv} kv heads"
    return None


def attention_path(q_shape, k_shape, block: Block = None,
                   interpret: bool = False) -> Tuple[str, str]:
    """(path, reason) :func:`flash_attention` takes for these shapes in
    this process: the kernel wherever it can run (compiled on a TPU
    backend, interpreted on request), the reference otherwise. A backend
    that cannot be initialised is an error here, not a reason."""
    blocks = _as_blocks(block) or pick_blocks(q_shape[1])
    why_not = _unsupported_reason(q_shape, k_shape, blocks)
    if why_not is not None:
        return PATH_REFERENCE, why_not
    if interpret:
        return PATH_PALLAS_INTERPRET, "interpret=True"
    platform = jax.default_backend()
    if platform == "tpu":
        return PATH_PALLAS, "tpu backend"
    return PATH_REFERENCE, f"backend is {platform!r}, not tpu"


_taken_lock = threading.Lock()
_taken: Dict[tuple, dict] = {}


def _record_path(q_shape, k_shape, dtype, path: str, reason: str,
                 blocks: Optional[Tuple[int, int]]) -> None:
    blocks = None if path == PATH_REFERENCE or blocks is None else list(blocks)
    key = (tuple(q_shape), tuple(k_shape), str(dtype), path,
           tuple(blocks or ()))
    with _taken_lock:
        rec = _taken.get(key)
        if rec is not None:
            rec["calls"] += 1
            return
        _taken[key] = {"q_shape": list(q_shape), "k_shape": list(k_shape),
                       "dtype": str(dtype), "path": path, "reason": reason,
                       "blocks": blocks, "calls": 1}
    logger.info("flash_attention q%s k%s %s -> %s (%s), blocks %s",
                list(q_shape), list(k_shape), dtype, path, reason, blocks)


def paths_taken() -> List[dict]:
    """Every distinct (shapes, dtype, path, blocks) :func:`flash_attention`
    has been traced with in this process, with its reason, the ``[grid
    block, loop step]`` the kernels ran (None on the reference path) and
    its call count: how a run proves which attention it used."""
    with _taken_lock:
        return [dict(rec) for rec in _taken.values()]


# --------------------------------------------------------------------------- #
# The loop of a grid step
# --------------------------------------------------------------------------- #


def _nt(a, b):
    """``a @ b.T``: operands as they come, float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    """``a @ b``, likewise."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scaled(x, scale):
    """``x * scale`` in float32, back in ``x``'s type: the score's scale on
    the operand that stays for the whole grid step, never on a score tile."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _walk(i, blk, step, total, causal, below, visit, carry):
    """The loop of grid step ``i``: ``visit(start, width, cut, carry)`` over
    the positions of the loop's axis that block ``i`` (``blk`` positions of
    the other axis) has pairs with. Not causal: all ``total``, ``step`` at a
    time. Causal: the keys up to the block's queries (``below``) or the
    queries from the block's keys on. Only the ``blk`` positions opposite the
    block itself are ``cut`` by the diagonal (its first row stands at its
    first column); what lies between them and the next whole step goes in
    pieces of ``blk``, the rest ``step`` at a time, neither with a mask."""
    def whole(lo, hi, width, carry):
        return jax.lax.fori_loop(
            lo, hi, lambda j, c: visit(pl.multiple_of(j * width, width),
                                       width, False, c), carry)

    if not causal:
        return whole(0, total // step, step, carry)
    carry = visit(pl.multiple_of(i * blk, blk), blk, True, carry)
    r = step // blk
    if r == 1:
        return (whole(0, i, blk, carry) if below
                else whole(i + 1, total // blk, blk, carry))
    if below:
        steps = jax.lax.div(i, r)  # whole steps under the block
        return whole(steps * r, i, blk, whole(0, steps, step, carry))
    first = jax.lax.div(i + r, r)  # the first whole step past the block
    return whole(first, total // step, step,
                 whole(i + 1, first * r, blk, carry))


def _cut(s, rows_are_queries=True):
    """A square tile on the diagonal with the pairs no query sees at
    NEG_INF: a query's position (the row's, or the column's where the tile
    lies keys by queries) is not before the key's."""
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = row >= col if rows_are_queries else col >= row
    return jax.lax.select(seen, s, jnp.full_like(s, NEG_INF))


def _fold(x, width):
    """[rows, n * width] -> [rows, width]: the column groups added."""
    return functools.reduce(jnp.add, (x[:, c:c + width]
                                      for c in range(0, x.shape[1], width)))


def _lane_sums(x, lanes):
    """[rows, width] -> [rows, lanes]: the tile's column groups added lane by
    lane (four groups of lanes at a time first, which is the same additions
    in fewer equations), which costs the elementwise passes and no sum
    ACROSS lanes: that one is made once, after the loop, on the lanes left.
    ``lanes`` 1: the plain sum."""
    if lanes == 1:
        return jnp.sum(x, axis=-1, keepdims=True)
    if x.shape[1] > 4 * lanes and x.shape[1] % (4 * lanes) == 0:
        x = _fold(x, 4 * lanes)
    return _fold(x, lanes)


# --------------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------------- #

# A visit is a jitted function of the refs and the carry, one equation of a
# kernel's body that Mosaic lowers in place and that is TRACED ONCE a process
# for each (shapes, width, cut): a train step traces a kernel's body at every
# call site, forward, recomputed and transposed (GLM-4.7-Flash's step: 24
# calls, 26 s of trace on the chip's host with the three bodies written
# into each, 22 at the parent; my chip runs, PR 53).


@functools.partial(jax.jit, static_argnames=("width", "cut"))
def _fwd_visit(q, k_ref, v_ref, start, carry, *, width, cut):
    acc, l, m = carry
    at = pl.ds(start, width)
    s = _nt(q, k_ref[0, 0, at, :])  # [blk, width]
    if cut:
        s = _cut(s)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    old = jnp.exp(m - m_new)
    v = v_ref[0, 0, at, :]
    return (acc * old + _nn(p.astype(v.dtype), v),
            l * old + _lane_sums(p, l.shape[1]), m_new)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, blk, step,
                causal):
    """q_ref (1,1,blk,D); k/v_ref (1,1,T,D); o_ref (1,1,blk,D); lse_ref
    (1,1,blk,1). The running sum is kept lane by lane ([blk, 128]) and
    summed across lanes once, after the loop."""
    f32 = jnp.float32
    D, T = q_ref.shape[-1], k_ref.shape[2]
    lanes = _LANE if blk % _LANE == 0 else 1
    q = _scaled(q_ref[0, 0], scale)

    def visit(start, width, cut, carry):
        return _fwd_visit(q, k_ref, v_ref, start, carry, width=width, cut=cut)

    acc, l, m = _walk(pl.program_id(2), blk, step, T, causal, True, visit,
                      (jnp.zeros((blk, D), f32), jnp.zeros((blk, lanes), f32),
                       jnp.full((blk, 1), NEG_INF, f32)))
    l = jnp.maximum(jnp.sum(l, axis=-1, keepdims=True), 1e-30)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _vmem_params(buffers: int, tiles: int, interpret: bool) -> dict:
    """``pallas_call``'s keywords for a call whose block specs keep
    ``buffers`` bytes in VMEM (each block twice: the pipeline's double
    buffering; a [.., 1] float32 column is padded to 128 lanes, a [1, ..]
    row to 8 sublanes) and whose loop step makes ``tiles`` bytes of float32
    [block, step] tiles. A call that fits the compiler's default scoped
    limit is compiled under it; one that does not asks for both and the
    default again (a v5e core has 128 MiB)."""
    if buffers + tiles <= _SCOPED_VMEM_DEFAULT or interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=buffers + tiles + _SCOPED_VMEM_DEFAULT)}


def _fwd(q, k, v, *, causal, blocks, interpret):
    """q [B,Hq,T,D], k/v [B,Hkv,T,D] -> (o [B,Hq,T,D], lse [B,Hq,T,1])."""
    B, Hq, T, D = q.shape
    rep = Hq // k.shape[1]
    blk, step = blocks
    # whole k and v, a block each of q and o, a block of lse; the score
    # tile, its exponentials and their rounded copy
    buffers = 2 * (2 * T * D * k.dtype.itemsize
                   + 2 * blk * D * q.dtype.itemsize + blk * _LANE * 4)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(D), blk=blk,
                          step=step, causal=causal),
        grid=(B, Hq, T // blk),
        **_vmem_params(buffers, 3 * blk * step * 4, interpret),
        in_specs=[
            pl.BlockSpec((1, 1, blk, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // rep, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, T, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------- #
# Backward kernels
# --------------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnames=("width", "cut"))
def _dq_visit(q, do, lse, delta, k_ref, v_ref, start, dq, *, width, cut):
    at = pl.ds(start, width)
    k = k_ref[0, 0, at, :]
    s = _nt(q, k)  # [blk, width]
    if cut:
        s = _cut(s)
    ds = jnp.exp(s - lse) * (_nt(do, v_ref[0, 0, at, :]) - delta)
    return dq + _nn(ds.astype(k.dtype), k)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, blk, step, causal):
    """q/do/dq_ref (1,1,blk,D); k/v_ref (1,1,T,D); lse/delta_ref
    (1,1,blk,1). ``ds`` leaves the loop without the score's scale: ``dq``
    takes it once, after the loop."""
    D, T = q_ref.shape[-1], k_ref.shape[2]
    q, do = _scaled(q_ref[0, 0], scale), do_ref[0, 0]
    lse, delta = lse_ref[0, 0], delta_ref[0, 0]  # [blk, 1]

    def visit(start, width, cut, dq):
        return _dq_visit(q, do, lse, delta, k_ref, v_ref, start, dq,
                         width=width, cut=cut)

    dq = _walk(pl.program_id(2), blk, step, T, causal, True, visit,
               jnp.zeros((blk, D), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("width", "cut"))
def _dkv_visit(k, v, q_ref, do_ref, lse_ref, delta_ref, start, carry, *,
               width, cut):
    dk, dv = carry
    at = pl.ds(start, width)
    q, do = q_ref[0, 0, at, :], do_ref[0, 0, at, :]
    s = _nt(k, q)  # [blk keys, width queries]
    if cut:
        s = _cut(s, rows_are_queries=False)
    p = jnp.exp(s - lse_ref[0, 0, :, at])
    ds = p * (_nt(v, do) - delta_ref[0, 0, :, at])
    return (dk + _nn(ds.astype(q.dtype), q),
            dv + _nn(p.astype(do.dtype), do))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, blk, step, causal):
    """q/do_ref (1,1,T,D); k/v_ref and dk/dv_ref (1,1,blk,D); lse/delta_ref
    (1,1,1,T), ROWS. A tile lies keys by queries, ``k @ q.T``, so that all
    four products contract as the MXU takes them (``p.T @ do`` and ``ds.T @
    q`` of a tile laid queries by keys transpose a whole tile each on the
    way in), and a query's lse and delta are a row's lanes."""
    D, T = q_ref.shape[-1], q_ref.shape[2]
    k, v = _scaled(k_ref[0, 0], scale), v_ref[0, 0]  # [blk, D]

    def visit(start, width, cut, carry):
        return _dkv_visit(k, v, q_ref, do_ref, lse_ref, delta_ref, start,
                          carry, width=width, cut=cut)

    zeros = jnp.zeros((blk, D), jnp.float32)
    dk, dv = _walk(pl.program_id(2), blk, step, T, causal, False, visit,
                   (zeros, zeros))
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _dq_call(q, k, v, do, lse, delta, *, causal, blocks, interpret):
    B, Hq, T, D = q.shape
    rep = Hq // k.shape[1]
    blk, step = blocks
    # whole k and v, a block each of q, do and dq, of lse and delta
    buffers = 2 * (2 * T * D * k.dtype.itemsize
                   + 3 * blk * D * q.dtype.itemsize + 2 * blk * _LANE * 4)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=1.0 / math.sqrt(D), blk=blk,
                          step=step, causal=causal),
        grid=(B, Hq, T // blk),
        **_vmem_params(buffers, 5 * blk * step * 4, interpret),
        in_specs=[
            pl.BlockSpec((1, 1, blk, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, blk, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)


def _dkv_call(q, k, v, do, lse, delta, *, causal, blocks, interpret):
    B, Hq, T, D = q.shape
    rep = Hq // k.shape[1]
    blk, step = blocks
    # a head's whole q and do, its lse and delta as rows (8 sublanes each), a
    # block each of k and v, a float32 block each of dk and dv: 1 KiB a
    # position at D = 128 (8.5 MiB at T = 8192), 2 at 256
    buffers = 2 * (T * (2 * D * q.dtype.itemsize + 2 * 8 * 4)
                   + 2 * blk * D * (k.dtype.itemsize + 4))
    whole = pl.BlockSpec((1, 1, T, D), lambda b, h, j: (b, h, 0, 0))
    block = pl.BlockSpec((1, 1, blk, D), lambda b, h, j: (b, h // rep, j, 0))
    row = pl.BlockSpec((1, 1, 1, T), lambda b, h, j: (b, h, 0, 0))
    out = pl.BlockSpec((1, 1, blk, D), lambda b, h, j: (b, h, j, 0))
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=1.0 / math.sqrt(D), blk=blk,
                          step=step, causal=causal),
        grid=(B, Hq, T // blk),
        **_vmem_params(buffers, 5 * blk * step * 4, interpret),
        in_specs=[whole, block, block, whole, row, row],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, T, D), jnp.float32)] * 2,
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse.reshape(B, Hq, 1, T), delta.reshape(B, Hq, 1, T))


def _bwd(q, k, v, o, lse, do, *, causal, blocks, interpret):
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B,Hq,T,1]
    dq = _dq_call(q, k, v, do, lse, delta, causal=causal, blocks=blocks,
                  interpret=interpret)
    dk_exp, dv_exp = _dkv_call(q, k, v, do, lse, delta, causal=causal,
                               blocks=blocks, interpret=interpret)
    # GQA group-sum: q heads [g*rep, (g+1)*rep) all attend kv head g
    dk = dk_exp.reshape(B, Hkv, rep, T, D).sum(axis=2).astype(k.dtype)
    dv = dv_exp.reshape(B, Hkv, rep, T, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom_vjp wrapper ([B,H,T,D] layout)
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhtd(q, k, v, causal, blocks, interpret):
    o, _ = _fwd(q, k, v, causal=causal, blocks=blocks, interpret=interpret)
    return o


def _flash_fwd_rule(q, k, v, causal, blocks, interpret):
    o, lse = _fwd(q, k, v, causal=causal, blocks=blocks, interpret=interpret)
    # the two residuals only the kernel can give, under the name the model
    # gives its attention products (models/llama.py KEEP_GROUPS): a
    # jax.checkpoint whose policy keeps "attn" then holds them and does not
    # run the forward kernel a second time in the backward; else the
    # identity. The log-sum-exp is named as [B, Hq, T]: as the kernel writes
    # it, [B, Hq, T, 1], the device's tiling fills its last dimension up to
    # 128 lanes, and a kept copy holds 128 times its 4 bytes a row (134 MB
    # a layer at 4 x 16 x 4096, by the compiler's account)
    o = checkpoint_name(o, "attn")
    lse = checkpoint_name(lse[..., 0], "attn")[..., None]
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, blocks, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, causal=causal, blocks=blocks,
                interpret=interpret)


_flash_bhtd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# --------------------------------------------------------------------------- #
# Public API ([B,T,H,D] layout, matching the model)
# --------------------------------------------------------------------------- #


def flash_attention(q, k, v, causal: bool = True,
                    block: Block = None,
                    interpret: bool = False):
    """Blockwise (flash) causal attention. GQA-aware — pass k/v unrepeated.

    q: [B, T, Hq, D]; k, v: [B, T, Hkv, D] with Hq % Hkv == 0.
    Returns [B, T, Hq, D] in q.dtype. Differentiable (custom VJP with
    Pallas backward kernels). Shapes that do not block cleanly, and
    backends other than TPU unless ``interpret`` is set, get the exact jnp
    reference; :func:`attention_path` says which and why beforehand, and
    :func:`paths_taken` afterwards.
    """
    B, T, Hq, D = q.shape
    blocks = _as_blocks(block) or pick_blocks(T, q.dtype.itemsize)
    path, reason = attention_path(q.shape, k.shape, blocks, interpret)
    _record_path(q.shape, k.shape, q.dtype, path, reason, blocks)
    if path == PATH_REFERENCE:
        return _reference(q, k, v, causal)
    # pad head_dim to the 128-lane boundary (zeros don't affect scores)
    Dp = ((D + _LANE - 1) // _LANE) * _LANE
    qt = jnp.swapaxes(q, 1, 2)  # [B,Hq,T,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
        qt, kt, vt = jnp.pad(qt, pad), jnp.pad(kt, pad), jnp.pad(vt, pad)
        # keep softmax scale of the true head_dim
        qt = qt * (math.sqrt(Dp) / math.sqrt(D))
    o = _flash_bhtd(qt, kt, vt, causal, blocks, interpret)
    if Dp != D:
        o = o[..., :D]
    return jnp.swapaxes(o, 1, 2)


def _reference(q, k, v, causal):
    """Exact reference path (materializes scores) for small/odd shapes."""
    Hq, Hkv = q.shape[2], k.shape[2]
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    from ray_tpu.parallel.ring_attention import plain_attention

    return plain_attention(q, k, v, causal=causal)
