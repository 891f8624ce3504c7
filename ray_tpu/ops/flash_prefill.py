"""Pallas TPU flash attention for the decode engine's prefill: forward
only, causal over the call's own positions, with a band.

What ``models/llama.py attend_tiles`` computes in 512 x 512 XLA tiles (float32
scores and accumulators through HBM at every tile) computed inside one
kernel, with the tile loop's arithmetic: operands as they come (``cfg.dtype``)
into the MXU, float32 accumulation, float32 running maximum and sum,
probabilities rounded to the operands' type for the second product. It takes
what the engine's three layer kinds hand it, each read from the SHAPES and
from ``window``:

- a score wider than the value, in two operands: a head's own keys ``k``
  ``[B, T, Hkv, Dk]`` score against ``q[..., :Dk]``, and ONE slice ``shared``
  ``[B, T, D - Dk]`` that every head shares (latent attention's rotated key
  slice) against ``q[..., Dk:]``; the scale is ``1 / sqrt(D)`` of the query's
  whole width. The ``[B, T, H, D]`` keys are never made.
- GQA by index map: query head ``h`` reads key/value head ``h // (H // Hkv)``;
  a group's keys stay in VMEM while its query heads go by.
- a band: with ``window`` position ``i`` sees ``j <= i`` with ``j > i -
  window``; a query block's loop over key blocks starts at
  :func:`first_key_block` and ends at its own diagonal, so a window layer's
  work follows the band's area. Only the blocks the diagonal or the band's
  edge cuts are masked; those between run without a mask.

Grid ``(batch, query head, query block)``; a head's whole keys and values lie
in VMEM (Pallas skips the copy while the block index stands still) and the
key blocks are a ``fori_loop`` with dynamic bounds, so a skipped block costs
nothing. The call asks for the VMEM its buffers need (4 + 4 MB of keys and
values at 16,384 x 128 in bfloat16, each twice) and 16 MiB for its own
tiles. The other way, a key-block grid axis with the keys' index clamped to
the band so that skipped steps copy nothing, keeps VMEM small and was
measured 5-8% slower at blocks of 1,024 (16,384 positions, 28 heads on 4:
16.0 against 15.2 ms full, 9.2 against 8.5 with the window; my chip runs, PR
39): a head has 136-528 (query, key) block pairs there and pays a grid step
for every one, the skipped half included.

No log-sum-exp output and no backward kernel: ``models/llama.py`` gives the
call the tile loop's transpose. ``ops/flash_attention.py`` (the trainer's
kernels) knows nothing of this module and a train process never imports it.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # the tile loop's: finite, so a row all masked stays finite
_LANE = 128
_KERNEL_TILES_VMEM = 16 * 2 ** 20  # room for the kernel's own score tiles


def pick_blocks(t: int) -> Optional[Tuple[int, int]]:
    """``(queries a grid step, keys a loop step)`` for ``t`` positions, or
    None where 128 does not divide them: the largest of 512, 256, 128 that
    divides ``t``, and keys two such blocks a step. What a loop step costs
    beside its products (two reductions along the keys and the rescaling of
    the running sum and values, a query row each) is paid once for 1,024
    keys: at 8,192 x 64 heads 15.6 ms against 20.7 with 512 keys a step,
    whatever the query block (1,024 x 1,024: 15.1; 2,048 keys: 16.7; my chip
    runs, PR 39). A query block of 512 divides every page count of the
    engine's; the keys are filled up to whole steps."""
    for blk in (512, 256, 128):
        if t % blk == 0:
            return blk, 2 * blk
    return None


def first_key_block(i, blk_q: int, blk_k: int, window: int):
    """The first key block query block ``i`` visits (``i`` a number or
    traced): 0, or with a ``window`` the block that holds the first key its
    FIRST query sees, ``i * blk_q - window + 1`` (the tile loop's ``near``)."""
    if not window:
        return 0
    return jnp.maximum(0, i * blk_q - window + 1) // blk_k


def _kernel(*refs, scale, blk_q, blk_k, window, dk, has_shared):
    """q (1,1,blk_q,D); k (1,1,T,Dk); [shared (1,T,D-Dk)]; v (1,1,T,Dv);
    o (1,1,blk_q,Dv); scratch: running maximum and sum (blk_q,1), weighted
    values (blk_q,Dv), float32."""
    if has_shared:
        q_ref, k_ref, shared_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    f32 = jnp.float32
    i = pl.program_id(2)
    q_own = q_ref[0, 0] if not has_shared else q_ref[0, 0, :, :dk]
    q_shared = q_ref[0, 0, :, dk:] if has_shared else None
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
    # query position - key position inside a pair of blocks with one number
    ahead = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0) \
        - jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)

    def visit(j, masked: bool):
        at = pl.ds(pl.multiple_of(j * blk_k, blk_k), blk_k)
        s = jax.lax.dot_general(q_own, k_ref[0, 0, at, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)
        if has_shared:
            s += jax.lax.dot_general(q_shared, shared_ref[0, at, :],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)
        s = s * scale
        if masked:
            d = ahead + (i * blk_q - j * blk_k)
            visible = d >= 0
            if window:
                visible &= d < window
            s = jnp.where(visible, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        old = jnp.exp(m - m_new)
        v = v_ref[0, 0, at, :]
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * old + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * old + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)

    def step(j, carry):
        # a block whose last key the first query sees, and whose first key
        # the last query still has in its window, needs no mask
        whole = (j + 1) * blk_k <= i * blk_q + 1
        if window:
            whole &= (i + 1) * blk_q - 1 - j * blk_k < window
        pl.when(whole)(lambda: visit(j, False))
        pl.when(jnp.logical_not(whole))(lambda: visit(j, True))
        return carry

    # up to the block that holds the last query's own position
    jax.lax.fori_loop(first_key_block(i, blk_q, blk_k, window),
                      ((i + 1) * blk_q - 1) // blk_k + 1, step, 0)
    o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, shared=None, window: int = 0,
                  blocks: Optional[Tuple[int, int]] = None,
                  interpret: bool = False):
    """Causal attention of ``q`` [B, T, H, D] over the call's own positions:
    ``k`` [B, T, Hkv, Dk], ``v`` [B, T, Hkv, Dv] (``Hkv`` divides ``H``),
    ``shared`` [B, T, D - Dk] or None (then ``Dk`` is ``D``), all of one
    type; ``window`` 0: every earlier position. Returns [B, T, H, Dv] in that
    type. ``blocks`` (:func:`pick_blocks`): the query block must divide
    ``T``; keys and values are filled up to whole key blocks with rows that
    lie ahead of every query and are never seen."""
    B, T, H, D = q.shape
    Hkv, dk, dv = k.shape[2], k.shape[3], v.shape[3]
    blocks = blocks or pick_blocks(T)
    if blocks is None or T % blocks[0]:
        raise ValueError(f"{T} positions are no multiple of a query block "
                         f"{blocks}")
    if H % Hkv or (D - dk) != (0 if shared is None else shared.shape[-1]):
        raise ValueError(f"q {q.shape}, k {k.shape}, shared "
                         f"{None if shared is None else shared.shape}")
    blk_q, blk_k = blocks
    rep = H // Hkv
    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(D), blk_q=blk_q, blk_k=blk_k,
        window=int(window), dk=dk, has_shared=shared is not None)
    Tk = -(-T // blk_k) * blk_k

    def keys(a):  # [B, T, ...] filled up to whole key blocks
        return jnp.pad(a, [(0, 0), (0, Tk - T)] + [(0, 0)] * (a.ndim - 2))

    # (operand, its block, the block's index): a block of queries; a group's
    # whole keys and values, which stay while its query heads go by
    operands = [
        (jnp.swapaxes(q, 1, 2), (1, 1, blk_q, D),
         lambda b, h, i: (b, h, i, 0)),
        (jnp.swapaxes(keys(k), 1, 2), (1, 1, Tk, dk),
         lambda b, h, i: (b, h // rep, 0, 0)),
        (jnp.swapaxes(keys(v), 1, 2), (1, 1, Tk, dv),
         lambda b, h, i: (b, h // rep, 0, 0)),
    ]
    if shared is not None:
        operands.insert(2, (keys(shared), (1, Tk, D - dk),
                            lambda b, h, i: (b, 0, 0)))
    out_block = (1, 1, blk_q, dv)
    # what the block specs keep in VMEM: every block twice (the pipeline's
    # double buffering), its last dimension filled up to whole lanes; and
    # room for the kernel's own score tiles (float32 [blk_q, blk_k], 2 MB
    # each, a handful alive)
    buffers = 2 * q.dtype.itemsize * sum(
        math.prod(block[:-1]) * -(-block[-1] // _LANE) * _LANE
        for block in [block for _, block, _ in operands] + [out_block])
    o = pl.pallas_call(
        kernel,
        grid=(B, H, T // blk_q),
        in_specs=[pl.BlockSpec(block, at) for _, block, at in operands],
        out_specs=pl.BlockSpec(out_block, lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=buffers + _KERNEL_TILES_VMEM),
        interpret=interpret,
        name="flash_prefill",
    )(*(a for a, _, _ in operands))
    return jnp.swapaxes(o, 1, 2)
