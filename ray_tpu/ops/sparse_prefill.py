"""Pallas TPU kernels for a prefill whose attention reads, for every query,
the ``topk`` keys a learned indexer picks (``models/llama.py index_block``):
forward only, causal over the call's own positions, two calls.

:func:`index_select` scores a block of queries against every visible key
and writes the selection as a mask ``[T, T]`` of int8. The index score is
``sum_j w_j relu(qi_j . ki)`` over the indexer's query heads ``j`` on ONE
key head: the heads of a query block are stacked into the rows of one
product ``[heads * block, Di] x [Di, keys]`` a key block, and the weighted
sum over heads runs on the tile while it is in VMEM (in XLA the ``[heads,
block, keys]`` float32 scores go through HBM: 1 GB a 512-query block at
32,768 keys). The block's whole row of scores stays in VMEM as int32 keys
that order as the floats do, and each row's ``topk``-th largest is found by
bisection over those bit patterns: 32 counts of the visible row, no sort
(``models/llama.py select_top`` is the same rule in XLA and the oracle:
ties at the last place go to the LOWER positions, found by a second
bisection over positions that runs only where a block has such ties).
Rows that see no more than ``topk`` keys select all of them.

:func:`masked_flash` is flash attention under that mask. A KV group's query
heads are stacked into the rows of one score product, so the mask tile is
read once a group and not once a head; a group's whole keys and values lie
in VMEM while its query blocks go by (``ops/flash_prefill.py``'s layout),
and a query block's loop over key blocks ends at its diagonal. The mask
already holds causality. Every tile under the diagonal is computed: with
2,048 of up to 32,768 keys chosen by a random indexer no tile is empty.

Imported by ``models/llama.py attend_selected`` on a TPU backend and by
nothing else: a train process never imports this module.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
INT_MIN = -2 ** 31
_LANE = 128
SELECT_BLOCK_Q = 128   # queries a grid step of index_select
SELECT_BLOCK_K = 512   # keys a loop step of index_select
FLASH_BLOCK_Q = 128    # queries (of every head of a group) a grid step
FLASH_BLOCK_K = 512    # keys a mask tile holds; masked_flash takes
FLASH_TILES = 2        # this many tiles a loop step


def _lanes(n: int) -> int:
    return -(-n // _LANE) * _LANE


def _select_kernel(q_ref, w_ref, kt_ref, o_ref, key_ref, *, heads, blk_q,
                   blk_k, topk, n_blocks):
    """q (1,1,heads*blk_q,Di) head-major rows; w (1,1,heads*blk_q,1) f32;
    kt (1,n_blocks,Di,blk_k) the keys transposed, a key block a slab; o
    (1,1,n_blocks,blk_q,blk_k) int8; scratch: the row's order keys
    (n_blocks,blk_q,blk_k) int32. A key block is a LEADING index
    everywhere: a dynamic offset along lanes is not."""
    i32 = jnp.int32
    i = pl.program_id(1)
    q = q_ref[0, 0]
    w = w_ref[0, 0]
    n_vis = ((i + 1) * blk_q - 1) // blk_k + 1   # key blocks a row may see
    # query position - key position inside a pair of blocks with one number
    ahead = jax.lax.broadcasted_iota(i32, (blk_q, blk_k), 0) \
        - jax.lax.broadcasted_iota(i32, (blk_q, blk_k), 1)

    def visible(j):
        return ahead + (i * blk_q - j * blk_k) >= 0

    def score(j, carry):
        s = jax.lax.dot_general(q, kt_ref[0, j],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w
        total = s[0:blk_q]
        for h in range(1, heads):
            total = total + s[h * blk_q:(h + 1) * blk_q]
        bits = pltpu.bitcast(total, i32)
        key = jnp.where(bits < 0, bits ^ i32(0x7FFFFFFF), bits)
        key_ref[j] = jnp.where(visible(j), key, i32(INT_MIN))
        return carry

    jax.lax.fori_loop(0, n_vis, score, 0)

    def count(test):
        """[blk_q, 1]: a row's keys (of the blocks it may see) that pass."""
        def body(j, acc):
            hit = test(key_ref[j], j).astype(i32)
            for c in range(blk_k // _LANE):
                acc = acc + hit[:, c * _LANE:(c + 1) * _LANE]
            return acc

        acc = jax.lax.fori_loop(0, n_vis, body,
                                jnp.zeros((blk_q, _LANE), i32))
        return jnp.sum(acc, axis=-1, keepdims=True)

    def value_bit(b, tau):  # the largest tau that topk of a row's keys reach
        cand = tau | jnp.left_shift(i32(1), 30 - b)
        enough = count(lambda key, j: key >= cand) >= topk
        return jnp.where(enough, cand, tau)

    tau = jnp.where(count(lambda key, j: key >= 0) >= topk, i32(0),
                    i32(INT_MIN))
    tau = jax.lax.fori_loop(0, 31, value_bit, tau)
    reach = count(lambda key, j: key >= tau)
    # the last place's ties go to the lower positions: ``cut`` is the largest
    # position with fewer than ``need`` ties under it. A row whose keys at
    # tau all fit (no surplus; or fewer than topk visible) keeps cut = all
    need = topk - count(lambda key, j: key > tau)
    surplus = (reach > topk).astype(i32)
    bits = max(1, (n_blocks * blk_k - 1).bit_length())
    cols = jax.lax.broadcasted_iota(i32, (blk_q, blk_k), 1)

    def index_bit(b, cut):
        cand = cut | jnp.left_shift(i32(1), bits - 1 - b)
        under = count(lambda key, j: (key == tau)
                      & (cols + j * blk_k < cand))
        return jnp.where(under < need, cand, cut)

    cut = jax.lax.cond(
        jnp.sum(surplus) > 0,
        lambda: jax.lax.fori_loop(0, bits, index_bit,
                                  jnp.zeros((blk_q, 1), i32)),
        lambda: jnp.full((blk_q, 1), 2 ** 30, i32))

    def write(j, carry):
        key = key_ref[j]
        chosen = (key > tau) | ((key == tau) & (cols + j * blk_k <= cut))
        o_ref[0, 0, j] = jnp.where(chosen & visible(j), 1, 0).astype(
            o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_vis, write, 0)

    def blank(j, carry):
        o_ref[0, 0, j] = jnp.zeros((blk_q, blk_k), o_ref.dtype)
        return carry

    jax.lax.fori_loop(n_vis, n_blocks, blank, 0)


def index_select(qi, ki, w, topk: int, *, interpret: bool = False):
    """The selection mask, int8, 1 where query ``t`` attends key ``s``, in
    tiles ``[B, T / 128, Tk / 512, 128, 512]`` (``Tk``: ``T`` filled up to
    whole key blocks; :func:`mask_rows` lays it out ``[B, T, Tk]``), as
    :func:`masked_flash` reads it: of the keys
    ``s <= t``, the ``topk`` of largest ``sum_j w[t, j] relu(qi[t, j] .
    ki[s])``, all of them while ``t + 1 <= topk``, a tie at the last place
    to the lower ``s``. ``qi`` [B, T, Hi, Di] and ``ki`` [B, T, Di] in one
    type, ``w`` [B, T, Hi] float32; 128 divides ``T``."""
    B, T, H, D = qi.shape
    blk_q, blk_k = SELECT_BLOCK_Q, SELECT_BLOCK_K
    if T % blk_q:
        raise ValueError(f"{T} positions are no multiple of {blk_q}")
    Tk = -(-T // blk_k) * blk_k
    nq = T // blk_q
    # a query block's heads stacked, head-major: row h * blk_q + r
    q = jnp.swapaxes(qi.reshape(B, nq, blk_q, H, D), 2, 3).reshape(
        B, nq, H * blk_q, D)
    ws = jnp.swapaxes(w.astype(jnp.float32).reshape(B, nq, blk_q, H), 2,
                      3).reshape(B, nq, H * blk_q, 1)
    nk = Tk // blk_k
    kt = jnp.swapaxes(jnp.pad(ki, ((0, 0), (0, Tk - T), (0, 0))).reshape(
        B, nk, blk_k, D), 2, 3)
    kernel = functools.partial(_select_kernel, heads=H, blk_q=blk_q,
                               blk_k=blk_k, topk=int(topk),
                               n_blocks=nk)
    item = qi.dtype.itemsize
    # the blocks twice (double buffering), the row's keys, and the kernel's
    # own tiles: the stacked scores [H * blk_q, blk_k] float32 a few times
    vmem = (2 * (H * blk_q * _lanes(D) * item + H * blk_q * _LANE * 4
                 + D * Tk * item + blk_q * Tk)
            + blk_q * Tk * 4 + 6 * H * blk_q * blk_k * 4)
    return pl.pallas_call(
        kernel,
        grid=(B, nq),
        in_specs=[
            pl.BlockSpec((1, 1, H * blk_q, D), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, H * blk_q, 1), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, nk, D, blk_k), lambda b, i: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nk, blk_q, blk_k),
                               lambda b, i: (b, i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nq, nk, blk_q, blk_k), jnp.int8),
        scratch_shapes=[pltpu.VMEM((nk, blk_q, blk_k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
        name="dsa_index_select",
    )(q, ws, kt)


def mask_rows(mask):
    """:func:`index_select`'s tiles as ``[B, T, Tk]``."""
    B, nq, nk, blk_q, blk_k = mask.shape
    return jnp.swapaxes(mask, 2, 3).reshape(B, nq * blk_q, nk * blk_k)


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale, rep, blk_q, blk_k, tiles):
    """q (1,1,1,rep*blk_q,D) a group's query heads stacked, head-major;
    k, v (1,1,Tk,D); mask (1,1,n_blocks,blk_q,blk_k) int8; o as q; scratch: running
    maximum and sum (rep*blk_q,1), weighted values (rep*blk_q,Dv), f32."""
    f32 = jnp.float32
    i = pl.program_id(2)
    q = q_ref[0, 0, 0]
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    keys = tiles * blk_k

    def step(j, carry):
        at = pl.ds(pl.multiple_of(j * keys, keys), keys)
        s = jax.lax.dot_general(q, k_ref[0, 0, at, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        seen = [mask_ref[0, 0, j * tiles + n].astype(jnp.int32)
                for n in range(tiles)]
        seen = (seen[0] if tiles == 1 else jnp.concatenate(seen, axis=1)) != 0
        bias = jnp.where(seen, 0.0, NEG_INF)
        s = s + jnp.concatenate([bias] * rep, axis=0)
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
        return carry

    # up to the block that holds the last query's own position; a row whose
    # first blocks hold none of its keys carries exp(0) sums until its first
    # chosen key rescales them by 0 (every row has chosen its own block's)
    jax.lax.fori_loop(0, ((i + 1) * blk_q - 1) // keys + 1, step, 0)
    o_ref[0, 0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def masked_flash(q, k, v, mask, *, tiles: int = FLASH_TILES,
                 interpret: bool = False):
    """Attention of ``q`` [B, T, H, D] over ``k`` / ``v`` [B, T, Hkv, D / Dv]
    (``Hkv`` divides ``H``; one type) under ``mask`` (:func:`index_select`'s
    tiles: non-zero where query ``t`` attends key ``s``,
    nothing beyond ``s = t``; every row attends at least one key of its own
    key block or an earlier one). Scale ``1 / sqrt(D)``, float32 scores and
    softmax. ``tiles`` mask tiles (1,024 keys) a loop step: what a step
    costs beside its products (the running maximum and sum of 1,024 stacked
    rows, a lane each, and the rescaling) is paid once for twice the keys:
    67.3 ms against 126.7 at 32,768 positions, 18.6 against 33.4 at 16,384;
    four tiles a step 72.8 and 20.5 (my chip run, PR 40). Returns [B, T, H,
    Dv] in the operands' type."""
    B, T, H, D = q.shape
    G, dv = k.shape[2], v.shape[3]
    rep = H // G
    blk_q, blk_k = FLASH_BLOCK_Q, FLASH_BLOCK_K
    nq, nk = T // blk_q, mask.shape[2]
    if T % blk_q or H % G or mask.shape != (B, nq, nk, blk_q, blk_k) \
            or nk * blk_k < T:
        raise ValueError(f"q {q.shape}, k {k.shape}, mask {mask.shape}")
    if nk % tiles:  # whole loop steps: tiles of zeros, never seen
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, -nk % tiles), (0, 0),
                              (0, 0)))
        nk = mask.shape[2]
    Tk = nk * blk_k

    def stacked(a):  # [B, T, G * rep, d] -> [B, G, nq, rep * blk_q, d]
        d = a.shape[-1]
        a = a.reshape(B, nq, blk_q, G, rep, d)
        return jnp.transpose(a, (0, 3, 1, 4, 2, 5)).reshape(
            B, G, nq, rep * blk_q, d)

    def keys(a):  # [B, T, G, d] -> [B, G, Tk, d]
        return jnp.swapaxes(jnp.pad(
            a, ((0, 0), (0, Tk - T), (0, 0), (0, 0))), 1, 2)

    kernel = functools.partial(_flash_kernel, scale=1.0 / math.sqrt(D),
                               rep=rep, blk_q=blk_q, blk_k=blk_k,
                               tiles=tiles)
    item = q.dtype.itemsize
    rows = rep * blk_q
    vmem = (2 * (rows * _lanes(D) * item + Tk * _lanes(D) * item
                 + Tk * _lanes(dv) * item + blk_q * Tk
                 + rows * _lanes(dv) * item)
            + rows * (2 * _LANE + _lanes(dv)) * 4
            + 8 * rows * tiles * blk_k * 4)
    o = pl.pallas_call(
        kernel,
        grid=(B, G, nq),
        in_specs=[
            pl.BlockSpec((1, 1, 1, rows, D), lambda b, g, i: (b, g, i, 0, 0)),
            pl.BlockSpec((1, 1, Tk, D), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, Tk, dv), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, nk, blk_q, blk_k),
                         lambda b, g, i: (b, i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, rows, dv),
                               lambda b, g, i: (b, g, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, nq, rows, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
        name="dsa_masked_flash",
    )(stacked(q), keys(k), keys(v), mask)
    # [B, G, nq, rep, blk_q, dv] -> [B, T, H, dv]
    o = o.reshape(B, G, nq, rep, blk_q, dv)
    return jnp.transpose(o, (0, 2, 4, 1, 3, 5)).reshape(B, T, H, dv)
