"""Pallas TPU flash attention: blockwise causal attention, GQA-aware.

The MXU-friendly replacement for ``plain_attention``'s [B, H, T, T] fp32
score materialization (the round-1 MFU bottleneck). Design:

- forward: grid over (batch, q_head, q_block); K/V for the head group live
  in VMEM once (Pallas skips the re-DMA when the block index is unchanged
  across consecutive grid steps; a call whose resident blocks pass the
  compiler's default scoped VMEM limit, at 8,192 positions the dK/dV call at
  head width 128 and all three at 256, asks for its limit); inner
  ``fori_loop`` over K/V blocks with
  online-softmax (max/sum) carries, so HBM traffic is O(T) not O(T^2).
  Causal skips future blocks entirely via a dynamic loop bound.
- backward: two kernels — dQ (grid over q blocks, loop over past K/V
  blocks) and dK/dV (grid over kv blocks, loop over future Q blocks),
  recomputing probabilities from the saved logsumexp, flash-attention-2
  style. GQA head-group reduction for dK/dV happens outside the kernel
  (one reshape-sum).
- GQA: q heads map to kv head ``h // (Hq // Hkv)`` in the BlockSpec index
  map — no ``jnp.repeat`` of K/V through HBM.
- head_dim is zero-padded to a lane multiple (128) when needed; padding
  contributes nothing to scores and is sliced off outputs/grads.

Reference behavior being replaced: ray.util's delegation of attention math
to torch (reference has no in-repo attention kernel; SURVEY.md §5
long-context row names Pallas flash/splash attention as the TPU design).
"""

from __future__ import annotations

import functools
import logging
import math
import threading
from typing import Dict, List, Optional, Tuple

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


def _pick_block(t: int) -> Optional[int]:
    for blk in (512, 256, 128, 64):
        if t % blk == 0:
            return blk
    return None


def _unsupported_reason(q_shape, k_shape, block: Optional[int]
                        ) -> Optional[str]:
    """Why the kernel cannot take these shapes, or None if it can."""
    _, T, Hq, _ = q_shape
    Tk, Hkv = k_shape[1], k_shape[2]
    if T != Tk:
        return f"q length {T} != k length {Tk} (self-attention only)"
    if block is None or T % block != 0:
        return (f"sequence length {T} is not a multiple of "
                f"{block or 'any block in (512, 256, 128, 64)'}")
    if Hq % Hkv != 0:
        return f"{Hq} q heads not a multiple of {Hkv} kv heads"
    return None


def attention_path(q_shape, k_shape, block: Optional[int] = None,
                   interpret: bool = False) -> Tuple[str, str]:
    """(path, reason) :func:`flash_attention` takes for these shapes in
    this process: the kernel wherever it can run (compiled on a TPU
    backend, interpreted on request), the reference otherwise. A backend
    that cannot be initialised is an error here, not a reason."""
    why_not = _unsupported_reason(q_shape, k_shape,
                                  block or _pick_block(q_shape[1]))
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


def _record_path(q_shape, k_shape, dtype, path: str, reason: str) -> None:
    key = (tuple(q_shape), tuple(k_shape), str(dtype), path)
    with _taken_lock:
        rec = _taken.get(key)
        if rec is not None:
            rec["calls"] += 1
            return
        _taken[key] = {"q_shape": list(q_shape), "k_shape": list(k_shape),
                       "dtype": str(dtype), "path": path, "reason": reason,
                       "calls": 1}
    logger.info("flash_attention q%s k%s %s -> %s (%s)",
                list(q_shape), list(k_shape), dtype, path, reason)


def paths_taken() -> List[dict]:
    """Every distinct (shapes, dtype, path) :func:`flash_attention` has
    been traced with in this process, with its reason and call count: how
    a run proves which attention it used."""
    with _taken_lock:
        return [dict(rec) for rec in _taken.values()]


# --------------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------------- #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, blk, causal,
                n_kv_blocks):
    """q_ref (1,1,blk,D); k/v_ref (1,1,T,D); o_ref (1,1,blk,D); lse (1,1,blk)."""
    qi = pl.program_id(2)
    D = q_ref.shape[-1]
    q = q_ref[0, 0].astype(jnp.float32) * scale  # [blk, D]

    def body(j, carry):
        acc, l, m = carry
        kb = k_ref[0, 0, pl.ds(j * blk, blk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [blk, blk]
        if causal:
            q_pos = qi * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            k_pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        vb = v_ref[0, 0, pl.ds(j * blk, blk), :].astype(jnp.float32)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, l, m_new

    acc0 = jnp.zeros((blk, D), jnp.float32)
    l0 = jnp.zeros((blk,), jnp.float32)
    m0 = jnp.full((blk,), NEG_INF, jnp.float32)
    upper = qi + 1 if causal else n_kv_blocks
    acc, l, m = jax.lax.fori_loop(0, upper, body, (acc0, l0, m0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, :, 0] = m + jnp.log(l)


def _vmem_params(buffers: int, interpret: bool) -> dict:
    """``pallas_call``'s keywords for a call whose block specs keep
    ``buffers`` bytes in VMEM (each block twice: the pipeline's double
    buffering; a [.., 1] float32 column is padded to 128 lanes). A call that
    fits the compiler's default scoped limit is compiled under it, as it
    always was; one that does not asks for its buffers and as much again as
    the default for the kernel's own tiles (a v5e core has 128 MiB). The
    buffers follow the sequence AND the head's width: a head group's whole
    K and V are 8.5 MiB at 8,192 positions and width 128 and 17.5 at 256
    (forward; dQ one block more), a head's whole q, do, lse and delta 25.5
    and 35 (dK/dV)."""
    if buffers <= _SCOPED_VMEM_DEFAULT or interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=buffers + _SCOPED_VMEM_DEFAULT)}


def _fwd(q, k, v, *, causal, blk, interpret):
    """q [B,Hq,T,D], k/v [B,Hkv,T,D] -> (o [B,Hq,T,D], lse [B,Hq,T])."""
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    grid = (B, Hq, T // blk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, blk=blk, causal=causal,
        n_kv_blocks=T // blk)
    # whole k and v, a block each of q and o, a block of lse
    buffers = 2 * (2 * T * D * k.dtype.itemsize
                   + 2 * blk * D * q.dtype.itemsize + blk * _LANE * 4)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        **_vmem_params(buffers, interpret),
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


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, blk, causal, n_kv_blocks):
    qi = pl.program_id(2)
    D = q_ref.shape[-1]
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]

    def body(j, dq):
        kb = k_ref[0, 0, pl.ds(j * blk, blk), :].astype(jnp.float32)
        vb = v_ref[0, 0, pl.ds(j * blk, blk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            k_pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    upper = qi + 1 if causal else n_kv_blocks
    dq = jax.lax.fori_loop(0, upper, body,
                           jnp.zeros((blk, D), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, blk, causal, n_q_blocks):
    kj = pl.program_id(2)
    D = q_ref.shape[-1]
    kb = k_ref[0, 0].astype(jnp.float32)  # [blk, D]
    vb = v_ref[0, 0].astype(jnp.float32)

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, 0, pl.ds(i * blk, blk), :].astype(jnp.float32)
        dob = do_ref[0, 0, pl.ds(i * blk, blk), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(i * blk, blk), 0]
        delta = delta_ref[0, 0, pl.ds(i * blk, blk), 0]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [q_blk, k_blk]
        if causal:
            q_pos = i * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
            k_pos = kj * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])  # [q, k]
        dv_new = dv + jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # p^T @ do -> [k, D]
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [q, k]
        ds = p * (dp - delta[:, None]) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # ds^T @ q -> [k, D]
        return dk_new, dv_new

    lower = kj if causal else 0
    dk, dv = jax.lax.fori_loop(
        lower, n_q_blocks, body,
        (jnp.zeros((blk, D), jnp.float32), jnp.zeros((blk, D), jnp.float32)))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, *, causal, blk, interpret):
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B,Hq,T,1]
    n_blocks = T // blk

    # whole k and v, a block each of q, do and dq, of lse and delta
    buffers = 2 * (2 * T * D * k.dtype.itemsize
                   + 3 * blk * D * q.dtype.itemsize + 2 * blk * _LANE * 4)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, blk=blk, causal=causal,
                          n_kv_blocks=n_blocks),
        grid=(B, Hq, n_blocks),
        **_vmem_params(buffers, interpret),
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

    # What the dK/dV call's block specs keep in VMEM (_vmem_params): a
    # head's whole q and do, its lse and delta, a block each of k and v, a
    # float32 block each of dk and dv. 3 KiB a position at D = 128: 7.5 MiB
    # at T = 2048, 13.5 at 4096, 25.5 at 8192
    buffers = 2 * (T * (2 * D * q.dtype.itemsize + 2 * _LANE * 4)
                   + 2 * blk * D * (k.dtype.itemsize + 4))
    dk_exp, dv_exp = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, blk=blk, causal=causal,
                          n_q_blocks=n_blocks),
        grid=(B, Hq, n_blocks),
        **_vmem_params(buffers, interpret),
        in_specs=[
            pl.BlockSpec((1, 1, T, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, blk, D), lambda b, h, j: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, blk, D), lambda b, h, j: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, 1), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, blk, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, T, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, T, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)

    # GQA group-sum: q heads [g*rep, (g+1)*rep) all attend kv head g
    dk = dk_exp.reshape(B, Hkv, rep, T, D).sum(axis=2).astype(k.dtype)
    dv = dv_exp.reshape(B, Hkv, rep, T, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom_vjp wrapper ([B,H,T,D] layout)
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhtd(q, k, v, causal, blk, interpret):
    o, _ = _fwd(q, k, v, causal=causal, blk=blk, interpret=interpret)
    return o


def _flash_fwd_rule(q, k, v, causal, blk, interpret):
    o, lse = _fwd(q, k, v, causal=causal, blk=blk, interpret=interpret)
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


def _flash_bwd_rule(causal, blk, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, causal=causal, blk=blk,
                interpret=interpret)


_flash_bhtd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# --------------------------------------------------------------------------- #
# Public API ([B,T,H,D] layout, matching the model)
# --------------------------------------------------------------------------- #


def flash_attention(q, k, v, causal: bool = True,
                    block: Optional[int] = None,
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
    blk = block or _pick_block(T)
    path, reason = attention_path(q.shape, k.shape, blk, interpret)
    _record_path(q.shape, k.shape, q.dtype, path, reason)
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
    o = _flash_bhtd(qt, kt, vt, causal, blk, interpret)
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
