"""Pallas TPU gated delta rule for the decode engine's prefill: forward only,
the causal convolution, the heads' l2-norm and the chunked recurrence in ONE
call, the state in VMEM.

What ``models/llama.py attend_delta`` computes in XLA (``ops/ssm.py
causal_conv`` over float32 rows, ``_delta_heads``, ``ops/gdn.py
gated_delta_chunked``: ``[B, H, N, C, C]`` float32 matrices for every chunk
of a segment at once, one ``solve_triangular``, five ``[.., C, K]`` arrays
through HBM and a ``lax.scan`` of one step a chunk) computed inside one
kernel, with that form's arithmetic and its rounding points: float32 ``g``,
decays, inverse, sums and carried state; products in the operands' type with
float32 accumulation; the state rounded to that type only where a chunk reads
it. ``ops/gdn.py``'s docstring has the algebra; that module is this one's
oracle and its transpose.

- The rows are read as the in-projection wrote them, ``qkv`` ``[B, T, W]``
  (``W`` = key heads x key dim, twice, then value heads x value dim), never
  copied, padded, repeated or transposed: a grid step takes a KEY head's
  query and key columns and the columns of the value heads that read it
  (``R`` = value heads / key heads of them; they share ``k k^T`` and ``q
  k^T``), three index maps into the one array.
- Grid ``(sequence, key head, row tile)``, the row tile the sequential axis:
  each value head's ``[K, V]`` float32 state and the convolution's last
  ``taps - 1`` raw rows stay in VMEM scratch across it, set from the start
  state and tail at tile 0, the state written at the last tile. The prompt is
  ONE piece whatever its length.
- In a tile: the depthwise causal convolution, ``silu``, the l2-norm (eps
  1e-6) and the query's scale in float32, rounded to the operands' type where
  ``_delta_heads`` rounds (a v5e's VPU has no bfloat16 arithmetic: what the
  kernel saves is passes over HBM). Then, for every chunk of the tile
  (:data:`CHUNK` positions) and of no state: the cumulative log-decay, the
  decay matrix (masked BEFORE the exponential), ``A``, ``T = (I + A)^-1``,
  ``T``'s two right-hand sides, masked ``q k^T``, decayed ``q`` and ``k``:
  ``ops/gdn.py``'s six arrays, in VMEM for a tile and not in HBM for a
  segment. A key head's value heads go as ONE block-diagonal problem of ``R
  x CHUNK`` rows (128 at the published widths: what the MXU takes at once).
  Last a loop over the chunks that carries the states: a head's ``[k_seen;
  q] S`` as one product, ``qk u`` for the group, ``k^T u``.
- ``T`` without a triangular solve (Mosaic has none), and NOT as the sum of
  ``-A``'s 63 powers either: :func:`_unit_lower_inverses` (Newton's
  iteration from the inverses of 8-row blocks, the last residual a float32
  product) says why and how near it comes.
- Float32 products cost the MXU six bfloat16 passes at ``Precision.HIGHEST``.
  Where one factor is bfloat16 already (``v``, ``k``, a matrix of ones) the
  other goes as its three bfloat16 parts, which is EXACT under the float32
  sum and three passes (:func:`_times`): ``T``'s right-hand sides and the
  sums that make the cumulative decays.
- A chain of small products one behind the other leaves the MXU waiting
  (some 250 cycles a product of 128 rows): the tile's chunks go through the
  state-free part TOGETHER, the chunk a batch dimension of every array, so
  that a stage's products are as many as the chunks and wait for nothing
  (and the kernel's text stays one chunk's: it is traced in a serving
  process's set-up, a prefill program a page count).

``g`` and ``beta`` come with the positions behind a prompt's last real one
already set to 0 (identity updates; the caller's two ``where``s). They are
small (``[B, T, value heads]`` float32) and are laid out for the kernel here,
a chunk of a group's heads side by side along a row.

Operations and bytes, for the roofline this kernel has not got yet (``C`` =
:data:`CHUNK`, ``K`` / ``V`` the key's / value's width, ``R`` value heads a
key head). What the recurrence NEEDS a chunk and value head: ``4 C^2 K / R``
(``k k^T`` and ``q k^T``, a key head's), ``2 C^2 (K + V)`` (``T``'s
right-hand sides), ``6 C K V`` (``k_seen S``, ``q S``, ``k^T u``), ``2 C^2
V`` (``qk u``) and ``2 C^3 / 3`` (the solve): 10.7 MFLOP at 64 / 128 / 128 /
2, 175 GFLOP a layer of 32 heads at 32,768 positions (0.9 ms at a v5e's
peak). What the kernel COMPUTES is more: every ``[R C, R C]`` product
carries the zero blocks between heads, and the inverse is 13 products of ``2
(R C)^3`` a chunk and KEY head. Bytes: a position's ``W`` compute-type
columns read once, ``V`` float32 columns a value head written once, 8 bytes
of ``g`` and ``beta`` a value head: 1.08 GB there (1.3 ms at 819 GB/s, the
floor). The call takes 11.8 ms (my chip runs, PR 46): the VPU's float32
convolution and norms some 4.4, the state's loop 3.4, the rest the
state-free products.

No backward kernel: ``models/llama.py`` gives the call the XLA form's
transpose. A train process never imports this module.
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
CHUNK = 64  # positions a chunk of the recurrence: the kernel's own
_SMALL = 8  # rows of the blocks the inverse starts from: a sublane tile's
_HALO = 8   # rows before a tile's own in the convolution's staging rows
_SPARE_VMEM = 8 * 2 ** 20  # room for a chunk's values beside the buffers


def pick_rows(t: int) -> Optional[int]:
    """Rows a grid step: the largest of 512, 256, 128 that divides ``t``
    positions, or None. 512 divides every page count of the engine's."""
    for rows in (512, 256, 128):
        if t % rows == 0:
            return rows
    return None


# products over a tile's chunks, the chunk the batch dimension (the first)
_EACH = (((2,), (1,)), ((0,), (0,)))     # a[n] @ b[n]
_EACH_NT = (((2,), (2,)), ((0,), (0,)))  # a[n] @ b[n].T
_ALL = (((2,), (0,)), ((), ()))          # a[n] @ b
_PLAIN = (((1,), (0,)), ((), ()))       # a @ b, two dimensions
_TN = (((0,), (0,)), ((), ()))           # a.T @ b, two dimensions


def _f32_dot(a, b, dims=_EACH):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def _dot(a, b, dims=_EACH):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


def _one_pass(a, b):
    return _dot(a.astype(BF16), b.astype(BF16))


def _parts(x, n: int):
    """``x`` [N, M, L] float32 as a sum of ``n`` bfloat16 arrays, the largest
    first, one below the other [N, n M, L]: three hold every bit of it."""
    out = []
    for _ in range(n):
        out.append(x.astype(BF16))
        x = x - out[-1].astype(F32)
    return jnp.concatenate(out, axis=1)


def _times(a, b):
    """``a[n] @ b[n]`` (``b`` of two dimensions: ``a[n] @ b``) to float32
    precision, ``a`` [N, M, L] float32 and ``b`` in the operands' type.
    Where that is bfloat16 the product of ``a``'s three parts with it is
    EXACT under a float32 sum, in three passes of the MXU where
    ``Precision.HIGHEST`` takes six."""
    dims = _EACH if b.ndim == 3 else _ALL
    if b.dtype != BF16:
        return _f32_dot(a, b.astype(F32), dims)
    M = a.shape[1]
    out = _dot(_parts(a, 3), b, dims)
    return out[:, :M] + out[:, M:2 * M] + out[:, 2 * M:]


def _unit_lower_inverses(a, eye, small):
    """``(I + a[n])^-1`` of the strictly lower triangular ``a[n]`` [N, L, L]
    float32 (every product below is one a matrix, ``N`` side by side: they
    wait for one another inside one matrix's chain, and not between
    matrices), ``eye`` the identity, ``small`` the mask of the diagonal
    blocks of :data:`_SMALL` rows: Newton's iteration ``x <- x + x (I - (I +
    a) x)`` from the inverse of those blocks. Every step squares the
    residual, whatever the precision of the steps before it, so they are
    cheap ones:

    - inside a small block the sum of ``-a``'s powers, ``(I - a)(I + a^2)(I
      + a^4)``, whose terms stay small (at 8 rows no larger than 20 times
      an entry; the same sum over a whole chunk of 64 reaches 1e17 where the
      keys of a chunk are alike, and float32 then holds nothing of it);
    - three steps with one bfloat16 pass a product: the part of the residual
      that is the blocks below the diagonal is gone after them (8 to 16 to
      32 to 64 rows), what is left is their rounding;
    - one step against ``x`` rounded to bfloat16, its residual exact
      (:func:`_times`) and taken in two parts;
    - one step whose residual is a float32 product, ``Precision.HIGHEST``.

    To 1e-6 of the largest entry for keys of every likeness (cosine 0.5 to 1
    between all of a chunk's, ``beta`` to 1, with and without decay; the
    plain sum of powers in float32: 4e1 to 9e10), at 13 products of which
    one takes six passes."""
    L = eye.shape[0]
    m, d = eye + a, jnp.where(small, a, 0.0)
    x, power = eye - d, _one_pass(d, d)
    both = _one_pass(jnp.concatenate([x, power], axis=1), power)
    x, power = x + both[:, :L], both[:, L:]
    x = x + _one_pass(x, power)
    for _ in range(int(math.log2(CHUNK // _SMALL))):
        x = x + _one_pass(x, eye - _one_pass(m, x))
    rounded = x.astype(BF16)
    rest = _parts(eye - _times(m, rounded), 2)
    x = rounded.astype(F32) + _dot(rounded, rest[:, :L]) \
        + _dot(rounded, rest[:, L:])
    return x + _one_pass(x, eye - _f32_dot(m, x))


def _kernel(q_ref, k_ref, v_ref, wq_ref, wk_ref, wv_ref, gb_ref, s0_ref,
            tq_ref, tk_ref, tv_ref, o_ref, s_ref,
            xq_ref, xk_ref, xv_ref, qn_ref, kn_ref, vn_ref, state_ref,
            own_ref, seen_ref, qk_ref, qin_ref, kout_ref, down_ref,
            *, taps, rep, dk, dv, scale):
    """Blocks: q / k (1, rows, dk) and v (1, rows, rep * dv) of ``qkv``;
    their convolution weights (taps, ..); gb (1, 1, chunks, 8, rep *
    CHUNK): a chunk's ``g`` (row 0) and ``beta`` (row 1), the group's heads
    side by side; s0 (1, rep, dk, dv); the start tail's last ``_HALO`` rows
    (1, _HALO, ..). Out: o (1, rep, rows, dv), s (1, rep, dk, dv).
    Scratch: the raw rows behind ``_HALO`` earlier ones, float32; the
    convolved, normed rows in the operands' type; the states, float32; a
    tile's chunks' ``u`` and ``k_seen`` before the state's part, masked ``q
    k^T``, decayed ``q`` and ``k`` (the operands' type) and decays down a
    column (``ops/gdn.py``'s six arrays, for a tile and not a segment)."""
    t = pl.program_id(2)
    rows = q_ref.shape[1]
    cd = qn_ref.dtype
    C, L = CHUNK, rep * CHUNK

    @pl.when(t == 0)
    def _():
        state_ref[...] = s0_ref[0]
        for x_ref, tail_ref in ((xq_ref, tq_ref), (xk_ref, tk_ref),
                                (xv_ref, tv_ref)):
            x_ref[0:_HALO, :] = tail_ref[0].astype(F32)

    @pl.when(t > 0)
    def _():
        for x_ref in (xq_ref, xk_ref, xv_ref):
            x_ref[0:_HALO, :] = x_ref[rows:rows + _HALO, :]

    def mixed(x_ref, raw_ref, w_ref):
        """silu(causal convolution) of the tile's rows, float32."""
        x_ref[_HALO:_HALO + rows, :] = raw_ref[0].astype(F32)
        w = w_ref[...].astype(F32)
        y = sum(x_ref[pl.ds(_HALO - (taps - 1) + j, rows), :] * w[j:j + 1]
                for j in range(taps))
        return y * jax.nn.sigmoid(y)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + 1e-6)

    qn_ref[...] = (unit(mixed(xq_ref, q_ref, wq_ref)) * scale).astype(cd)
    kn_ref[...] = unit(mixed(xk_ref, k_ref, wk_ref)).astype(cd)
    vn_ref[...] = mixed(xv_ref, v_ref, wv_ref).astype(cd)

    # a chunk of the group's heads as ONE block-diagonal problem: head r's
    # chunk is rows (and columns) r * C .. (r + 1) * C of [L, L]
    at_row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    at_col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    same = at_row // C == at_col // C
    lower, strict = same & (at_row >= at_col), same & (at_row > at_col)
    small = at_row // _SMALL == at_col // _SMALL
    upper_ones = jnp.where(same & (at_row <= at_col), 1.0, 0.0).astype(BF16)
    eye = jnp.where(at_row == at_col, 1.0, 0.0).astype(F32)
    is_g = jax.lax.broadcasted_iota(jnp.int32, (8, L), 0) == 0

    def heads(parts):  # one part a head [N, C, ..], one below the other
        return parts[0] if rep == 1 else jnp.concatenate(parts, axis=1)

    def prepare():
        """What the tile's ``N`` chunks need of no state, into scratch: all
        that ``ops/gdn.py`` prepares before its scan, the chunk a batch
        dimension. No chunk waits for another here, and a product over the
        batch is ``N`` products side by side: the inverse is a chain of 13
        one behind the other, and only another chunk's can run between them
        (a 32,768-position call: 37.0 ms a chunk at a time, 24.1 two, 18.1
        four, 14.5 a tile's eight; my chip runs, PR 46)."""
        N = rows // C
        q = heads([qn_ref[...].reshape(N, C, dk)] * rep)
        k = heads([kn_ref[...].reshape(N, C, dk)] * rep)
        v = heads([vn_ref[:, r * dv:(r + 1) * dv].reshape(N, C, dv)
                   for r in range(rep)])
        gb = gb_ref[0, 0]                                     # [N, 8, L]
        # the log-decay from a head's chunk's start up to and including each
        # position, along a row (sums of float32 under a float32 sum: exact
        # products with ones); then the rows as columns
        across = jnp.where(is_g, _times(gb, upper_ones), gb)
        down = jnp.swapaxes(across, 1, 2)                     # [N, L, 8]
        G, beta = down[:, :, 0:1], down[:, :, 1:2]
        # masked BEFORE the exponential (ops/gdn.py)
        decay = jnp.exp(jnp.where(lower, G - across[:, 0:1, :], -jnp.inf))
        inv = _unit_lower_inverses(
            jnp.where(strict, beta * decay * _dot(k, k, _EACH_NT), 0.0),
            eye, small)
        # the two right-hand sides diag(beta) v and diag(beta exp G) k: the
        # diagonals go to the inverse's columns, v and k stay as they are
        inv = inv * across[:, 1:2, :]
        own_ref[...] = _times(inv, v).astype(cd)
        seen_ref[...] = _times(inv * jnp.exp(across[:, 0:1, :]), k).astype(cd)
        k32, grown = k.astype(F32), jnp.exp(G)
        qk_ref[...] = jnp.where(lower, decay * _dot(q, k, _EACH_NT),
                                0.0).astype(cd)
        qin_ref[...] = (grown * q.astype(F32)).astype(cd)
        # a head's chunk's whole log-decay, down its rows (Mosaic broadcasts
        # along sublanes or lanes, not both at once)
        end = heads([jnp.broadcast_to(G[:, (r + 1) * C - 1:(r + 1) * C, :],
                                      (N, C, 1)) for r in range(rep)])
        kout_ref[...] = (jnp.exp(end - G) * k32).astype(cd)
        down_ref[...] = down

    def carry_state(c, carry):
        """The four products that read the state, a head: ``ops/gdn.py``'s
        ``carry_state``."""
        at = pl.ds(pl.multiple_of(c * C, C), C)
        u, read = [], []
        for r in range(rep):
            mine = slice(r * C, (r + 1) * C)
            # what the state before the chunk answers for the keys written
            # and for the queries: one product
            both = _dot(jnp.concatenate([seen_ref[c, mine], qin_ref[c, mine]],
                                        axis=0), state_ref[r].astype(cd),
                        _PLAIN)
            u.append((own_ref[c, mine].astype(F32) - both[:C]).astype(cd))
            read.append(both[C:])
        o = jnp.concatenate(read, axis=0) + _dot(
            qk_ref[c], jnp.concatenate(u, axis=0), _PLAIN)
        for r in range(rep):
            mine = slice(r * C, (r + 1) * C)
            o_ref[0, r, at, :] = o[mine]
            end = down_ref[c, (r + 1) * C - 1:(r + 1) * C, 0:1]
            state_ref[r] = jnp.exp(jnp.broadcast_to(end, (dk, 1))) \
                * state_ref[r] + _dot(kout_ref[c, mine], u[r], _TN)
        return carry

    prepare()
    jax.lax.fori_loop(0, rows // C, carry_state, 0)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        s_ref[0] = state_ref[...]


# jitted: a program that runs it in several layers, or whose scan traces its
# body more than once, traces and lowers the kernel ONCE (a serving process
# builds a prefill program a page count: 14 programs of three delta layers a
# scanned period, each traced three times, were 126 traces of the kernel and
# 130 s of a warm process's set-up; PERF.md, PR 46)
@functools.partial(jax.jit,
                   static_argnames=("key_heads", "key_dim", "interpret"))
def gdn_prefill(qkv, conv_w, g, beta, state, tail, *, key_heads: int,
                key_dim: int, interpret: bool = False):
    """The delta-rule layer between its in-projection and its gated norm,
    over ``T`` positions of ``B`` sequences. ``qkv`` [B, T, W] (``[q | k |
    v]``, ``key_heads`` heads of ``key_dim`` for ``q`` and for ``k``, the
    rest value heads as wide as the state's last dimension says), ``conv_w``
    [taps, W], ``g`` / ``beta`` [B, T, value heads] float32, ``state`` [B,
    value heads, key_dim, value dim] float32 and ``tail`` [B, taps - 1, W]
    (the rows before the first, ``qkv``'s type): where the sequence stands.
    ``T`` must be a multiple of :func:`pick_rows`' tile. Returns ``(o [B, T,
    value heads, value dim] float32, state)``."""
    B, T, W = qkv.shape
    taps = conv_w.shape[0]
    hv, dk, dv = state.shape[1], key_dim, state.shape[3]
    rows = pick_rows(T)
    rep = hv // key_heads
    if (rows is None or hv % key_heads or rep * CHUNK > _LANE or dk % _LANE
            or dv % _LANE or W != 2 * key_heads * dk + hv * dv
            or (2 * key_heads * dk) % (rep * dv) or taps - 1 > _HALO):
        raise ValueError(f"qkv {qkv.shape}, {key_heads} key heads of {dk}, "
                         f"state {state.shape}, {taps} taps")
    n_tile, L = rows // CHUNK, rep * CHUNK
    # a chunk's g (row 0) and beta (row 1), a group's heads side by side,
    # filled up to 8 rows: [B, key heads, chunks, 8, L]
    gb = jnp.stack([a.astype(F32).reshape(B, T // CHUNK, CHUNK, key_heads, rep)
                    for a in (g, beta)], axis=3)      # [B, n, C, 2, hk, rep]
    gb = jnp.transpose(gb, (0, 4, 1, 3, 5, 2)).reshape(
        B, key_heads, T // CHUNK, 2, L)
    gb = jnp.pad(gb, [(0, 0)] * 3 + [(0, 6), (0, 0)])
    tail = jnp.pad(tail, [(0, 0), (_HALO - (taps - 1), 0), (0, 0)])
    v_at = 2 * key_heads * dk // (rep * dv)  # the values' first column block

    def columns(width, first):
        """(block of ``qkv``, of ``conv_w``, of the tail) at a group's
        ``width`` columns from column block ``first`` on."""
        return (pl.BlockSpec((1, rows, width),
                             lambda b, h, t: (b, t, first + h)),
                pl.BlockSpec((taps, width), lambda b, h, t: (0, first + h)),
                pl.BlockSpec((1, _HALO, width),
                             lambda b, h, t: (b, 0, first + h)))

    cols = [columns(dk, 0), columns(dk, key_heads), columns(rep * dv, v_at)]
    states = pl.BlockSpec((1, rep, dk, dv), lambda b, h, t: (b, h, 0, 0))
    in_specs = [c[0] for c in cols] + [c[1] for c in cols] + [
        pl.BlockSpec((1, 1, n_tile, 8, L), lambda b, h, t: (b, h, t, 0, 0)),
        states] + [c[2] for c in cols]
    out_specs = [pl.BlockSpec((1, rep, rows, dv),
                              lambda b, h, t: (b, h, t, 0)), states]
    cd = qkv.dtype
    scratch = [((_HALO + rows, dk), F32), ((_HALO + rows, dk), F32),
               ((_HALO + rows, rep * dv), F32),     # raw rows behind a halo
               ((rows, dk), cd), ((rows, dk), cd), ((rows, rep * dv), cd),
               ((rep, dk, dv), F32),                # the states
               ((n_tile, L, dv), cd), ((n_tile, L, dk), cd),
               ((n_tile, L, L), cd), ((n_tile, L, dk), cd),
               ((n_tile, L, dk), cd), ((n_tile, L, 8), F32)]

    def held(shape, dtype):  # bytes in VMEM: the last dimension whole lanes
        return math.prod(shape[:-1]) * -(-shape[-1] // _LANE) * _LANE \
            * jnp.dtype(dtype).itemsize

    # every block twice (the pipeline's double buffering; each counted as
    # float32, the widest of them) and the scratch once
    buffers = 2 * sum(held(spec.block_shape, F32)
                      for spec in in_specs + out_specs) \
        + sum(held(*one) for one in scratch)
    o, state = pl.pallas_call(
        functools.partial(_kernel, taps=taps, rep=rep, dk=dk, dv=dv,
                          scale=dk ** -0.5),
        grid=(B, key_heads, T // rows),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((B, hv, T, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        scratch_shapes=[pltpu.VMEM(*one) for one in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=buffers + _SPARE_VMEM),
        interpret=interpret,
        name="gdn_prefill",
    )(qkv, qkv, qkv, conv_w, conv_w, conv_w, gb, state.astype(F32),
      tail, tail, tail)
    # a head's rows lie together: what reads ``o`` a head (the gated norm)
    # takes the swap as a layout and not as a copy (rows of all heads side by
    # side, ``[B, T, value heads x value dim]``, cost a float32 copy of 537
    # MB a layer at 32,768 positions and as much of the peak; PERF.md, PR 46)
    return jnp.swapaxes(o, 1, 2), state
