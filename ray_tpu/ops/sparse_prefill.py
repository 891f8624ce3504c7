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
COUNTING, no sort, in as many passes over the row as its scores need. A row
holds a bracket, ``lo`` that at least ``topk`` keys reach and ``hi`` that
fewer reach, with both counts. The first pass reads the row's own smallest
and largest key (the bracket's first ends: their counts are known from the
positions). A pass then counts the keys at or above one candidate a row,
strictly inside its bracket: placed where a normal curve through the two
counts would meet ``topk``, between the floats the ends stand for (a side
that stays twice running weighs half; a row whose counts stay far apart
after four candidates is placed between the order keys instead, and past
``SELECT_FREE_STEPS`` every other candidate is the bracket's middle, which
bounds the search whatever the scores: :func:`select_pass_cap`). A row
STOPS on a count of exactly ``topk`` (``key >= candidate`` is its selection,
no tie to break) or on a bracket one key wide (the key is ``lo``, and how
many of its ties are taken is known from ``hi``'s count). When every row
still searching is ONE key from the end (``topk - 1`` reach ``hi``, or
``topk + 1`` ``lo``: where two neighbouring scores lie close, candidates
fall between them only by luck, and the unluckiest of a block's 128 rows
would hold the others for eight more passes), one pass reads that key
itself, the largest under ``hi`` or the smallest at ``lo``, and the count
after it ends the row. The loop ends when the block's 128 rows have all
stopped; a block whose rows see no more than ``topk`` keys runs no pass.
Some 13 passes a block on the benchmark's scores, 34 fixed before (my chip
runs, PR 51); the passes a block ran are the call's second output
(:func:`index_select_passes`). ``models/llama.py select_top`` is the same
RULE in XLA, by a fixed walk over the 32 bits, and the oracle: ties at the
last place go to the LOWER positions, found by a bisection over positions
that runs only where a block has a row that stopped on such a tie. Rows
that see no more than ``topk`` keys select all of them.

:func:`masked_flash` is flash attention under that mask. A KV group's query
heads are stacked into the rows of one score product, so the mask tile is
read once a group and not once a head; a group's whole keys and values lie
in VMEM while its query blocks go by (``ops/flash_prefill.py``'s layout),
and a query block's loop over key blocks ends at its diagonal. A grid
step holds up to 1,024 stacked rows whatever the group's size
(:func:`flash_step`): one query block of a group of eight heads, eight
consecutive query blocks where a key head has ONE query head. The mask
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
INT_MAX = 2 ** 31 - 1
_LANE = 128
SELECT_BLOCK_Q = 128   # queries a grid step of index_select
SELECT_BLOCK_K = 512   # keys a loop step of index_select
# index_select's search places a row's candidates freely for this many steps
# (by then a row of bell-shaped scores has long stopped); from there on every
# other step halves the bracket, so a 32-bit bracket closes in 64 more
SELECT_FREE_STEPS = 12
# from this step on, a row whose bracket still holds more than this many
# keys has its candidates placed between the order keys, not the floats
SELECT_STUCK = (4, 256)
# a row whose counts stood still for this many candidates running is taken
# to stand in a run of equal keys
SELECT_STILL = 3
SELECT_EDGES = 4   # the most edge passes of a query block
FLASH_BLOCK_Q = 128    # queries a mask tile holds
FLASH_BLOCK_K = 512    # keys a mask tile holds; masked_flash takes
# this many tiles (1,024 keys) a loop step: what a step costs beside its
# products (the running maximum and sum of its stacked rows, a lane each, and
# the rescaling) is paid once for twice the keys: 67.3 ms against 126.7 at
# 32,768 positions, 18.6 against 33.4 at 16,384; four tiles a step 72.8 and
# 20.5 (32 heads on 4 of 128; my chip run, PR 40)
FLASH_TILES = 2
FLASH_ROWS = 1024      # stacked query rows a grid step of masked_flash holds
# what a call of masked_flash asks of the chip's 128 MiB of VMEM at most:
# 1,024 rows at 16,384 positions and width 256 ask 100 MiB and run (my chip
# runs, PR 64)
FLASH_VMEM = 104 * 2 ** 20


def _lanes(n: int) -> int:
    return -(-n // _LANE) * _LANE


# the most passes of a search: the one for the rows' range, the free steps,
# two a halving of a 32-bit bracket, the edge passes and the halving each
# may displace
SELECT_SEARCH_PASSES = 1 + SELECT_FREE_STEPS + 64 + 3 * SELECT_EDGES


def select_pass_cap(keys: int) -> int:
    """The most passes a query block of :func:`index_select` can run over
    ``keys`` key positions: its search's, and a bit a position for the
    last tie's."""
    return SELECT_SEARCH_PASSES + max(1, (keys - 1).bit_length())


# --- the search ------------------------------------------------------------- #
# A query block's threshold search, from its row of order keys in VMEM to
# each row's last chosen key. It is ONE jitted function of the key scratch
# (a ref, whose shape no page count changes: ``SELECT_KEY_BLOCKS``) and two
# small arrays, so it is one equation of the kernel's body (Mosaic lowers
# the call in place) that is traced once a process. Written into the body
# it was 600 equations more than the fixed walk, every prefill program's
# trace paid for them (a serving process traces the kernel for thirteen page
# counts) and warm set-up grew by 19 s of 66 (my chip runs, PR 51).

SELECT_KEY_BLOCKS = 64   # key blocks of the scratch, at least: 32,768 keys
_STOP, _EDGE, _COUNT = 0, 1, 2   # a block's next pass


def _probit(p):
    """The normal quantile of ``p`` in (0, 1) to 4.5e-4 (Abramowitz and
    Stegun 26.2.23): what places a candidate, never what decides."""
    t = jnp.sqrt(-2.0 * jnp.log(jnp.minimum(p, 1.0 - p)))
    x = t - (2.515517 + t * (0.802853 + t * 0.010328)) / (
        1.0 + t * (1.432788 + t * (0.189269 + t * 0.001308)))
    return jnp.where(p < 0.5, -x, x)


def _order_key(x):
    """int32 keys that order as the float32 ``x`` do."""
    bits = pltpu.bitcast(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _float_of(key):
    """The float32 an order key stands for."""
    return pltpu.bitcast(
        jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key), jnp.float32)


def _own(n):   # lane r of a row of the search's numbers is query row r's
    return jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)


def _along(x, how):
    """[blk_q, 128] -> [1, blk_q]: a row's lanes reduced by ``how``."""
    return jnp.sum(jnp.where(_own(x.shape[0]),
                             how(x, axis=-1, keepdims=True), 0),
                   axis=0, keepdims=True)


def _down(x, lanes=_LANE):
    """[1, blk_q] -> [blk_q, lanes]: a row's number in every lane of the
    row, as a pass compares it (compared as ``[blk_q, 1]`` it is spread
    anew for every register of keys, and the pass takes twice as long: my
    chip run, PR 51)."""
    n = x.shape[1]
    return jnp.broadcast_to(
        jnp.sum(jnp.where(_own(n), x, 0), axis=-1, keepdims=True),
        (n, lanes))


def _chunks(blk_k):   # a key block's lane-wide pieces
    return [slice(c * _LANE, (c + 1) * _LANE) for c in range(blk_k // _LANE)]


def _fold(key_ref, n_vis, body, init):
    """A pass: ``body(128 keys a row, carry)`` over every lane-wide chunk of
    the ``n_vis`` key blocks a row of the block may see."""
    def block(j, carry):
        key = key_ref[j]
        for c in _chunks(key.shape[1]):
            carry = body(key[:, c], carry)
        return carry

    return jax.lax.fori_loop(0, n_vis, block, init)


@functools.partial(jax.jit, static_argnames=("topk",))
def _search(key_ref, seeing, n_vis, *, topk):
    """``(tau, n_hi, sure, passes)`` of a query block: ``key_ref`` [blocks,
    blk_q, blk_k] its rows' order keys (INT_MIN: a key the row does not
    see) in the first ``n_vis`` blocks, ``seeing`` [1, blk_q] the keys each
    row sees. Every key above ``tau`` is chosen; of those AT ``tau`` none
    where ``sure``, else the ``topk - n_hi`` at the lowest positions.

    A row's bracket: at least topk keys reach ``lo`` (``n_lo`` of them),
    fewer reach ``hi`` (``n_hi``); ``still``: the counts running that moved
    neither count; ``forced``: the next candidate, where ``pending``;
    ``g_lo``, ``g_hi``, ``last``: the Illinois rule's weights and the side
    that moved last; ``n``: the passes so far; ``kind``: the next pass.
    The bracket starts at the whole range of int32, which the first pass
    draws in to the row's own smallest and largest key. Flags are int32: a
    loop carries no vector of booleans."""
    i32, f32 = jnp.int32, jnp.float32
    blk_q = key_ref.shape[1]
    lanes = (blk_q, _LANE)
    fold = functools.partial(_fold, key_ref, n_vis)
    odds = seeing.astype(f32) + 1.0
    aim = _probit(topk / odds)
    free, (stuck_at, stuck_keys) = SELECT_FREE_STEPS, SELECT_STUCK
    zeros = jnp.zeros((1, blk_q), i32)
    ones = jnp.ones((1, blk_q), f32)

    def after(s, **new):
        """``s`` with what a pass found, closed and with its next pass. A
        row whose bracket is one key wide has found it (``tau`` is ``lo``),
        and other keys reach it than it needs. The next pass is a count
        while any row's search is open; an edge pass once every row still
        live is one key from the end (topk - 1 keys reach ``hi``, or topk
        + 1 ``lo``) or stands still, as a row does whose last place lies
        in a run of equal keys: the key at the bracket's edge is then the
        row's last, and the count after it says so. A call makes
        ``SELECT_EDGES`` such passes at most."""
        s = dict(s, **new)
        was_live = s["stopped"] == 0   # a count's exact rows are not
        stopped = s["stopped"] | (s["hi"] - s["lo"] == 1)
        near = ((s["n_lo"] == topk + 1) | (s["n_hi"] == topk - 1)
                | (s["still"] >= SELECT_STILL)) & (s["pending"] == 0)
        kind = jnp.max(jnp.where(stopped == 0,
                                 jnp.where(near, _EDGE, _COUNT), _STOP))
        return dict(
            s, stopped=stopped, tau=jnp.where(was_live, s["lo"], s["tau"]),
            n=s["n"] + 1, kind=jnp.where(
                (kind == _EDGE) & (s["edges"] >= SELECT_EDGES), _COUNT, kind))

    def span(s):
        """The rows' largest and smallest key, a lane at a time."""
        top, low = fold(
            lambda key, c: (jnp.maximum(c[0], key), jnp.minimum(
                c[1], jnp.where(key == INT_MIN, INT_MAX, key))),
            (jnp.full(lanes, INT_MIN, i32), jnp.full(lanes, INT_MAX, i32)))
        top = _along(top, jnp.max)  # INT_MAX: a NaN's key
        return after(s, lo=_along(low, jnp.min),
                     hi=jnp.where(top == INT_MAX, top, top + 1))

    def edge(s):
        """The largest key under ``hi`` where topk - 1 keys reach ``hi``,
        else the smallest at or above ``lo`` (as the same maximum, over the
        keys' complements): no key lies between it and that end, and the
        next count is at it (above it, from ``lo``)."""
        lo, hi = s["lo"], s["hi"]
        above = s["n_hi"] == topk - 1
        flip = jnp.where(above, 0, -1)
        turn, bound = _down(flip), _down(jnp.where(above, hi, -lo))

        def body(key, acc):
            key = key ^ turn
            return jnp.maximum(acc, jnp.where(key < bound, key, INT_MIN))

        key = _along(fold(body, jnp.full(lanes, INT_MIN, i32)),
                     jnp.max) ^ flip
        live = s["stopped"] == 0
        return after(
            s, lo=jnp.where(live & ~above, key, lo),
            hi=jnp.where(live & above, key + 1, hi), still=zeros,
            edges=s["edges"] + 1, forced=jnp.where(above, key, key + 1),
            pending=live.astype(i32))

    def step(s):
        lo, hi, n_lo, n_hi, n = s["lo"], s["hi"], s["n_lo"], s["n_hi"], s["n"]
        width = hi - lo   # of 32 bits without a sign
        # where a normal curve through the two counts would meet topk,
        # between the FLOATS the bracket stands for; a side that stayed
        # twice running weighs half (the Illinois rule)
        f_lo = (_probit((n_lo.astype(f32) + 0.5) / odds) - aim) * s["g_lo"]
        f_hi = (aim - _probit((n_hi.astype(f32) + 0.5) / odds)) * s["g_hi"]
        part = f_lo / (f_lo + f_hi)
        x_lo, x_hi = _float_of(lo), _float_of(hi)
        placed = _order_key(x_lo + (x_hi - x_lo) * part)
        # a row whose counts stay far apart is no bell: between the KEYS
        by_key = (s["by_key"] != 0) | (
            (n > stuck_at) & (n_lo - n_hi > stuck_keys))
        quarter = jax.lax.shift_right_logical(width, 2).astype(f32)
        placed = jnp.where(by_key, lo + 4 * (quarter * part).astype(i32),
                           placed)
        # past the free steps every other one halves the bracket, and so
        # does a row that stood still twice
        halve = ((n > free) & ((n - free) % 2 == 1)) | (s["still"] >= 2)
        cand = jnp.where(halve, lo + jax.lax.shift_right_logical(width, 1),
                         placed)
        cand = jnp.where(s["pending"] != 0, s["forced"], cand)
        cand = jnp.minimum(jnp.maximum(cand, lo + 1), hi - 1)
        at = _down(cand)
        got = _along(fold(lambda key, acc: acc + (key >= at).astype(i32),
                          jnp.zeros(lanes, i32)), jnp.sum)
        up = got >= topk
        live = s["stopped"] == 0
        exact = live & (got == topk)   # key >= cand IS the selection: tau
        # is the key under cand, and ``after`` leaves a stopped row's alone
        moved = got != jnp.where(up, n_lo, n_hi)
        return after(
            s, lo=jnp.where(live & up, cand, lo),
            hi=jnp.where(live & ~up, cand, hi),
            n_lo=jnp.where(live & up, got, n_lo),
            n_hi=jnp.where(live & ~up, got, n_hi),
            sure=s["sure"] | exact, stopped=s["stopped"] | exact,
            tau=jnp.where(exact, cand - 1, s["tau"]), pending=zeros,
            still=jnp.where(moved, 0, s["still"] + 1),
            g_lo=jnp.where(up, 1.0, jnp.where(
                s["last"] == -1, s["g_lo"] * 0.5, s["g_lo"])),
            g_hi=jnp.where(up, jnp.where(
                s["last"] == 1, s["g_hi"] * 0.5, s["g_hi"]), 1.0),
            last=jnp.where(up, 1, -1), by_key=by_key.astype(i32))

    # a row that sees no more than topk keys takes them all
    whole = (seeing <= topk).astype(i32)
    s = jax.lax.while_loop(
        lambda s: (s["kind"] != _STOP) & (s["n"] < SELECT_SEARCH_PASSES),
        lambda s: jax.lax.cond(
            s["n"] == 0, span,
            lambda s: jax.lax.cond(s["kind"] == _EDGE, edge, step, s), s),
        {"lo": zeros + (INT_MIN + 1), "hi": zeros + INT_MAX,
         "n_lo": seeing, "n_hi": zeros, "stopped": whole,
         "tau": zeros + INT_MIN, "sure": whole, "still": zeros,
         "forced": zeros, "pending": zeros, "g_lo": ones, "g_hi": ones,
         "last": zeros, "by_key": zeros, "n": i32(0), "edges": i32(0),
         # the first pass is the range's, whatever the kind
         "kind": jnp.where(jnp.min(whole) == 0, _COUNT, _STOP)})
    return s["tau"], s["n_hi"], s["sure"], s["n"]


@functools.partial(jax.jit, static_argnames=("bits",))
def _last_tie(key_ref, tau, need, sure, n_vis, *, bits):
    """``cut`` [1, blk_q]: a row takes its keys AT ``tau`` at positions up
    to ``cut``, which are the ``need`` at the LOWEST positions; none of
    them where ``sure`` (``cut`` -1). A bisection over ``bits`` bits of
    position, a count a bit."""
    i32 = jnp.int32
    blk_q, blk_k = key_ref.shape[1:]
    cols = jax.lax.broadcasted_iota(i32, (blk_q, blk_k), 1)
    at = _down(tau, blk_k)

    def index_bit(b, cut):  # the largest with fewer than need under it
        cand = cut | jnp.left_shift(i32(1), bits - 1 - b)
        end = _down(cand, blk_k)

        def block(j, acc):
            under = ((key_ref[j] == at)
                     & (cols + j * blk_k < end)).astype(i32)
            return acc + sum(under[:, c] for c in _chunks(blk_k))

        under = _along(jax.lax.fori_loop(
            0, n_vis, block, jnp.zeros((blk_q, _LANE), i32)), jnp.sum)
        return jnp.where(under < need, cand, cut)

    return jnp.where(sure != 0, -1, jax.lax.fori_loop(
        0, bits, index_bit, jnp.zeros_like(tau)))


def _select_kernel(q_ref, w_ref, kt_ref, o_ref, n_ref, key_ref, *, heads,
                   blk_q, blk_k, topk, n_blocks):
    """q (1,1,heads*blk_q,Di) head-major rows; w (1,1,heads*blk_q,1) f32;
    kt (1,n_blocks,Di,blk_k) the keys transposed, a key block a slab; o
    (1,1,n_blocks,blk_q,blk_k) int8; n (1,1,1,128) int32, the passes this
    block ran; scratch: the row's order keys (at least n_blocks,blk_q,blk_k)
    int32. A key block is a LEADING index everywhere: a dynamic offset
    along lanes is not. The body scores and writes; the search between
    them is :func:`_search`'s."""
    i32, f32 = jnp.int32, jnp.float32
    i = pl.program_id(1)
    q = q_ref[0, 0]
    w = w_ref[0, 0]
    n_vis = ((i + 1) * blk_q - 1) // blk_k + 1   # key blocks a row may see
    # query position - key position inside a pair of blocks with one number
    ahead = jax.lax.broadcasted_iota(i32, (blk_q, blk_k), 0) \
        - jax.lax.broadcasted_iota(i32, (blk_q, blk_k), 1)

    def score(j, carry):
        s = jax.lax.dot_general(q, kt_ref[0, j],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
        s = jnp.maximum(s, 0.0) * w
        total = s[0:blk_q]
        for h in range(1, heads):
            total = total + s[h * blk_q:(h + 1) * blk_q]
        seen = ahead + (i * blk_q - j * blk_k) >= 0
        key_ref[j] = jnp.where(seen, _order_key(total), i32(INT_MIN))
        return carry

    jax.lax.fori_loop(0, n_vis, score, 0)

    seeing = i * blk_q + 1 + jax.lax.broadcasted_iota(i32, (1, blk_q), 1)
    tau, n_hi, sure, n = _search(key_ref, seeing, n_vis, topk=topk)
    # a row that is not sure stopped on a key that more keys reach than it
    # needs (topk - n_hi): the LOWER positions take the last places
    tied = jnp.min(sure) == 0
    bits = max(1, (n_blocks * blk_k - 1).bit_length())
    at = _down(tau, blk_k)

    def write(chosen):
        def body(j, carry):
            o_ref[0, 0, j] = jnp.where(chosen(key_ref[j], j * blk_k), 1,
                                       0).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, n_vis, body, 0)

    def plain():
        write(lambda key, first: key > at)

    def with_ties():
        cut = _down(_last_tie(key_ref, tau, topk - n_hi, sure, n_vis,
                              bits=bits), blk_k)
        cols = jax.lax.broadcasted_iota(i32, (blk_q, blk_k), 1)
        write(lambda key, first: (key > at)
              | ((key == at) & (cols + first <= cut)))

    jax.lax.cond(tied, with_ties, plain)

    def blank(j, carry):
        o_ref[0, 0, j] = jnp.zeros((blk_q, blk_k), o_ref.dtype)
        return carry

    jax.lax.fori_loop(n_vis, n_blocks, blank, 0)
    n_ref[0, 0] = jnp.broadcast_to(n + jnp.where(tied, bits, 0), (1, _LANE))


def _index_select(qi, ki, w, topk: int, interpret: bool):
    """The one call: ``(mask tiles, passes [B, T / 128])``."""
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
    # the key scratch is no shorter than SELECT_KEY_BLOCKS whatever the
    # positions, so that every page count's search is one traced function
    held = max(nk, SELECT_KEY_BLOCKS)
    # the blocks twice (double buffering), the row's keys, its range a lane
    # (two accumulators), and the kernel's own tiles: the stacked scores
    # [H * blk_q, blk_k] float32 a few times
    vmem = (2 * (H * blk_q * _lanes(D) * item + H * blk_q * _LANE * 4
                 + D * Tk * item + blk_q * Tk + _LANE * 4)
            + held * blk_q * blk_k * 4 + 2 * blk_q * _LANE * 4
            + 6 * H * blk_q * blk_k * 4)
    mask, passes = pl.pallas_call(
        kernel,
        grid=(B, nq),
        in_specs=[
            pl.BlockSpec((1, 1, H * blk_q, D), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, H * blk_q, 1), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, nk, D, blk_k), lambda b, i: (b, 0, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, 1, nk, blk_q, blk_k),
                                lambda b, i: (b, i, 0, 0, 0)),
                   pl.BlockSpec((1, 1, 1, _LANE), lambda b, i: (b, i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((B, nq, nk, blk_q, blk_k), jnp.int8),
            jax.ShapeDtypeStruct((B, nq, 1, _LANE), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((held, blk_q, blk_k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
        name="dsa_index_select",
    )(q, ws, kt)
    return mask, passes[:, :, 0, 0]


def index_select(qi, ki, w, topk: int, *, interpret: bool = False):
    """The selection mask, int8, 1 where query ``t`` attends key ``s``, in
    tiles ``[B, T / 128, Tk / 512, 128, 512]`` (``Tk``: ``T`` filled up to
    whole key blocks; :func:`mask_rows` lays it out ``[B, T, Tk]``), as
    :func:`masked_flash` reads it: of the keys
    ``s <= t``, the ``topk`` of largest ``sum_j w[t, j] relu(qi[t, j] .
    ki[s])``, all of them while ``t + 1 <= topk``, a tie at the last place
    to the lower ``s``. ``qi`` [B, T, Hi, Di] and ``ki`` [B, T, Di] in one
    type, ``w`` [B, T, Hi] float32; 128 divides ``T``."""
    return _index_select(qi, ki, w, topk, interpret)[0]


def index_select_passes(qi, ki, w, topk: int, *, interpret: bool = False):
    """:func:`index_select`'s mask and, ``[B, T / 128]`` int32, the passes
    over its visible keys each query block's search ran (the same call:
    the served program drops the second output); at most
    :func:`select_pass_cap`."""
    return _index_select(qi, ki, w, topk, interpret)


def mask_tiles_shape(B: int, T: int) -> tuple:
    """The shape of :func:`index_select`'s tiles for ``T`` positions."""
    return (B, T // SELECT_BLOCK_Q, -(-T // SELECT_BLOCK_K), SELECT_BLOCK_Q,
            SELECT_BLOCK_K)


def mask_rows(mask):
    """:func:`index_select`'s tiles as ``[B, T, Tk]``."""
    B, nq, nk, blk_q, blk_k = mask.shape
    return jnp.swapaxes(mask, 2, 3).reshape(B, nq * blk_q, nk * blk_k)


def flash_step(q, k, v) -> dict:
    """What a grid step of :func:`masked_flash` holds for these operands
    (their shapes and type alone: ``rep`` query heads a key head, ``T``
    positions, head widths ``D`` / ``dv``): ``rows_a_step`` stacked query
    rows, ``rep`` heads of ``n`` consecutive query blocks, of
    ``heads_a_step`` key heads, and the ``vmem_bytes`` the call asks for.
    ``n`` is the largest of 8, 4, 2, 1 that keeps the rows within
    ``FLASH_ROWS``, divides the query blocks and asks no more than
    ``FLASH_VMEM`` (``n`` 1 asks what it asks). A product of 128 rows
    leaves the MXU loading a weight tile as long as it streams rows through
    it: with ONE query head a key head at width 256, 16,384 positions, 128
    rows a step 92.0 ms, 256: 73.4, 512: 69.1, 1,024: 67.1 (66.3 with the
    bias spread over the heads where it is added, as the body has it),
    which is what a group of eight heads takes for the same pairs at 32,768
    positions and width 128 (67.4); the mask's way to a bias is not the
    cost (taken out: 92.3 ms at 128 rows, 68.4 for 69.1 at 512), so ONE key
    head a step: two of 128 rows each run 83.7, two of 256 70.3; nor is the
    keys' transpose (handed over transposed: 92.2 and 68.7; my chip runs,
    PR 64). Up to
    eight consecutive query blocks that start on a multiple of their count
    end in the same loop step of 1,024 keys, so a step computes no tile its
    blocks did not compute alone."""
    T, D, dv = q.shape[1], q.shape[3], v.shape[3]
    rep, item = q.shape[2] // k.shape[2], jnp.dtype(q.dtype).itemsize
    blk_q, keys = FLASH_BLOCK_Q, FLASH_TILES * FLASH_BLOCK_K
    Tk = -(-T // keys) * keys

    def vmem(n):
        # q, o, a head's whole keys and values and the mask row twice
        # (double buffering), the scratch, and the kernel's own tiles: the
        # stacked scores [rows, keys] float32 a few times
        rows = n * rep * blk_q
        return (2 * (rows * _lanes(D) * item + Tk * _lanes(D) * item
                     + Tk * _lanes(dv) * item + n * blk_q * Tk
                     + rows * _lanes(dv) * item)
                + rows * (2 * _LANE + _lanes(dv)) * 4 + 8 * rows * keys * 4)

    n = next(n for n in (8, 4, 2, 1) if n == 1 or (
        n * rep * blk_q <= FLASH_ROWS and T // blk_q % n == 0
        and vmem(n) <= FLASH_VMEM))
    return {"rows_a_step": n * rep * blk_q, "heads_a_step": 1,
            "vmem_bytes": vmem(n)}


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale, rep, blk_q, blk_k, tiles):
    """q (1,1,1,rows,D): ``rows`` = n*rep*blk_q, a group's query heads
    stacked, head-major, a query block after another; k, v (1,1,Tk,D); mask
    (1,n,n_blocks,blk_q,blk_k) int8; o as q; scratch: running maximum and
    sum (rows,1), weighted values (rows,Dv), f32."""
    f32 = jnp.float32
    i = pl.program_id(2)
    q = q_ref[0, 0, 0]
    n = mask_ref.shape[1]
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    keys = tiles * blk_k

    def step(j, carry):
        at = pl.ds(pl.multiple_of(j * keys, keys), keys)
        s = jax.lax.dot_general(q, k_ref[0, 0, at, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        seen = [mask_ref[0, :, j * tiles + t] for t in range(tiles)]
        seen = (seen[0] if tiles == 1
                else jnp.concatenate(seen, axis=2)).astype(jnp.int32) != 0
        # a query block's bias under each of its heads' scores: spread over
        # the heads where it is added, never laid out ``rep`` times
        bias = jnp.where(seen, 0.0, NEG_INF)[:, None]
        s = (s.reshape(n, rep, blk_q, keys) + bias).reshape(s.shape)
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

    # up to the block that holds the step's last query's own position (the
    # mask holds zeros past an earlier block's diagonal); a row whose first
    # blocks hold none of its keys carries exp(0) sums until its first
    # chosen key rescales them by 0 (every row has chosen its own block's)
    jax.lax.fori_loop(0, ((i + 1) * n * blk_q - 1) // keys + 1, step, 0)
    o_ref[0, 0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def masked_flash(q, k, v, mask, *, interpret: bool = False):
    """Attention of ``q`` [B, T, H, D] over ``k`` / ``v`` [B, T, Hkv, D / Dv]
    (``Hkv`` divides ``H``; one type) under ``mask`` (:func:`index_select`'s
    tiles: non-zero where query ``t`` attends key ``s``,
    nothing beyond ``s = t``; every row attends at least one key of its own
    key block or an earlier one). Scale ``1 / sqrt(D)``, float32 scores and
    softmax. A grid step is :func:`flash_step`'s, chosen from the operands'
    shapes. Returns [B, T, H, Dv] in the operands' type."""
    B, T, H, D = q.shape
    G, dv = k.shape[2], v.shape[3]
    rep = H // G
    blk_q, blk_k, tiles = FLASH_BLOCK_Q, FLASH_BLOCK_K, FLASH_TILES
    if T % blk_q or H % G or mask.shape != mask_tiles_shape(B, T):
        raise ValueError(f"q {q.shape}, k {k.shape}, mask {mask.shape}")
    nq, nk = mask.shape[1:3]
    if nk % tiles:  # whole loop steps: tiles of zeros, never seen
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, -nk % tiles), (0, 0),
                              (0, 0)))
        nk = mask.shape[2]
    Tk = nk * blk_k
    took = flash_step(q, k, v)
    rows = took["rows_a_step"]
    n = rows // (rep * blk_q)   # query blocks a grid step

    def stacked(a):  # [B, T, G * rep, d] -> [B, G, nq / n, n * rep * blk_q, d]
        d = a.shape[-1]
        a = a.reshape(B, nq, blk_q, G, rep, d)
        return jnp.transpose(a, (0, 3, 1, 4, 2, 5)).reshape(
            B, G, nq // n, rows, d)

    def keys(a):  # [B, T, G, d] -> [B, G, Tk, d]
        return jnp.swapaxes(jnp.pad(
            a, ((0, 0), (0, Tk - T), (0, 0), (0, 0))), 1, 2)

    kernel = functools.partial(_flash_kernel, scale=1.0 / math.sqrt(D),
                               rep=rep, blk_q=blk_q, blk_k=blk_k,
                               tiles=tiles)
    o = pl.pallas_call(
        kernel,
        grid=(B, G, nq // n),
        in_specs=[
            pl.BlockSpec((1, 1, 1, rows, D), lambda b, g, i: (b, g, i, 0, 0)),
            pl.BlockSpec((1, 1, Tk, D), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, Tk, dv), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, n, nk, blk_q, blk_k),
                         lambda b, g, i: (b, i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, rows, dv),
                               lambda b, g, i: (b, g, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, nq // n, rows, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=int(took["vmem_bytes"])),
        interpret=interpret,
        name="dsa_masked_flash",
    )(stacked(q), keys(k), keys(v), mask)
    # [B, G, nq, rep, blk_q, dv] -> [B, T, H, dv]
    o = o.reshape(B, G, nq, rep, blk_q, dv)
    return jnp.transpose(o, (0, 2, 4, 1, 3, 5)).reshape(B, T, H, dv)
