"""Pallas TPU gather-sum for the held experts' combine in the decode engine's
prefill: forward only, a chunk of sorted places in ONE call.

What ``ops/moe.py _held_chunks`` computed as a float32 ``[places, d]``
product written to HBM and XLA's float32 scatter-add onto ``[N, d]`` (which
sorts the call's indices, gathers the float32 rows in that order and adds
them row by row: 7-11 rows a microsecond on a v5e; PERF.md, PR 47)::

    y[t] += sum over the chunk's LIVE places p with token(p) == t
            of w[p] * float32(ys[p])

with no float32 row in HBM, no scatter and no sort.

- The places lie sorted by held expert and, within an expert, by token (the
  router's stable sort). So the rows a TOKEN TILE gets from ONE expert are a
  RUN of consecutive rows of ``ys``. Before the call, on numbers only
  (:func:`held_sum`): the table of those runs, ``[tiles, experts]`` first and
  last places, from one product of two one-hot matrices and a running sum.
- The grid walks the token tiles. ``ys`` stays in HBM in the compute type,
  as the grouped kernel wrote it. One row of a ``[c, d]`` array cannot be
  copied out of it (rows share tiles: the compiler refuses the slice), so a
  run is fetched in SLABS of ``group`` rows on whole tiles, the slabs its
  rows lie in (a run's first and last slab read on into rows the tile does
  not use; they are never added), into a ring of ``depth`` buffers that
  runs on from one tile into the next, the copies ``depth`` ahead of the
  adds. A place behind the live ones belongs to no run: its row, UNWRITTEN
  memory on the TPU, may ride along in a slab but never reaches an add
  (``inf * 0`` cannot happen).
- A slab that has landed is widened to float32 once; each live row of it
  is multiplied by its float32 weight and added in float32 onto its token's
  row of the tile on the VPU: the arithmetic ``_held_rows`` states, a
  token's rows in the order of their experts. The tile is read once, added
  to where it lies in VMEM and stored once (``y`` is aliased to the result).

No backward: a differentiated call never comes here
(``ops/moe.py _held_chunks_jvp``), and a train process never imports this
module.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
# what was measured (PERF.md, PR 49): rows a slab (whole tiles of a 2-byte
# type), copies in flight, the float32 bytes of a token tile of the sum
GROUP = 16
RING = 32
_TILE_BYTES = 8 * 2 ** 20
# a chunk's tokens and weights lie in scalar memory (1 MiB on a v5e, which
# the table of runs shares): the places a call may bring
MAX_PLACES = 96 * 1024
_SPARE_VMEM = 8 * 2 ** 20


def pick_tile(n_tokens: int, d: int):
    """Tokens a grid step: the largest power of two up to 1,024 that divides
    ``n_tokens`` and whose float32 tile is at most 8 MiB (1,024 at d 2,048,
    256 at 6,144: the longer a tile, the longer an expert's run of rows in
    it); None where that is under 8 or ``d`` is not whole lanes."""
    if d % _LANE:
        return None
    tile = 1024
    while tile >= 8 and (n_tokens % tile or 4 * tile * d > _TILE_BYTES):
        tile //= 2
    return tile if tile >= 8 else None


def _kernel(lo_ref, hi_ref, tok_ref, w_ref, y_ref, ys_ref, o_ref, ring, sem,
            wide, cur, *, tile, count, runs, depth, group):
    """lo, hi [runs + 1]: run ``tile * count + expert``'s places; tok, w [c];
    y, o (tile, d) float32; ys [c, d] in HBM; ring (depth, group, d) in ys'
    type; sem (depth,); wide (group, d) float32: the slab under the adds;
    cur: the run and the slab the next copy is, the copies issued, the
    copies waited for."""
    f32 = jnp.float32
    i = pl.program_id(0)

    def copy(q, n):  # slab ``q``: rows ``q * group`` on, whole tiles
        return pltpu.make_async_copy(
            ys_ref.at[pl.ds(pl.multiple_of(q * group, group), group), :],
            ring.at[n % depth], sem.at[n % depth])

    def settle(u, q):  # on to the first run from ``u`` on with a slab left
        return jax.lax.while_loop(
            lambda s: (s[0] < runs) & (hi_ref[s[0]] <= jnp.maximum(
                s[1] * group, lo_ref[s[0]])),
            lambda s: (s[0] + 1, lo_ref[s[0] + 1] // group), (u, q))

    def issue():
        u, q = cur[0], cur[1]

        @pl.when(u < runs)
        def _():
            copy(q, cur[2]).start()
            cur[2] = cur[2] + 1
            cur[0], cur[1] = settle(u, q + 1)

    @pl.when(i == 0)
    def _():
        cur[0], cur[1] = settle(0, lo_ref[0] // group)
        cur[2] = 0
        cur[3] = 0
        jax.lax.fori_loop(0, depth, lambda _, carry: issue(), None)

    o_ref[...] = y_ref[...]

    def run(e, n):
        lo, hi = lo_ref[i * count + e], hi_ref[i * count + e]
        slabs = jnp.where(hi > lo, (hi - 1) // group - lo // group + 1, 0)

        def piece(k, n):
            base = (lo // group + k) * group

            @pl.when(n > 0)  # the copy before is done with: its slot is free
            def _():
                issue()

            copy(0, n).wait()
            wide[...] = ring[n % depth].astype(f32)

            def add(p, _):
                at = tok_ref[p] - i * tile
                o_ref[pl.ds(at, 1), :] += w_ref[p] * wide[pl.ds(p - base, 1), :]

            jax.lax.fori_loop(jnp.maximum(lo, base),
                              jnp.minimum(hi, base + group), add, None)
            return n + 1

        return jax.lax.fori_loop(0, slabs, piece, n)

    # the tiles come in order and so do their runs: the ring runs on from
    # one grid step into the next
    cur[3] = jax.lax.fori_loop(0, count, run, cur[3])


# jitted: a program's layers that call it at one shape share ONE traced and
# lowered kernel, as ``grouped_ffn``'s do
@functools.partial(jax.jit, static_argnames=("tile", "depth", "group",
                                             "interpret"))
def held_sum(y, ys, token, w, edges, *, tile=None, depth: int = RING,
             group: int = GROUP, interpret: bool = False):
    """``y`` [N, d] float32 plus ``w[p] * float32(ys[p])`` on row
    ``token[p]`` for every place ``p`` of a held expert. ``ys`` [c, d] in a
    2- or 4-byte float type; ``edges`` [count + 1] int32: expert ``e`` holds
    places ``edges[e]`` to ``edges[e + 1]``, within which ``token`` [c]
    (int32, below ``N``) does not fall; ``w`` [c] float32. The rows from
    ``edges[-1]`` on are dead: never added. ``tile`` (:func:`pick_tile`)
    divides ``N``, ``group`` (whole tiles of ``ys``' type) ``c``."""
    (N, d), c = y.shape, ys.shape[0]
    tile = tile or pick_tile(N, d)
    if (tile is None or N % tile or d % _LANE or y.dtype != jnp.float32
            or ys.shape != (c, d) or c % group or not 0 < c <= MAX_PLACES
            or token.shape != (c,) or w.shape != (c,)):
        raise ValueError(f"sum {y.shape} {y.dtype}, rows {ys.shape} "
                         f"{ys.dtype}, tokens {token.shape}, weights "
                         f"{w.shape}, token tiles of {tile}, copies of "
                         f"{group} rows")
    tiles, count = N // tile, edges.shape[0] - 1
    edges = edges.astype(jnp.int32)
    place = jnp.arange(c, dtype=jnp.int32)
    token = token.astype(jnp.int32)
    # the places a tile gets from an expert: one product of two one-hot
    # matrices (0 / 1 in bfloat16, summed in float32: exact)
    of_tile = (token // tile)[:, None] == jnp.arange(tiles, dtype=jnp.int32)
    of_expert = ((place[:, None] >= edges[None, :-1])
                 & (place[:, None] < edges[None, 1:]))  # no dead place's
    n = jnp.dot(of_tile.astype(jnp.bfloat16).T, of_expert.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32).astype(jnp.int32)
    lo = edges[None, :-1] + jnp.cumsum(n, axis=0) - n  # [tiles, count]
    runs = tiles * count
    lo, hi = (jnp.pad(a.reshape(runs), (0, 1)) for a in (lo, lo + n))

    def tile_at(i, lo, hi, token, w):
        return i, 0

    item = ys.dtype.itemsize
    buffers = 2 * 2 * 4 * tile * d + depth * group * d * item + 4 * group * d
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, count=count, runs=runs,
                          depth=depth, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles,),
            in_specs=[pl.BlockSpec((tile, d), tile_at),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), tile_at),
            scratch_shapes=[pltpu.VMEM((depth, group, d), ys.dtype),
                            pltpu.SemaphoreType.DMA((depth,)),
                            pltpu.VMEM((group, d), jnp.float32),
                            pltpu.SMEM((4,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((N, d), jnp.float32),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=buffers + _SPARE_VMEM),
        interpret=interpret,
        name="held_sum",
    )(lo, hi, token, w.astype(jnp.float32), y, ys)
