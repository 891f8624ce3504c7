"""Pallas TPU grouped feed-forward for the decode engine's prefill: forward
only, the gated experts' three products on rows sorted by expert in ONE call.

What ``ops/moe.py _expert_ffn`` computes as three ``jax.lax.ragged_dot``
products with float32 results (each written whole and rounded by an operation
of its own) computed inside one kernel, with ``_expert_ffn``'s arithmetic and
its rounding points: gate and up products in the rows' type with float32
accumulation, both rounded to that type, the gate function in float32, their
product rounded once, the down product accumulated in float32 and rounded
once at the store.

- The weights are a kind's whole stacked leaves ``[L, count, d, f]`` and are
  read where they lie: a weight block's index map is ``(layer, group of this
  grid step)``, both scalar-prefetch operands. No reshape to ``L * count``
  groups, no copy of a layer.
- Row tiles are walked by group metadata made from ``counts``
  (:func:`visits`): one grid step a (group, row tile) pair in which the
  group has rows; a tile a group boundary cuts is visited once a group and
  stored under a mask; a group without rows and a tile behind ``sum(counts)``
  are never visited (the grid's length is the number of visits, a traced
  value), so the rows of no group stay UNWRITTEN as XLA's kernel leaves them.
  While consecutive steps stay in one group its weights stay in VMEM (Pallas
  skips a copy whose block index stands still), and the next group's arrive
  behind the running step.
- Gate, up, activation and down in one pass over a row tile: the rows are
  read once, ``g``, ``u`` and ``a`` never leave VMEM. An expert's three
  matrices lie whole in VMEM where they fit :data:`_WEIGHTS_VMEM` twice
  buffered (at d 2,560 and f 768 11.8 MB, twice); a wider expert goes by in
  column blocks of ``f`` (:func:`pick_columns`), the down product summed over
  them in a float32 scratch tile.

No backward kernel: ``ops/moe.py`` gives the call ``_grouped_dot``'s
transposes. The trainer's step never comes here (its layers reach
``_expert_ffn`` without a stack) and a train process never imports this
module.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
ROW_TILE = 256  # rows a grid step (PERF.md, PR 43: what was measured)
# an expert's three column blocks, twice buffered, may take this much VMEM
_WEIGHTS_VMEM = 48 * 2 ** 20
_SPARE_VMEM = 8 * 2 ** 20


def pick_columns(d: int, f: int, itemsize: int) -> Optional[int]:
    """Columns of ``f`` a grid step takes of an expert's matrices: ``f``
    whole where the three blocks fit :data:`_WEIGHTS_VMEM` twice buffered,
    else its largest divisor of whole lanes that does; None where ``d`` or
    ``f`` are not whole lanes."""
    if d % _LANE or f % _LANE:
        return None
    for n in range(f // _LANE, 0, -1):
        cols = n * _LANE
        if f % cols == 0 and 2 * 3 * d * cols * itemsize <= _WEIGHTS_VMEM:
            return cols
    return None


def visits(counts, rows: int, tile: int):
    """The grid of :func:`grouped_ffn` from ``counts`` [G] (rows a group,
    the groups' rows one behind the other from row 0): ``(group, row_tile)``
    of each visit, both [V] with ``V = tiles + G - 1`` (every boundary
    between groups can cut one tile), ``edges`` [G + 1] (group ``g`` holds
    rows ``edges[g]`` to ``edges[g + 1]``) and the number of visits that
    have rows. The places behind that number hold a valid pair and are
    never run. Made of comparisons over ``[V, G]`` and sums alone: a gather
    from a table of ``G`` (``first[group]``, ``jnp.repeat``) is what the
    TPU compiler unrolls into ``G`` slices and selects a table from 321
    places on, 5 MB more of every prefill program from 11 pages on and 0.5 s
    more to load it from the compile cache (PERF.md, PR 43)."""
    G = counts.shape[0]
    tiles = -(-rows // tile)
    V = tiles + G - 1
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    first = (ends - counts) // tile  # the tile a group's first row lies in
    n = jnp.where(counts > 0, -(-ends // tile) - first, 0)
    done = jnp.cumsum(n)  # visits up to a group's last
    v = jnp.arange(V, dtype=jnp.int32)
    # a visit's group: as many groups as have all their visits before it
    group = jnp.minimum(
        (done[None, :] <= v[:, None]).sum(1, dtype=jnp.int32), G - 1)
    mine = group[:, None] == jnp.arange(G, dtype=jnp.int32)[None, :]
    # the group's first tile, and on from it by the visits since its first
    row_tile = v + jnp.where(mine, (first - (done - n))[None, :], 0).sum(
        1, dtype=jnp.int32)
    edges = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return group, jnp.clip(row_tile, 0, tiles - 1), edges, done[-1]


def _kernel(layer_ref, group_ref, tile_ref, edges_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref, *acc, tile, act, steps):
    """x (tile, d); w_gate, w_up (d, cols); w_down (cols, d); o (tile, d);
    with column blocks a float32 (tile, d) scratch for the down product."""
    del layer_ref
    f32 = jnp.float32
    v, j = pl.program_id(0), pl.program_id(1)
    x = x_ref[...]
    cd = x.dtype
    g = jnp.dot(x, wg_ref[...], preferred_element_type=f32).astype(cd)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=f32).astype(cd)
    gate = jax.nn.relu if act == "reglu" else jax.nn.silu
    a = (gate(g.astype(f32)) * u.astype(f32)).astype(cd)
    y = jnp.dot(a, wd_ref[...], preferred_element_type=f32)

    def store(y):
        # the rows of this tile that are the group's; the others keep what
        # an earlier visit of the tile wrote, or nothing
        grp = group_ref[v]
        row = tile_ref[v] * tile + jax.lax.broadcasted_iota(
            jnp.int32, (tile, 1), 0)
        mine = (row >= edges_ref[grp]) & (row < edges_ref[grp + 1])
        o_ref[...] = jnp.where(mine, y, o_ref[...].astype(f32)).astype(
            o_ref.dtype)

    if steps == 1:
        store(y)
        return
    acc, = acc

    @pl.when(j == 0)
    def _():
        acc[...] = y

    @pl.when(j > 0)
    def _():
        acc[...] += y

    @pl.when(j == steps - 1)
    def _():
        store(acc[...])


# jitted: a program's layers (and the held path's blocks of places) that call
# it at one shape share ONE traced and lowered kernel (PERF.md, PR 43)
@functools.partial(jax.jit,
                   static_argnames=("act", "tile", "columns", "interpret"))
def grouped_ffn(xs, w_gate, w_up, w_down, counts, layer, *,
                act: str = "swiglu", tile: Optional[int] = None,
                columns: Optional[int] = None, interpret: bool = False):
    """``act(xs W_gate) * (xs W_up) W_down`` a group, in ``xs``' type: ``xs``
    [A, d] rows sorted by group (``counts`` [count] rows a group, from row 0
    on), ``w_gate`` / ``w_up`` [L, count, d, f] and ``w_down`` [L, count, f,
    d] in ``xs``' type, of which layer ``layer``'s (a number or traced) are
    read. ``act``: ``"swiglu"`` (silu) or ``"reglu"`` (relu). Rows behind
    ``sum(counts)`` come back unwritten. ``tile`` (:data:`ROW_TILE`) need not
    divide ``A``; ``columns`` (:func:`pick_columns`) must divide ``f``."""
    (A, d), (L, count, _, f) = xs.shape, w_up.shape
    tile = tile or ROW_TILE
    cd = xs.dtype
    cols = columns or pick_columns(d, f, cd.itemsize)
    if cols is None or f % cols or w_gate.shape != (L, count, d, f) \
            or w_down.shape != (L, count, f, d) \
            or {w.dtype for w in (w_gate, w_up, w_down)} != {cd}:
        raise ValueError(f"rows {xs.shape} {cd}, w_gate {w_gate.shape} "
                         f"{w_gate.dtype}, w_up {w_up.shape} {w_up.dtype}, "
                         f"w_down {w_down.shape} {w_down.dtype}, column "
                         f"blocks of {cols}")
    steps = f // cols
    group, row_tile, edges, n = visits(counts, A, tile)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def rows_at(v, j, layer, group, row_tile, edges):
        return row_tile[v], 0

    def up_at(v, j, layer, group, row_tile, edges):
        return layer[0], group[v], 0, j

    def down_at(v, j, layer, group, row_tile, edges):
        return layer[0], group[v], j, 0

    # every block twice (the pipeline's double buffering), the scratch tile,
    # and the kernel's own values: g, u (float32, then rounded), a, y
    item = cd.itemsize
    buffers = (2 * item * (2 * tile * d + 3 * d * cols)
               + (4 * tile * d if steps > 1 else 0)
               + tile * cols * (2 * 4 + 3 * item) + 2 * 4 * tile * d)
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, act=act, steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n, steps),
            in_specs=[pl.BlockSpec((tile, d), rows_at),
                      pl.BlockSpec((None, None, d, cols), up_at),
                      pl.BlockSpec((None, None, d, cols), up_at),
                      pl.BlockSpec((None, None, cols, d), down_at)],
            out_specs=pl.BlockSpec((tile, d), rows_at),
            scratch_shapes=([pltpu.VMEM((tile, d), jnp.float32)]
                            if steps > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((A, d), cd),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=buffers + _SPARE_VMEM),
        interpret=interpret,
        name="moe_ffn",
    )(layer, group, row_tile, edges, xs, w_gate, w_up, w_down)
