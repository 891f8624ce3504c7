"""Mixture-of-Experts feed-forward layers, TPU-native. Two paths:

- :func:`routed_mlp` is the MEASURED one (``models/llama.py _mlp_half``:
  the trainer's step, the decode engine): dropless top-k. The ``tokens x k``
  assignments are sorted by expert, their rows gathered, the expert
  products run as grouped products over the sorted rows
  (``jax.lax.ragged_dot``; a served kind's prefill on a TPU in one Pallas
  call, :func:`_expert_ffn`), a token's rows summed from one gather in the
  compute type (:func:`_sum_rows`): memory grows with ``tokens x k``, every
  choice is computed whatever the imbalance, every shape is static, rows
  move by gathers both ways (no scatter). A layer that HOLDS A RANGE of the
  router's experts (``held=``: one chip's share of an expert-parallel job)
  routes over all of them, sorts the assignments to experts held elsewhere
  behind the held ones, and makes rows, products and the float32 scatter-add
  onto tokens for the LIVE places (:func:`_held_rows`: a loop up to ``end``).
  The router's variants (scores, a choice-only bias, a weight scale, the
  experts' form, a shared expert, identity experts, a router input of its
  own) are arguments, each by itself.
- :func:`moe_ffn` / :func:`top_k_routing` are the older GShard/Switch
  *dense dispatch*: one-hot ``[G, S, E, C]`` dispatch/combine tensors with a
  static capacity that DROPS tokens and always renormalises the gates: the
  only path with a live ``expert`` mesh axis (GSPMD all-to-all,
  ``models/moe_llama.py``), in no benchmark cell; it goes when the four-chip
  expert-parallel cell is built on ``routed_mlp`` (ROADMAP Design 1 and 2).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp


def top_k_routing(gate_logits, num_experts: int, top_k: int,
                  capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compute dispatch/combine tensors.

    gate_logits: [G, S, E] router scores (G = groups, S = tokens/group).
    Returns (dispatch [G,S,E,C] bool-ish float, combine [G,S,E,C] float,
    aux_loss scalar). Tokens beyond an expert's capacity C are dropped
    (their combine weight is 0 → they pass through the residual only).
    """
    G, S, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    masks = []          # [G,S,E] one-hot per choice (after capacity)
    gate_vals = []      # [G,S] gate prob per choice
    positions = []      # [G,S] slot index within the chosen expert
    remaining = probs
    # tokens claim expert slots choice-major, then in token order: choice 0
    # of every token outranks choice 1 of any token (t5x/flax convention)
    counts = jnp.zeros((G, 1, E), jnp.float32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=jnp.float32)          # [G,S,E]
        gate_vals.append(jnp.sum(remaining * m, axis=-1))      # [G,S]
        remaining = remaining * (1.0 - m)
        pos_e = jnp.cumsum(m, axis=1) - m + counts             # [G,S,E]
        counts = counts + jnp.sum(m, axis=1, keepdims=True)
        within = (pos_e < capacity).astype(jnp.float32) * m
        masks.append(within)
        positions.append(jnp.sum(pos_e * within, axis=-1))     # [G,S]

    # renormalize surviving gate weights to sum to 1 per token (Mixtral)
    kept = [jnp.sum(m, axis=-1) for m in masks]                # [G,S] 0/1
    denom = sum(g * k for g, k in zip(gate_vals, kept)) + 1e-9
    dispatch = jnp.zeros((G, S, E, capacity), jnp.float32)
    combine = jnp.zeros((G, S, E, capacity), jnp.float32)
    for m, g, p in zip(masks, gate_vals, positions):
        slot = jax.nn.one_hot(p.astype(jnp.int32), capacity,
                              dtype=jnp.float32)               # [G,S,C]
        d = m[..., None] * slot[:, :, None, :]                 # [G,S,E,C]
        dispatch = dispatch + d
        combine = combine + d * (g / denom)[:, :, None, None]

    # Switch aux loss: E * sum_e fraction_tokens_e * mean_prob_e
    # (fractions from choice-0 assignment, pre-capacity)
    first = jax.nn.one_hot(jnp.argmax(probs, -1), E, dtype=jnp.float32)
    frac = jnp.mean(first, axis=(0, 1))
    mean_p = jnp.mean(probs, axis=(0, 1))
    aux = num_experts * jnp.sum(frac * mean_p)
    return dispatch, combine, aux


def expert_capacity(tokens_per_group: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    c = int(math.ceil(top_k * tokens_per_group / num_experts
                      * capacity_factor))
    return max(c, 1)


def moe_ffn(x, router_w, w_gate, w_up, w_down, *, top_k: int = 2,
            capacity_factor: float = 1.25, compute_dtype=jnp.bfloat16,
            mesh=None, rules=None):
    """MoE SwiGLU FFN.  x: [B, S, d].

    router_w: [d, E];  w_gate/w_up: [E, d, f];  w_down: [E, f, d].
    Returns (y [B, S, d] in x.dtype, aux_loss scalar fp32).

    Sharding: expert weights carry logical axis ``expert`` (mesh axis
    ``expert``); the dispatched activations [E, B, C, d] get an explicit
    constraint on E so the dispatch einsum becomes an all-to-all over ICI.
    """
    B, S, d = x.shape
    E = router_w.shape[-1]
    C = expert_capacity(S, E, top_k, capacity_factor)
    cd = compute_dtype

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    dispatch, combine, aux = top_k_routing(logits, E, top_k, C)

    ex_in = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(cd), x.astype(cd))
    if mesh is not None and "expert" in mesh.axis_names:
        from ray_tpu.parallel.sharding import constraint

        ex_in = constraint(ex_in, ("expert", "batch", None, None),
                           mesh, rules)
    g = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", ex_in, w_gate.astype(cd)))
    u = jnp.einsum("ebcd,edf->ebcf", ex_in, w_up.astype(cd))
    ex_out = jnp.einsum("ebcf,efd->ebcd", g * u, w_down.astype(cd))
    if mesh is not None and "expert" in mesh.axis_names:
        from ray_tpu.parallel.sharding import constraint

        ex_out = constraint(ex_out, ("expert", "batch", None, None),
                            mesh, rules)
    y = jnp.einsum("bsec,ebcd->bsd", combine.astype(cd), ex_out)
    return y.astype(x.dtype), aux


# --------------------------------------------------------------------------- #
# Dropless routed SwiGLU (assignments sorted by expert, grouped products)
# --------------------------------------------------------------------------- #

_GROUPS_BY_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _float0(x):
    return np.zeros(x.shape, jax.dtypes.float0)


@jax.custom_vjp
def _grouped_dot(x, w, group_sizes):
    """``x`` [A, k] (rows sorted by group, compute type) times the group's
    own ``w[g]`` of ``w`` [G, k, n] (as stored): [A, n] float32. Products
    in ``x``'s type, accumulated in float32, forward and backward: the
    backward's cotangent is brought to ``x``'s type first, and a group's
    weight gradient is accumulated over its rows in float32. On the TPU
    ``ragged_dot`` is XLA's own grouped-matmul kernel (512-row tiles; a
    tile that straddles a group boundary is visited once a group), not a
    product over every group."""
    return jax.lax.ragged_dot(x, w.astype(x.dtype), group_sizes,
                              preferred_element_type=jnp.float32)


def _grouped_dot_fwd(x, w, group_sizes):
    return _grouped_dot(x, w, group_sizes), (x, w, group_sizes)


def _grouped_dot_bwd(res, ct):
    x, w, group_sizes = res
    ct = ct.astype(x.dtype)
    # the weights transposed in memory: contracting their last dimension
    # in place compiles, on the TPU, to a product over EVERY group
    dx = jax.lax.ragged_dot(ct, jnp.swapaxes(w.astype(x.dtype), 1, 2),
                            group_sizes, preferred_element_type=jnp.float32)
    dw = jax.lax.ragged_dot_general(
        x, ct, group_sizes, _GROUPS_BY_ROWS,
        preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype), _float0(group_sizes)


_grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


def _sum_rows(rows, inverse, n, w=None):
    """Each token's ``k`` rows of ``rows`` [A, d] (its ``j``-th at place
    ``inverse[t * k + j]``), times ``w[t, j]`` where ``w`` [n, k] is given,
    summed in float32 [n, d]: ONE gather in ``rows``' type, slot by slot ([k
    * n, d] seen as [k, n, d]: ``n``, not ``k``, beside the lanes), and one
    fused pass, in which the barrier keeps the widening (PERF.md, PR 41)."""
    g = rows[inverse.reshape(n, -1).T.reshape(-1)]
    g = jax.lax.optimization_barrier(g.reshape(-1, n, rows.shape[-1]))
    return (g if w is None else g * w.T[:, :, None]).sum(0, dtype=jnp.float32)


@jax.custom_vjp
def _dispatch(h, order, inverse):
    """``h`` [N, d] -> the assignments' rows in sorted order [A, d], a gather
    as its transpose is: ``order[i]`` is the assignment (token ``order[i] //
    k``) in sorted place ``i``, ``inverse[a]`` assignment ``a``'s place."""
    return h[order // (order.shape[0] // h.shape[0])]


def _dispatch_bwd(res, ct):
    n, order, inverse = res
    with jax.named_scope("moe.dispatch"):
        dh = _sum_rows(ct, inverse, n)
    return dh.astype(ct.dtype), _float0(order), _float0(inverse)


_dispatch.defvjp(lambda h, *ix: (_dispatch(h, *ix), (h.shape[0], *ix)),
                 _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, order, inverse):
    """``y[n] = sum_j w[n, j] * ys[inverse[n * k + j]]`` in float32."""
    return _sum_rows(ys, inverse, w.shape[0], w=w)


def _combine_bwd(res, ct):
    ys, w, order, inverse = res
    with jax.named_scope("moe.combine"):
        # ONE gather (of ``ct``) for both; NUMBERS change order by a sort
        _, w_sorted = jax.lax.sort_key_val(inverse, w.reshape(-1))
        ct = ct[order // w.shape[1]]
        d_ys = (w_sorted[:, None] * ct).astype(ys.dtype)
        _, d_w = jax.lax.sort_key_val(
            order, (ys.astype(jnp.float32) * ct).sum(-1))
    return (d_ys, d_w.reshape(w.shape).astype(w.dtype), _float0(order),
            _float0(inverse))


_combine.defvjp(lambda *args: (_combine(*args), args), _combine_bwd)


def _filled(f: int) -> bool:
    """Whether an expert width is filled up to a multiple of 512
    (:func:`_expert_ffn` says why): over 512 and no multiple of it, but
    for 768, the one such width measured faster as it is."""
    return f > 512 and f % 512 != 0 and f != 768


def expert_groups(w_up, dtype) -> int:
    """The groups XLA's grouped products run over for a kind's stacked
    ``w_up`` [L, count, d, f] and rows of ``dtype``: the stack's ``L *
    count`` where it is read where it lies, one layer's ``count`` where the
    layer is cut out of it (:func:`_expert_ffn` says when). What a decode
    call's products run over in every engine; a prefill's that take the
    kernel (:func:`expert_product_path`) read ``w[layer, g]`` by index map
    and know one layer's ``count`` groups."""
    L, count, _, f = w_up.shape
    cut = w_up.dtype != dtype or _filled(f)
    return count if cut else L * count


# told ``(xs, w_up, path, reason)`` of every call of the experts' products on
# a kind's STACKED leaves, where a program is traced: the decode engine
# registers its count here (models/llama.py expert_product_paths and the
# gauge beside it); this module knows the rule and no metric. Calls with one
# layer's weights (the trainer's step) have one path and tell nobody
_stacked_call_watchers: list = []


def watch_stacked_calls(tell) -> None:
    """Register ``tell(xs, w_up, path, reason)`` (once, however often it is
    asked) for every call of :func:`_expert_ffn` on stacked leaves."""
    if tell not in _stacked_call_watchers:
        _stacked_call_watchers.append(tell)


def expert_product_path(xs, w_gate, w_up, layer=None) -> Tuple[str, str]:
    """``(path, reason)`` :func:`_expert_ffn` takes for these operands in
    this process: ``"kernel"`` (``ops/grouped_ffn.py``) on a TPU backend for
    gated experts that come as a kind's stack with their layer's number, in
    the rows' type, of widths that are whole lanes and are not filled up, on
    at least one row tile of rows; ``"xla"`` with what stands in the way
    otherwise. Read from the backend and the call's own shapes alone."""
    platform = jax.default_backend()
    if platform != "tpu":
        return "xla", f"backend is {platform!r}, not tpu"
    if layer is None:
        return "xla", ("one layer's experts [count, d, f], not a kind's "
                       "stack with its layer's number")
    if w_gate is None:
        return "xla", "two-matrix experts (no gate)"
    L, count, d, f = w_up.shape
    if expert_groups(w_up, xs.dtype) != L * count:
        return "xla", (f"a stack in {w_up.dtype.name} of width {f} under "
                       f"rows in {xs.dtype.name}: the layer is cut out of it")
    from ray_tpu.ops.grouped_ffn import ROW_TILE, pick_columns

    if pick_columns(d, f, xs.dtype.itemsize) is None:
        return "xla", f"widths {d} and {f} are not whole lanes of 128"
    if xs.shape[0] < ROW_TILE:
        return "xla", (f"{xs.shape[0]} rows are under one row tile of "
                       f"{ROW_TILE}")
    return "kernel", "tpu backend"


def _expert_ffn(xs, w_gate, w_up, w_down, counts, layer=None,
                act: str = "swiglu"):
    """The experts' products on rows sorted by expert (``counts`` rows an
    expert): where there is a ``w_gate`` the gated ``act(x W_gate) * (x
    W_up) W_down`` (``act`` ``"swiglu"``: silu; ``"reglu"``: relu), else the
    two-matrix ``relu(x W_up) ** 2 W_down``. Compute type in, compute type
    out. Gate and up are rounded to the compute type, the gate function runs
    in float32, the product is rounded once, and so is the down product:
    on BOTH paths.

    With ``layer`` the weights are a kind's whole stacked leaves ``[L,
    count, ...]``, read where they lie (``w[layer]`` under a traced
    ``layer`` is a copy of the whole layer's experts a matrix: 14.7 ms of
    every call of the LongCat engine; ledger, PR 35). Which way, from the
    call's own shapes (:func:`expert_product_path`; the decode engine
    counts the stacked calls where a program is traced, through
    :func:`watch_stacked_calls`:
    ``ray_tpu_serve_engine_expert_products{path}`` and
    ``models.llama.expert_product_paths()``):

    - ``kernel``: a prefill's rows (at least a row tile) on a TPU backend,
      gated experts in the rows' type at widths of whole lanes: ONE
      forward-only Pallas call (``ops/grouped_ffn.py``, imported here and
      nowhere else) that reads tile ``w[layer, g]`` by index map, makes
      gate, up and the activation in one pass over a row tile and writes
      every result in the compute type. Under ``jax.grad`` it gives the
      other path's transposes (:func:`_expert_kernel`).
    - ``xla``: everything else, three ``jax.lax.ragged_dot`` products with
      float32 results (:func:`_expert_xla`): a decode call's 6-16 rows (0.12
      ms; PR 37), two-matrix experts, another backend, and every call with
      ``layer=None`` (the trainer's step, whose scan hands each layer its
      own slice), which also keeps a train process from ever importing
      the kernel's module."""
    if layer is not None:
        path, reason = expert_product_path(xs, w_gate, w_up, layer)
        for tell in _stacked_call_watchers:
            tell(xs, w_up, path, reason)
        if path == "kernel":
            return _expert_kernel(xs, w_gate, w_up, w_down, counts,
                                  jnp.asarray(layer, jnp.int32), act)
    return _expert_xla(xs, w_gate, w_up, w_down, counts, layer, act)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _expert_kernel(xs, w_gate, w_up, w_down, counts, layer, act):
    from ray_tpu.ops.grouped_ffn import grouped_ffn

    return grouped_ffn(xs, w_gate, w_up, w_down, counts, layer, act=act)


def _expert_kernel_fwd(xs, w_gate, w_up, w_down, counts, layer, act):
    return (_expert_kernel(xs, w_gate, w_up, w_down, counts, layer, act),
            (xs, w_gate, w_up, w_down, counts, layer))


def _expert_kernel_bwd(act, res, ct):
    # forward only: the transposes are the XLA path's, made again from the
    # operands (its products are not kept)
    *operands, counts, layer = res
    _, transpose = jax.vjp(
        lambda *a: _expert_xla(*a, counts, layer, act), *operands)
    return (*transpose(ct), _float0(counts), _float0(layer))


_expert_kernel.defvjp(_expert_kernel_fwd, _expert_kernel_bwd)


def _expert_xla(xs, w_gate, w_up, w_down, counts, layer, act):
    """:func:`_expert_ffn` as XLA's grouped products
    (:func:`_grouped_dot`).

    A stack (``layer``) in the rows' type is handed over as ``L * count``
    groups (the same bytes) of which this layer's, from ``layer * count``
    on, get ``counts`` and every other one no row: the grouped product
    reads the tiles of the groups that have rows. A stack in another type
    than the rows', or of a width that is filled up below, would be copied
    WHOLE: there the layer is cut out.

    A width over 512 that is no multiple of it is filled up with zero
    columns (and zero rows of ``w_down``), which add nothing: on the TPU
    the grouped product of 6,144 rows in 8 groups takes 6.2 ms forward and
    16.6 with its backward at width 1856, 6.0 / 17.1 at 1920 and 3.5 / 9.0
    at 2048 (PERF.md, PR 31). 768 alone is left as it is: the product of
    98,304 rows by 64 experts of a stack of 8 x 64 takes 4.5 ms read where
    it lies against 6.3 cut out and filled to 1024, and a decode call's 16
    rows 0.12 ms against 1.8 (PERF.md, PR 38); no other multiple of 256 was
    measured, so none is exempt. A row count that is no multiple of 8 (a
    decode call's ``top_k`` = 12) is filled up with rows of no group: the
    TPU compiler makes the grouped kernel only of whole sublanes, and of
    anything else a dense product over EVERY group under a mask, which
    reads every expert's weights (compiled for a described v5e at 1, 9, 12
    and 20 rows against 8, 16 and 24; PR 37)."""
    cd = xs.dtype
    f = w_up.shape[-1]
    if layer is not None:
        stack = (w_gate, w_up, w_down)
        (L, count), groups = w_up.shape[:2], expert_groups(w_up, cd)
        if groups == L * count:
            w_gate, w_up, w_down = (
                None if w is None else w.reshape(groups, *w.shape[2:])
                for w in stack)
            counts = jax.lax.dynamic_update_slice(
                jnp.zeros(groups, counts.dtype), counts,
                (jnp.asarray(layer, jnp.int32) * count,))
        else:
            w_gate, w_up, w_down = (None if w is None else w[layer]
                                    for w in stack)
    if _filled(f):
        cols, rows = ((0, 0), (0, 0), (0, -f % 512)), ((0, 0), (0, -f % 512),
                                                       (0, 0))
        w_up, w_down = jnp.pad(w_up, cols), jnp.pad(w_down, rows)
        w_gate = None if w_gate is None else jnp.pad(w_gate, cols)
    n = xs.shape[0]
    if n % 8:
        xs = jnp.pad(xs, ((0, -n % 8), (0, 0)))
    if w_gate is None:
        u = _grouped_dot(xs, w_up, counts)
        a = jnp.square(jax.nn.relu(u)).astype(cd)
    else:
        g = _grouped_dot(xs, w_gate, counts).astype(cd)
        u = _grouped_dot(xs, w_up, counts).astype(cd)
        gate = jax.nn.relu if act == "reglu" else jax.nn.silu
        a = (gate(g.astype(jnp.float32)) * u).astype(cd)
    ys = _grouped_dot(a, w_down, counts).astype(cd)
    return ys[:n] if n % 8 else ys


# what a row tile of the grouped kernel holds: a chunk of the held path's
# forward loop is whole tiles (held_chunk), so that a chunk edge cuts a group
# where a tile edge would
HELD_TILE = 256


def _held_rows(hf, top_w, order, starts, end, weights, experts,
               layer=None, act: str = "swiglu"):
    """The routed sum ``[N, d]`` float32 of a layer that holds some of the
    router's ``experts`` experts. ``order`` [A] are the assignments sorted
    by held expert (``starts`` [count]: each one's first place), from place
    ``end`` on those that chose an expert held elsewhere: they get no row,
    no product and no part in the sum. ``weights`` / ``layer``: as
    :func:`_expert_ffn` takes them.

    The index maps here are a gather of rows and a float32 sum onto tokens
    (``_dispatch`` / ``_combine`` gather a row for EVERY assignment), both
    linear in the places a row is made for (a gather from HBM 24-30 rows a
    microsecond; the sum by :func:`held_sum_path`: a kernel in a prefill's
    loop, XLA's scatter-add elsewhere; PERF.md, PRs 41, 47 and 49). So the
    places follow the router's own count ``end``, in few large chunks, by the
    call's shape and whether it is differentiated (:func:`held_places_made`):

    - ``A <= held_chunk(..)`` (every decode call: 8-12 places): one straight
      block, a row for every place (:func:`_held_blocks`);
    - a larger FORWARD call (a prefill program, ``forward`` / ``loss_fn``):
      :func:`_held_chunks`' ONE loop of ``ceil(end / chunk)`` chunks of the
      EVEN share of the places and a margin: a balanced router's load is one
      chunk with a short dead tail, a heavier one more chunks, none can outrun
      it, and a program holds one expert (and sum) kernel a routed block;
    - a larger DIFFERENTIATED call (the trainer's step): TIERS of static,
      uneven edges (:func:`held_tiers`), the first, one chunk of the loop's,
      always made, those behind it under nested ``cond`` s
      (:func:`_held_chunks_jvp`): a loop of traced length has no transpose.
      ``train-glm47flash-1chip``'s step makes 11,264 rows a routed block for
      some 8,192 live places of 65,536, ``train-nemotron3nano-1chip``'s 8,448
      for 6,144 of 98,304 (32,768 and 24,576 before PR 57).

    Whichever runs: float32 rows times float32 weights added in float32
    onto ``[N, d]``, every live assignment's row whatever the routing, and
    nothing from a dead place. On the TPU the grouped product leaves the
    rows of no group UNWRITTEN (whatever the buffer held): places from
    ``end`` on are set to zero going in and coming out (the kernel never
    adds them), and so are, transposed, their cotangents (:func:`_place_rows`)."""
    if order.shape[0] <= held_chunk(order.shape[0], starts.shape[0], experts):
        return _held_blocks(hf, top_w, order, starts, end, weights,
                            (0, order.shape[0]), layer, act)
    return _held_chunks(act, experts, hf, top_w, order, starts, end, weights,
                        None if layer is None else jnp.asarray(layer,
                                                               jnp.int32))


# --------------------------------------------------------------------------- #
# The held path's forms (:func:`_held_rows` says which runs when)
# --------------------------------------------------------------------------- #


def held_chunk(places: int, count: int, experts: int) -> int:
    """The sorted places a chunk of :func:`_held_chunks`' loop (and the most
    a straight block): the EVEN share of the call's ``places`` that ``count``
    held experts of the router's ``experts`` get, and one part in
    ``sqrt(count)`` more (what the sum of ``count`` experts' uneven loads
    strays by: a quarter over 16 experts, a twelfth over 128), in whole row
    tiles. A function of the call's shapes and no knob, chosen (PERF.md, PR
    47) so that the common load is ONE chunk and its dead tail the margin;
    whether a smaller chunk pays under the sum's kernel is not measured."""
    even = places * count / experts
    return math.ceil(even * (1 + count ** -0.5) / HELD_TILE) * HELD_TILE


def held_tiers(places: int, count: int, experts: int) -> Tuple[int, ...]:
    """The static edges ``(0, e_1, ..., places)`` of the TIERS a
    DIFFERENTIATED call of :func:`_held_rows` makes rows by
    (:func:`_held_blocks`): tier ``[0, e_1)`` in every step, tier ``[e_i,
    e_i+1)`` only in a step whose router sends places past ``e_i``. The first
    is ONE chunk of the forward loop's (:func:`held_chunk`: the even share
    and its margin, what a balanced router fills), the others end where the
    EVEN BLOCKS the form had until PR 57 ended (a block: four even shares of
    the ``experts`` experts' assignments, two where four would be all of
    them, at least 128 places), so that no tier is larger than such a block
    (a compiled step's worst branch holds the temporaries it held) and no
    step makes more places than it made then: ``(0, 11264, 32768, 65536)``
    for 8 of 64 experts on 65,536 places (``train-glm47flash-1chip``), ``(0,
    8448, 24576, 49152, 73728, 98304)`` for 8 of 128 on 98,304
    (``train-nemotron3nano-1chip``). Where a chunk is no smaller than a
    block the first tier is the block. Strictly increasing, a function of
    the call's shapes and no knob."""
    blocks = max(1, min(max(experts // (4 * count),
                            min(2, experts // (2 * count))), places // 128))
    if places % blocks:
        blocks = 1
    block = places // blocks
    c = held_chunk(places, count, experts)
    return (0,) + ((c,) if c < block else ()) + tuple(
        range(block, places + 1, block))


def held_places_made(places: int, live, count: int, experts: int,
                     differentiated: bool = False) -> int:
    """The places :func:`_held_rows` makes a row for (gathers, multiplies
    and adds onto its token) in a call over ``places`` sorted places of which
    the first ``live`` fell on the ``count`` held of ``experts`` experts:
    all of them where the call is one straight block; else in a FORWARD call
    whole chunks up to ``live``, in a ``differentiated`` one whole tiers
    (:func:`held_tiers`), the first whatever ``live`` is."""
    c = held_chunk(places, count, experts)
    if places <= c:
        return places
    if not differentiated:
        return -(-int(live) // c) * c
    edges = held_tiers(places, count, experts)
    return next(e for e in edges[1:] if e >= min(int(live), places))


def _place_outputs(hf, flat_w, order, edges, end, weights, layer, act, lo, n):
    """``(live, mine, token, ys, inside)`` of the ``n`` sorted places from
    ``lo`` on: whether a place lies before ``end``, its assignment, its
    token, its expert's output ``[n, d]`` in the compute type (UNWRITTEN from
    place ``end`` on) and the experts' edges inside the range. ``edges``
    [count + 1]: each held expert's first place, then ``end``; a group the
    range cuts is handed over with the rows it has inside."""
    live = lo + jnp.arange(n, dtype=jnp.int32) < end
    mine = jax.lax.dynamic_slice_in_dim(order, lo, n)
    token = mine // (flat_w.shape[0] // hf.shape[0])
    with jax.named_scope("moe.dispatch"):
        xs = jnp.where(live[:, None], hf[token], 0)
    with jax.named_scope("moe.experts"):
        inside = jnp.clip(edges - lo, 0, n)
        ys = _expert_ffn(xs, *weights, jnp.diff(inside), layer, act)
    return live, mine, token, ys, inside


def _place_rows(hf, flat_w, order, *place):
    """``(token, rows)`` of :func:`_place_outputs`' places: each place's
    token, and its expert's output times its weight, float32 ``[n, d]``,
    zero from place ``end`` on."""
    live, mine, token, ys, _ = _place_outputs(hf, flat_w, order, *place)
    with jax.named_scope("moe.combine"):
        return token, jnp.where(live, flat_w[mine], 0.0)[:, None] * jnp.where(
            live[:, None], ys, 0).astype(jnp.float32)


def _place_sum(hf, flat_w, order, starts_end, end, weights, layer, lo, *, n,
               act):
    """:func:`_place_rows` of the ``n`` sorted places from ``lo`` on, added
    onto their tokens: float32 ``[N, d]``."""
    token, ys = _place_rows(hf, flat_w, order, starts_end, end, weights,
                            layer, act, lo, n)
    with jax.named_scope("moe.combine"):
        return jnp.zeros(hf.shape, jnp.float32).at[token].add(ys)


# a tier of the trainer's step: jitted, so that a tier's body is traced (and
# differentiated, and lowered) ONCE a size in a process, whatever ``lo`` and
# however many routed blocks and programs hold it (a step is built twice,
# train/spmd.py _KeepingStep; GLM's has the module's block beside the
# stack's): with a third tier's body GLM's two programs trace in 9.1 s where
# the even blocks' two bodies took 10.0 and the tiers' three unjitted 11.2
# (the CPU, PR 57: a count of Python's work, no device's)
_tier_sum = jax.jit(_place_sum, static_argnames=("n", "act"))


def _held_blocks(hf, top_w, order, starts, end, weights, edges, layer, act):
    """:func:`_held_rows` over the tiers ``[edges[i], edges[i + 1])`` of the
    sorted places (``edges``: static, from 0 to ``A``), in operations that
    have a transpose. The first tier's rows are always made; a further
    tier's only in a step whose router sends places into it (recomputed in
    the backward: it is the rare step), so no assignment is ever left out
    whatever the routing, and a common step makes ``edges[1]`` rows, live or
    dead. The ``cond`` s are NESTED, a tier's branch holds the next tier's,
    and a branch is ONE checkpoint over everything behind its edge: the
    common step evaluates one predicate, adds one ``[N, d]`` of zeros and
    carries one set of a ``cond``'s residuals (a differentiated ``cond``
    hands its branch's inputs on as outputs, zeros where the branch was not
    taken: the held experts' weights, 0.3 GB a routed block in both train
    cells) however many tiers there are."""
    (N, d), A = hf.shape, order.shape[0]
    assert edges[0] == 0 and edges[-1] == A and all(
        lo < hi for lo, hi in zip(edges, edges[1:])), edges
    _tell_held_sum(jax.ShapeDtypeStruct((edges[1], d), hf.dtype), N, layer,
                   False)
    starts_end = jnp.append(starts, end).astype(jnp.int32)
    flat_w = top_w.reshape(A)
    # the trainer's tiers (one layer's weights) through the jitted form; one
    # straight block (a decode program's text is what it was) and a served
    # kind's stacked leaves, whose calls are counted where they are traced
    # (_expert_ffn's watchers), are traced where they stand
    place_sum = _tier_sum if len(edges) > 2 and layer is None else _place_sum

    def tier(i):
        return place_sum(hf, flat_w, order, starts_end, end, weights, layer,
                         edges[i], n=edges[i + 1] - edges[i], act=act)

    def behind(i):  # tier i and, if places fall behind it, those tiers
        y = tier(i)
        if i + 2 < len(edges):
            y = y + jax.lax.cond(edges[i + 1] < end,
                                 jax.checkpoint(partial(behind, i + 1)),
                                 lambda: jnp.zeros((N, d), jnp.float32))
        return y

    return behind(0)


@partial(jax.custom_jvp, nondiff_argnums=(0, 1))
def _held_chunks(act, experts, hf, top_w, order, starts, end, weights, layer):
    """:func:`_held_rows` as ONE loop over chunks of :func:`held_chunk`
    sorted places whose trip count is ``ceil(end / chunk)``: a chunk's
    rows gathered, through :func:`_expert_ffn` with the groups' counts
    clipped to the chunk (a group a chunk's edge cuts is one more cut
    tile), weighted and added in float32 INTO THE CARRIED sum: by ONE
    Pallas call a chunk that fetches a token tile's rows in the compute type
    and adds them in VMEM (``ops/row_sum.py``, imported here and nowhere
    else), or by a float32 product ``[chunk, d]`` and XLA's scatter-add
    (:func:`held_sum_path` says which). Only the last chunk has dead
    places."""
    (N, d), A = hf.shape, order.shape[0]
    c = held_chunk(A, starts.shape[0], experts)
    path = _tell_held_sum(jax.ShapeDtypeStruct((c, d), hf.dtype), N, layer)
    edges = jnp.append(starts, end).astype(jnp.int32)
    flat_w = top_w.reshape(A)
    order = jnp.pad(order, (0, -A % c))  # a last chunk's places behind ``A``

    def chunk(i, y):
        place = (edges, end, weights, layer, act, i * c, c)
        if path == "kernel":
            from ray_tpu.ops.row_sum import held_sum

            _, mine, token, ys, inside = _place_outputs(hf, flat_w, order,
                                                        *place)
            with jax.named_scope("moe.combine"):
                return held_sum(y, ys, token, flat_w[mine], inside)
        token, ys = _place_rows(hf, flat_w, order, *place)
        with jax.named_scope("moe.combine"):
            return y.at[token].add(ys)

    with jax.named_scope("moe.combine"):
        y = jnp.zeros((N, d), jnp.float32)
    return jax.lax.fori_loop(0, (end + (c - 1)) // c, chunk, y)


@_held_chunks.defjvp
def _held_chunks_jvp(act, experts, primals, tangents):
    """A differentiated :func:`_held_chunks` is :func:`_held_blocks` over
    :func:`held_tiers`' edges, traced where the call stands, and the loop is
    in no such program at all (a ``custom_jvp`` and not a ``custom_vjp``: a
    tier's transposes then add to the cotangents one by one, and the
    experts' float32 gradients are carried through no loop). The first tier
    is the loop's chunk, so the common step makes the rows a forward call's
    one trip makes (``train-glm47flash-1chip``: 11,264 a routed block where
    it made 32,768; ``train-nemotron3nano-1chip``: 8,448 for 24,576) and
    ``stats["held_chunks"]`` reads 1.0 exactly when it did."""
    hf, top_w, order, starts, end, weights, layer = primals
    edges = held_tiers(order.shape[0], starts.shape[0], experts)
    return jax.jvp(
        lambda hf, top_w, weights: _held_blocks(
            hf, top_w, order, starts, end, weights, edges, layer, act),
        (hf, top_w, weights), (tangents[0], tangents[1], tangents[5]))


# told ``(ys, n_tokens, path, reason)`` of every held sum on a kind's STACKED
# leaves, where a program is traced, as ``_stacked_call_watchers`` are of the
# products (models/llama.py held_sum_paths and the gauge beside it)
_held_sum_watchers: list = []


def watch_held_sums(tell) -> None:
    """Register ``tell(ys, n_tokens, path, reason)`` (once, however often it
    is asked) for every sum of the held path on stacked leaves."""
    if tell not in _held_sum_watchers:
        _held_sum_watchers.append(tell)


def _tell_held_sum(ys, n_tokens: int, layer, loop: bool = True) -> str:
    """The path of this call's sum; a served kind's calls tell the
    watchers."""
    path, reason = held_sum_path(ys, n_tokens, layer, loop)
    if layer is not None:
        for tell in _held_sum_watchers:
            tell(ys, n_tokens, path, reason)
    return path


def held_sum_path(ys, n_tokens: int, layer=None, loop: bool = True
                  ) -> Tuple[str, str]:
    """``(path, reason)`` by which a call of the held path adds ``ys`` [c, d]
    (a chunk's or a block's rows) onto ``n_tokens`` tokens in this process:
    ``"kernel"`` (``ops/row_sum.py``) on a TPU backend, in
    :func:`_held_chunks`' forward ``loop``, for a served kind's stacked
    leaves (a train process never imports the kernel's module), rows of
    whole lanes in a 2- or 4-byte float type, in whole slabs, tokens that
    split into tiles and a chunk whose tokens and weights fit the scalar
    memory; ``"xla"`` (the weighted float32 rows and a
    scatter-add) with what stands in the way otherwise: every decode call
    and every differentiated call (:func:`_held_blocks`). Read from the
    backend and the call's own shapes alone."""
    platform = jax.default_backend()
    if platform != "tpu":
        return "xla", f"backend is {platform!r}, not tpu"
    if layer is None:
        return "xla", ("one layer's experts [count, d, f], not a kind's "
                       "stack with its layer's number")
    c, d = ys.shape
    if not loop:
        return "xla", (f"one straight block of {c} places (a decode call, or "
                       "a differentiated call's first tier)")
    dtype = jnp.dtype(ys.dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize not in (2, 4):
        return "xla", f"rows in {dtype.name}, not a 2- or 4-byte float type"
    if d % 128:
        return "xla", f"rows of {d} are not whole lanes of 128"
    from ray_tpu.ops.row_sum import GROUP, MAX_PLACES, pick_tile

    if pick_tile(n_tokens, d) is None:
        return "xla", f"{n_tokens} tokens do not split into tiles of 8 or more"
    if c % GROUP:
        return "xla", f"{c} places are not whole slabs of {GROUP} rows"
    if c > MAX_PLACES:
        return "xla", (f"a chunk of {c} places: their tokens and weights pass "
                       f"the scalar memory ({MAX_PLACES})")
    return "kernel", "tpu backend"


def routed_mlp(h, router_w, w_gate, w_up, w_down, *, top_k: int,
               norm_topk_prob: bool = False,
               stat_axes: Sequence[str] = (),
               scoring: str = "softmax", choice_bias=None,
               scale: float = 1.0, held: Optional[Tuple[int, int]] = None,
               shared=None, zero_experts: int = 0, layer=None,
               router_input=None, act: str = "swiglu", shared_gated=None
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Dropless top-k routed experts. ``h`` [..., d] in the compute type.

    ``router_w`` [d, E]; ``w_gate`` / ``w_up`` [E, d, f]; ``w_down``
    [E, f, d], as stored. Router product and scores in float32; the
    ``top_k`` weights stay as the scores gave them unless
    ``norm_topk_prob``; the expert products in ``h``'s type with float32
    accumulation. What a configuration may switch on, each by itself:

    - ``scoring="sigmoid"``: ``sigmoid(logits)`` an expert in place of the
      softmax over experts. The router losses below are the softmax
      router's; a sigmoid router reports none.
    - ``choice_bias`` [E]: added to the scores for the CHOICE of the
      ``top_k`` alone; the weights are the unbiased scores of the chosen,
      and no gradient reaches the bias.
    - ``scale``: multiplies the weights, after ``norm_topk_prob``'s
      renormalisation (``w_j = s_j / (sum_j s_j + 1e-20) * scale``).
    - ``w_gate=None``: two-matrix experts, ``relu(x W_up) ** 2 W_down``.
    - ``act="reglu"``: gated experts whose gate goes through relu,
      ``relu(x W_gate) * (x W_up) W_down``, in place of SwiGLU's silu.
    - ``router_input`` [..., d]: what the ROUTER reads where that is not
      what its experts read (a block whose router sits before its
      attention): logits, scores and the choice come from it, the experts'
      rows from ``h``.
    - ``shared = (w_up [d, fs], w_down [fs, d])``: one expert of that form
      (no gate) every token runs, added to the routed sum once.
    - ``shared_gated = (w_sg [d], w_gate [d, fs], w_up [d, fs], w_down
      [fs, d])``: one SwiGLU expert every token runs, behind the token's own
      gate ``sigmoid(h . w_sg)`` (float32), added to the routed sum once.
      ``w_sg=None``: no gate in front of it, the expert's output as it is.
    - ``held = (first, count)``: this device holds the ``count`` experts
      from ``first`` on of the router's ``E`` (``w_up`` [count, d, f]):
      router and ``top_k`` run over all ``E``; assignments to experts held
      elsewhere sort behind the held experts' and get no row, no product
      and no part in the sum (:func:`_held_rows`: rows up to the held
      experts' last place, in a forward call by chunks of the even share,
      in a differentiated one by tiers whose first is that chunk); ``y`` is
      the partial sum of the held experts (plus the shared one). With
      ``held=None`` every expert is here.
    - ``zero_experts = n``: the LAST ``n`` of the router's ``E`` outputs
      are identity experts, which have no weights and run where the token
      is: an assignment to one adds ``w_j h`` and gets no row, no place
      among the experts' groups and no product (it sorts behind the held
      experts', as one held elsewhere does). ``w_gate`` / ``w_up`` /
      ``w_down`` hold the ``E - n`` real experts, or ``held``'s range.

    Returns ``(y [..., d] float32, stats)``; ``stats`` are float32 scalars:

    - ``lb_loss``: ``E * sum_e fraction_e * mean_prob_e``, ``fraction_e``
      the assignments expert ``e`` got over the TOKENS (the fractions sum
      to ``top_k``), no gradient through it (softmax scoring only);
    - ``z_loss``: ``mean(logsumexp(logits) ** 2)`` (softmax scoring only);
    - ``max_load_ratio``: the heaviest (held) expert's assignments over the
      mean of all ``E``;
    - ``dropped``: assignments to experts here that no group holds (0 by
      construction: every assignment has a place);
    - ``held_share`` (with ``held`` only): the share of all assignments
      that fell on held experts;
    - ``held_chunks`` (with ``held`` only): ``ceil(end / held_chunk(..))``,
      the chunks of the even share the held experts' live places fill: the
      forward loop's trip count, and 1.0 exactly when a differentiated
      call's first tier (:func:`held_tiers`) held every live place;
    - ``zero_share`` (with ``zero_experts`` only): the share that fell on
      identity experts. What neither share counts fell elsewhere.

    ``stat_axes``: inside a ``shard_map`` whose axes split the tokens, the
    axis names to average ``fraction_e`` over, so that ``lb_loss``, averaged
    over those axes by the caller, is the loss of the whole batch and its
    gradient the whole batch's (the fractions carry no gradient, so no
    collective is differentiated)."""
    if (w_up.ndim == 4) != (layer is not None):
        raise ValueError(
            f"expert weights {w_up.shape} with layer={layer!r}: stacked "
            "leaves [L, count, d, f] come with their layer's number, one "
            "layer's [count, d, f] without")
    cd = h.dtype
    lead, d = h.shape[:-1], h.shape[-1]
    E, K = router_w.shape[-1], top_k
    hf = h.reshape(-1, d)
    N = hf.shape[0]
    A = N * K
    rf = hf if router_input is None else router_input.reshape(-1, d)
    with jax.named_scope("moe.route"):
        logits = jnp.dot(rf.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if scoring == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        else:
            probs = jax.nn.sigmoid(logits)
        if choice_bias is None:
            top_w, top_e = jax.lax.top_k(probs, K)
        else:
            _, top_e = jax.lax.top_k(
                probs + jax.lax.stop_gradient(choice_bias), K)
            top_w = jnp.take_along_axis(probs, top_e, axis=-1)
        if norm_topk_prob:
            top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
        if scale != 1.0:
            top_w = top_w * scale
    if zero_experts and held is None:  # the real experts, all here
        held = (0, E - zero_experts)
    first, count = (0, E) if held is None else held
    with jax.named_scope("moe.dispatch"):
        ids = jnp.arange(A, dtype=jnp.int32)
        group = top_e.reshape(A).astype(jnp.int32)
        if held is not None:  # experts held elsewhere sort behind these
            here = (group >= first) & (group < first + count)
            group = jnp.where(here, group - first, count)
        sorted_e, order = jax.lax.sort_key_val(group, ids)  # stable
        starts = jnp.searchsorted(sorted_e,
                                  jnp.arange(count, dtype=jnp.int32))
        end = jnp.int32(A) if held is None else jnp.searchsorted(
            sorted_e, jnp.int32(count)).astype(jnp.int32)
        counts = jnp.diff(starts.astype(jnp.int32), append=end)
        if held is None:
            _, inverse = jax.lax.sort_key_val(order, ids)
            xs = _dispatch(hf, order, inverse)
    if held is None:
        with jax.named_scope("moe.experts"):
            ys = _expert_ffn(xs, w_gate, w_up, w_down, counts, layer, act)
        with jax.named_scope("moe.combine"):
            y = _combine(ys, top_w, order, inverse)
    else:
        y = _held_rows(hf, top_w, order, starts, end, (w_gate, w_up, w_down),
                       E, layer, act)
    if zero_experts:
        with jax.named_scope("moe.zero"):
            to_zero = top_e >= E - zero_experts
            y = y + (jnp.sum(jnp.where(to_zero, top_w, 0.0), axis=-1,
                             keepdims=True) * hf.astype(jnp.float32))
    if shared is not None:
        with jax.named_scope("moe.shared"):
            a = jnp.square(jax.nn.relu(hf @ shared[0].astype(cd)))
            y = y + (a @ shared[1].astype(cd)).astype(jnp.float32)
    if shared_gated is not None:
        with jax.named_scope("moe.shared"):
            w_sg, s_gate, s_up, s_down = shared_gated
            a = (jax.nn.silu((hf @ s_gate.astype(cd)).astype(jnp.float32))
                 * (hf @ s_up.astype(cd))).astype(cd)
            open_ = None if w_sg is None else jax.nn.sigmoid(jnp.dot(
                hf.astype(jnp.float32), w_sg.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))[:, None]
            out = (a @ s_down.astype(cd)).astype(jnp.float32)
            y = y + (out if open_ is None else open_ * out)
    with jax.named_scope("moe.route"):
        fraction = jax.lax.stop_gradient(counts.astype(jnp.float32) / N)
        for ax in stat_axes:
            fraction = jax.lax.pmean(fraction, ax)
        stats = {}
        if scoring == "softmax":
            mean_prob = jnp.mean(probs, axis=0)
            if held is not None:  # this device's terms of the sum over E
                mean_prob = mean_prob[first:first + count]
            stats = {
                "lb_loss": E * jnp.sum(fraction * mean_prob),
                "z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            }
        stats["max_load_ratio"] = jnp.max(fraction) * (E / K)
        stats["dropped"] = ((A if held is None else jnp.sum(here))
                            - jnp.sum(counts)).astype(jnp.float32)
        if held is not None:
            stats["held_share"] = jnp.sum(fraction) / K
            c = held_chunk(A, count, E)
            stats["held_chunks"] = ((end + (c - 1)) // c).astype(jnp.float32)
        if zero_experts:
            stats["zero_share"] = jnp.mean(to_zero.astype(jnp.float32))
    return y.reshape(*lead, d), stats
