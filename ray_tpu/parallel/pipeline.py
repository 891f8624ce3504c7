"""Pipeline parallelism over the ``pipe`` mesh axis, TPU-native.

The reference delegates pipeline parallelism to DeepSpeed/Megatron engines
(SURVEY.md §2.3); here it is in-framework and expressed the XLA way: a
GPipe-style microbatch schedule written as ``lax.scan`` over pipeline ticks
with ``lax.ppermute`` moving activations to the next stage, the whole thing
living inside a single ``shard_map`` region over the mesh. Because the
schedule is ordinary traced JAX (scan + ppermute + where), **autodiff
derives the backward pipeline automatically** — the transpose of ppermute
is the reverse rotation, so gradients flow stage P-1 → 0 with the same
overlap structure, and XLA overlaps the ICI transfer with stage compute.

Schedule (per device, SPMD): at tick ``t`` of ``M + P - 1`` ticks,
stage 0 feeds microbatch ``t`` (while ``t < M``), every stage applies its
layer block to whatever sits in its buffer, and the result rotates one hop
along the ``pipe`` axis. Stage ``P-1`` has produced microbatch ``t-(P-1)``
by tick ``t``; outputs accumulate into a per-device buffer and are
broadcast back to all stages at the end (a masked ``psum``) so downstream
loss code is uniform SPMD.

Bubble fraction is the GPipe ``(P-1)/(M+P-1)``; pick ``M >= 4*P``.

Constraints (by construction of the rotation): ``stage_fn`` must map an
activation pytree to one of the same structure/shape/dtype (a residual
stream — true for transformer blocks). Embedding/head live outside the
pipelined region, replicated over ``pipe``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


def num_ticks(num_microbatches: int, num_stages: int) -> int:
    return num_microbatches + num_stages - 1


def split_microbatches(batch, num_microbatches: int):
    """[B, ...] pytree -> [M, B/M, ...] pytree (leading microbatch axis)."""

    def split(x):
        B = x.shape[0]
        if B % num_microbatches:
            raise ValueError(
                f"batch {B} not divisible by num_microbatches "
                f"{num_microbatches}")
        return x.reshape((num_microbatches, B // num_microbatches)
                         + x.shape[1:])

    return jax.tree.map(split, batch)


def merge_microbatches(mb):
    """Inverse of :func:`split_microbatches`."""
    return jax.tree.map(
        lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), mb)


def pipelined_apply(stage_fn: Callable[[Any, Any], Any], stage_params,
                    microbatches, *, axis_name: str = "pipe"):
    """GPipe schedule — call **inside** ``shard_map`` over ``axis_name``.

    stage_fn(stage_params, x) -> y with y matching x's structure/shapes.
    ``stage_params``: this device's stage slice of the layer stack.
    ``microbatches``: [M, mb, ...] pytree, identical on every stage (the
    pipe axis must not shard the batch).
    Returns [M, mb, ...] outputs, valid on every stage.
    """
    P = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = jax.tree.leaves(microbatches)[0].shape[0]
    rotate = [(i, (i + 1) % P) for i in range(P)]

    def tick(carry, t):
        buf, outputs = carry
        # stage 0 ingests microbatch t (clamped; ticks >= M recompute M-1,
        # whose result is discarded by the output mask)
        mb_t = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(
                a, jnp.clip(t, 0, M - 1), 0, keepdims=False), microbatches)
        x = jax.tree.map(
            lambda fresh, held: jnp.where(idx == 0, fresh, held), mb_t, buf)
        y = stage_fn(stage_params, x)
        # stage P-1 finished microbatch t-(P-1) this tick
        out_t = t - (P - 1)
        write = jnp.logical_and(idx == P - 1, out_t >= 0)
        safe = jnp.clip(out_t, 0, M - 1)

        def upd(o, yy):
            cur = lax.dynamic_index_in_dim(o, safe, 0, keepdims=False)
            return lax.dynamic_update_index_in_dim(
                o, jnp.where(write, yy, cur), safe, 0)

        outputs = jax.tree.map(upd, outputs, y)
        buf = lax.ppermute(y, axis_name, perm=rotate)
        return (buf, outputs), None

    zero_buf = jax.tree.map(
        lambda a: jnp.zeros(a.shape[1:], a.dtype), microbatches)
    zero_out = jax.tree.map(jnp.zeros_like, microbatches)
    (_, outputs), _ = lax.scan(
        tick, (zero_buf, zero_out),
        jnp.arange(num_ticks(M, P), dtype=jnp.int32))
    # broadcast the last stage's outputs to every stage (masked psum), so
    # callers compute loss uniformly; psum's transpose keeps grads correct
    mask = (idx == P - 1).astype(jax.tree.leaves(outputs)[0].dtype)
    return jax.tree.map(
        lambda o: lax.psum(o * mask.astype(o.dtype), axis_name), outputs)


def stage_slice_len(total_layers: int, num_stages: int) -> int:
    if total_layers % num_stages:
        raise ValueError(
            f"{total_layers} layers not divisible into {num_stages} stages")
    return total_layers // num_stages


def make_pipelined_fn(stage_fn, mesh, num_microbatches: int, *,
                      axis_name: str = "pipe",
                      stage_param_specs, batch_spec):
    """Wrap :func:`pipelined_apply` in shard_map over the full mesh.

    ``stage_param_specs``: pytree of PartitionSpecs for the *stacked* stage
    params (leading stage dim on ``axis_name``). ``batch_spec``: spec for
    one [B, ...] activation (batch sharded over data axes, NOT pipe).
    Returns fn(stage_params, batch) -> out with batch/out shape [B, ...].
    """
    from jax.sharding import PartitionSpec as P

    def inner(stage_params, batch):
        # shard_map hands us the local stage slice with its leading
        # (length-1) stage dim still present: drop it
        local = jax.tree.map(lambda a: a[0], stage_params)
        mb = split_microbatches(batch, num_microbatches)
        out = pipelined_apply(stage_fn, local, mb, axis_name=axis_name)
        return merge_microbatches(out)

    from ray_tpu.util.jax_compat import shard_map

    return shard_map(
        inner, mesh=mesh,
        in_specs=(stage_param_specs, batch_spec),
        out_specs=batch_spec, check=False)
