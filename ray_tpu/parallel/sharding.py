"""Logical-axis sharding rules → NamedShardings (t5x/flax-partitioning style).

Arrays carry *logical* axis names (batch, seq, embed, heads, mlp, vocab, ...);
rules map logical axes to mesh axes; XLA/GSPMD does the rest. This replaces
the reference's reliance on torch FSDP/DeepSpeed for sharding math.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

# logical axis -> mesh axis (or tuple of axes). None = replicated.
DEFAULT_RULES: Tuple[Tuple[str, object], ...] = (
    ("batch", ("slice", "data", "fsdp")),
    ("seq", "seq"),                # activation sequence axis (ring attention)
    ("embed", "fsdp"),             # param fsdp shard axis
    ("heads", "tensor"),
    ("kv_heads", "tensor"),
    ("mlp", "tensor"),
    ("vocab", "tensor"),
    ("expert", "expert"),
    ("kv", None),
    ("layers", None),
    ("stage", "pipe"),
)


def _mesh_axes_for(logical: Optional[str], rules, mesh) -> Optional[object]:
    if logical is None:
        return None
    for name, axes in rules:
        if name == logical:
            if axes is None:
                return None
            if isinstance(axes, (tuple, list)):
                present = tuple(a for a in axes if a in mesh.axis_names)
                return present if present else None
            return axes if axes in mesh.axis_names else None
    return None


def logical_spec(logical_axes: Sequence[Optional[str]], mesh, rules=None):
    """PartitionSpec for an array annotated with logical axis names."""
    from jax.sharding import PartitionSpec as P

    rules = rules or DEFAULT_RULES
    return P(*(_mesh_axes_for(ax, rules, mesh) for ax in logical_axes))


def logical_sharding(logical_axes: Sequence[Optional[str]], mesh, rules=None):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, logical_spec(logical_axes, mesh, rules))


def shard_pytree(tree, logical_tree, mesh, rules=None):
    """device_put a pytree of host arrays according to per-leaf logical axes.

    ``logical_tree`` mirrors ``tree`` with tuples of logical axis names.
    """
    import jax

    def place(x, axes):
        return jax.device_put(x, logical_sharding(axes, mesh, rules))

    return jax.tree.map(place, tree, logical_tree,
                        is_leaf=lambda x: x is None)


def fsdp_sharding(tree, mesh, axis: str = "fsdp", min_size: int = 2 ** 16):
    """Automatic FSDP-style param sharding: shard each param's largest
    divisible dimension over the fsdp axis; small params replicate.

    The ZeRO-3 analog without optimizer-state partitioning bookkeeping —
    GSPMD shards optimizer state the same way for free because optax state
    mirrors param shapes.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if axis not in mesh.axis_names:
        return jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(mesh, P())), tree)
    n = mesh.shape[axis]

    def spec_for(x):
        if x.ndim == 0 or x.size < min_size:
            return P()
        dims = sorted(range(x.ndim), key=lambda d: -x.shape[d])
        for d in dims:
            if x.shape[d] % n == 0:
                out = [None] * x.ndim
                out[d] = axis
                return P(*out)
        return P()

    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, spec_for(x))), tree)


def opt_state_shardings(optimizer, sample_params, param_shardings, default):
    """Match optimizer-state leaves to param shardings *structurally*.

    Optax moment pytrees mirror the params pytree, so a state leaf whose
    path suffix equals a param path gets that param's sharding. (Shape
    matching is wrong: e.g. wq/wo share a shape but have transposed
    specs.) Leaves with no matching param path (step counters, scalars)
    get ``default``, as do path-matched leaves whose shape differs from
    the param's — factored states like adafactor's ``v_row``/``v_col``
    drop a dimension, so the param's spec cannot apply.
    """
    import jax
    from jax.tree_util import tree_flatten_with_path, tree_map_with_path

    opt_state = jax.eval_shape(optimizer.init, sample_params)
    flat_params, _ = tree_flatten_with_path(sample_params)
    by_path = {}
    for (path, leaf), ps in zip(flat_params,
                                jax.tree.leaves(param_shardings)):
        by_path[tuple(str(k) for k in path)] = (ps, tuple(leaf.shape))

    def match(path, leaf):
        p = tuple(str(k) for k in path)
        for start in range(len(p)):
            hit = by_path.get(p[start:])
            if hit is not None:
                ps, shape = hit
                if tuple(getattr(leaf, "shape", ())) == shape:
                    return ps
                return default
        return default

    return tree_map_with_path(match, opt_state)


def constraint(x, logical_axes, mesh, rules=None):
    """with_sharding_constraint using logical names (inside jit)."""
    import jax

    return jax.lax.with_sharding_constraint(
        x, logical_sharding(logical_axes, mesh, rules))


def observed_placement_jit(fn, sharding, program: str):
    """jit ``fn`` with ``out_shardings=sharding``, registered with the
    XLA compile observatory under ``program`` — the jit-entry seam the
    placement/gather helpers (and ``train/spmd.py``) share, so every
    placement executable lands in the compiled-program registry with
    its compile time and cost/memory analyses."""
    import jax

    from ray_tpu.util.xla_observatory import observe_compiled

    return observe_compiled(jax.jit(fn, out_shardings=sharding), program)


def shard_device_put(x, sharding):
    """Per-shard host→device placement for ingest.

    Slices the host array into exactly the shards ``sharding``
    prescribes and ``device_put``s each slice straight onto its device,
    assembling the global array with
    ``jax.make_array_from_single_device_arrays`` — each device's H2D
    copy is a separate async transfer of batch/N bytes, dispatched
    back-to-back, instead of one synchronous global put. With a single
    device (or a fully-replicated spec) this degrades to a plain
    ``device_put``.
    """
    import jax
    import numpy as np

    devices = getattr(sharding, "device_set", None)
    if devices is None or len(devices) <= 1:
        return jax.device_put(x, sharding)
    x = np.asarray(x) if not isinstance(x, np.ndarray) else x
    index_map = sharding.addressable_devices_indices_map(x.shape)
    shards = [jax.device_put(np.ascontiguousarray(x[idx]), d)
              for d, idx in index_map.items()]
    return jax.make_array_from_single_device_arrays(
        x.shape, sharding, shards)


def shard_layout(x) -> dict:
    """Where one sharded array actually lives: how many devices hold a
    shard of it and how many of those shards are distinct slices. The
    check that a mesh built from real devices spread a leaf over all of
    them, and did not leave every shard on the first."""
    shards = x.addressable_shards
    return {
        "shape": list(x.shape),
        "devices": len({s.device.id for s in shards}),
        "distinct_shards": len({str(s.index) for s in shards}),
        "shard_shape": list(shards[0].data.shape),
    }


def param_residency_bytes(params, specs, mesh, mode: str = "upfront",
                          scan_key: str = "layers", window: int = 2):
    """Analytic peak per-device LIVE param bytes inside the shard_map
    train step (train/spmd.py) — the resident shards plus the
    fsdp-gathered working copies the gather schedule keeps alive.

    ``"upfront"`` gathers the whole tree before the first layer, so
    every leaf's fsdp-full copy is simultaneously live. ``"streamed"``
    keeps the scanned stack (the top-level ``scan_key`` subtree, leaves
    shaped [L, ...]) sharded and holds at most ``window`` fsdp-full
    layers (current + prefetched next); non-scanned leaves still gather
    up front. Tensor-sharded dims stay sharded under both schedules.
    ``params`` may be an ``eval_shape`` tree. Returns
    ``{"mode", "shard_bytes", "gathered_bytes", "peak_bytes"}`` —
    analytic, so it gates identically on CPU and TPU.
    """
    import jax
    import numpy as np
    from jax.tree_util import tree_flatten_with_path

    def nbytes(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        dt = np.dtype(getattr(leaf, "dtype", np.float32))
        return int(np.prod(shape, dtype=np.int64)) * dt.itemsize

    def div(spec, only=None):
        d = 1
        for ax in spec:
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                if a is None or a not in mesh.axis_names:
                    continue
                if only is None or a in only:
                    d *= mesh.shape[a]
        return d

    def isspec(x):
        return isinstance(x, jax.sharding.PartitionSpec)

    spec_by_path = {path: s for path, s in
                    tree_flatten_with_path(specs, is_leaf=isspec)[0]}
    shard_bytes = 0
    gathered_bytes = 0
    for path, leaf in tree_flatten_with_path(params)[0]:
        spec = spec_by_path[path]
        b = nbytes(leaf)
        shard_bytes += b // div(spec)
        # fsdp-gathered working copy: only tensor dims stay sharded
        g = b // div(spec, only=("tensor",))
        key0 = str(getattr(path[0], "key", getattr(path[0], "idx", path[0])))
        if mode == "streamed" and key0 == scan_key:
            L = max(1, int(getattr(leaf, "shape", (1,))[0]))
            gathered_bytes += min(window, L) * (g // L)
        else:
            gathered_bytes += g
    return {"mode": mode, "shard_bytes": int(shard_bytes),
            "gathered_bytes": int(gathered_bytes),
            "peak_bytes": int(shard_bytes + gathered_bytes)}
