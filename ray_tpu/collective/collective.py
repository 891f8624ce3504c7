"""Collective groups and module-level collective ops.

Reference parity: ``python/ray/util/collective/collective.py`` — the
``GroupManager`` (:40) caches per-process groups; module functions look up the
group by name and execute. The NCCL group (``nccl_collective_group.py:128``)
maps here to :class:`XlaGroup` — collectives as jitted shard_map programs over
a 1-D device mesh (ICI on TPU) — and the Gloo group maps to
:class:`StoreGroup`, a cross-process fallback over the object store + head KV.
"""

from __future__ import annotations

import pickle
import threading
import time
from functools import lru_cache, partial
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ray_tpu.collective.types import Backend, ReduceOp


# --------------------------------------------------------------------------- #
# XLA in-process multi-device group (the NCCL replacement)
# --------------------------------------------------------------------------- #


class XlaGroup:
    """World = the caller's local XLA devices; ops are compiled XLA programs.

    On a TPU host this spans the host's chips over ICI; under
    ``xla_force_host_platform_device_count=N`` it spans N virtual CPU devices
    (the test topology). Compiled once per (op, world, shape, dtype) and
    cached — repeat calls are pure device execution, no trace overhead.
    """

    def __init__(self, world_size: int, group_name: str = "default",
                 devices: Optional[list] = None):
        import jax

        devs = devices or jax.devices()
        if world_size > len(devs):
            raise ValueError(
                f"world_size {world_size} exceeds {len(devs)} local devices")
        self.world_size = world_size
        self.group_name = group_name
        self.devices = devs[:world_size]
        from jax.sharding import Mesh

        self.mesh = Mesh(np.array(self.devices), ("col",))
        self._compiled: Dict[tuple, Any] = {}

    # -- helpers -----------------------------------------------------------

    def _to_global(self, tensors: Sequence[Any]):
        """Stack per-device tensors into one sharded global array (axis 0 =
        device axis), placing each shard on its device without host copies
        where possible."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if len(tensors) != self.world_size:
            raise ValueError(
                f"expected {self.world_size} per-device tensors, got {len(tensors)}")
        shape = np.shape(tensors[0])
        sharding = NamedSharding(self.mesh, P("col", *([None] * len(shape))))
        shards = [
            jax.device_put(np.asarray(t)[None, ...], d)
            for t, d in zip(tensors, self.devices)
        ]
        return jax.make_array_from_single_device_arrays(
            (self.world_size, *shape), sharding, shards)

    def _to_list(self, global_arr) -> List[Any]:
        return [np.asarray(s.data)[0] for s in
                sorted(global_arr.addressable_shards, key=lambda s: s.index[0])]

    def _program(self, op: str, reduce_op: ReduceOp, extra=()):
        import jax
        import functools
        from jax.sharding import PartitionSpec as P

        from ray_tpu.util.jax_compat import shard_map as _shard_map

        shard_map = functools.partial(_shard_map, check=False)

        key = (op, reduce_op, extra)
        if key in self._compiled:
            return self._compiled[key]

        def _reduce(x, axis_name):
            if reduce_op == ReduceOp.SUM:
                return jax.lax.psum(x, axis_name)
            if reduce_op == ReduceOp.MEAN:
                return jax.lax.pmean(x, axis_name)
            if reduce_op == ReduceOp.MAX:
                return jax.lax.pmax(x, axis_name)
            if reduce_op == ReduceOp.MIN:
                return jax.lax.pmin(x, axis_name)
            if reduce_op == ReduceOp.PRODUCT:
                return jax.lax.all_gather(x, axis_name).prod(axis=0)
            raise ValueError(reduce_op)

        spec_dev = P("col")
        if op == "allreduce":
            def fn(x):
                return _reduce(x, "col")
            prog = shard_map(fn, mesh=self.mesh, in_specs=spec_dev,
                             out_specs=spec_dev)
        elif op == "allgather":
            def fn(x):
                # local (1, *s) -> (world, *s), replicated on every device
                return jax.lax.all_gather(x[0], "col")
            prog = shard_map(fn, mesh=self.mesh, in_specs=spec_dev,
                             out_specs=P())
        elif op == "reducescatter":
            def fn(x):
                # local (1, world*k) -> reduce then keep this rank's k-chunk
                red = _reduce(x, "col")
                idx = jax.lax.axis_index("col")
                k = red.shape[1] // self.world_size
                return jax.lax.dynamic_slice_in_dim(red, idx * k, k, axis=1)
            prog = shard_map(fn, mesh=self.mesh, in_specs=spec_dev,
                             out_specs=spec_dev)
        elif op == "broadcast":
            (root,) = extra

            def fn(x):
                full = jax.lax.all_gather(x[0], "col")
                return full[root][None]
            prog = shard_map(fn, mesh=self.mesh, in_specs=spec_dev,
                             out_specs=spec_dev)
        elif op == "permute":
            (perm,) = extra  # tuple of (src, dst)

            def fn(x):
                return jax.lax.ppermute(x, "col", perm=list(perm))
            prog = shard_map(fn, mesh=self.mesh, in_specs=spec_dev,
                             out_specs=spec_dev)
        else:
            raise ValueError(op)
        compiled = jax.jit(prog)
        self._compiled[key] = compiled
        return compiled

    # -- public ops --------------------------------------------------------

    def allreduce(self, tensors: Sequence[Any], op: ReduceOp = ReduceOp.SUM):
        g = self._to_global(tensors)
        return self._to_list(self._program("allreduce", op)(g))

    def allgather(self, tensors: Sequence[Any]):
        g = self._to_global(tensors)
        out = np.asarray(self._program("allgather", ReduceOp.SUM)(g))
        return [out for _ in range(self.world_size)]

    def reducescatter(self, tensors: Sequence[Any], op: ReduceOp = ReduceOp.SUM):
        flat = [np.reshape(t, (1, -1)) for t in tensors]
        if flat[0].shape[1] % self.world_size:
            raise ValueError("reducescatter requires size divisible by world")
        g = self._to_global([f[0] for f in flat])
        return self._to_list(self._program("reducescatter", op)(g))

    def broadcast(self, tensors: Sequence[Any], src_rank: int = 0):
        g = self._to_global(tensors)
        return self._to_list(self._program("broadcast", ReduceOp.SUM,
                                           (src_rank,))(g))

    def send_recv(self, tensors: Sequence[Any], pairs: Sequence[tuple]):
        """ppermute: list of (src_rank, dst_rank) pairs."""
        g = self._to_global(tensors)
        return self._to_list(self._program("permute", ReduceOp.SUM,
                                           (tuple(pairs),))(g))

    def barrier(self):
        self.allreduce([np.zeros(1) for _ in range(self.world_size)])

    def destroy(self):
        self._compiled.clear()


# --------------------------------------------------------------------------- #
# In-program collectives (for shard_map / pmap bodies)
# --------------------------------------------------------------------------- #
#
# The group classes above are *host-level* collectives: eager calls from
# driver code over materialized tensors. These helpers are the *traced*
# counterpart — called INSIDE a shard_map/pmap program (e.g. the SPMD
# train step's gradient reduction, train/spmd.py), where the axis names
# of the enclosing mesh are in scope.


def psum_tree(tree, axis_names):
    """Sum every leaf of ``tree`` over ``axis_names`` (str or sequence).

    Inside shard_map this lowers to one fused cross-replica all-reduce
    per leaf (XLA combines adjacent psums over the same axes)."""
    import jax

    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    axis_names = tuple(axis_names)
    if not axis_names:
        return tree

    def red(x):
        for ax in axis_names:
            x = jax.lax.psum(x, ax)
        return x

    return jax.tree.map(red, tree)


def pmean_tree(tree, axis_names):
    """Mean of every leaf over ``axis_names`` — the gradient reduction
    of a data-parallel shard_map train step. The divisor
    (``jax.lax.axis_size``) is a trace-time constant."""
    import jax

    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    axis_names = tuple(axis_names)
    if not axis_names:
        return tree
    denom = 1
    for ax in axis_names:
        denom = denom * jax.lax.axis_size(ax)
    return jax.tree.map(lambda x: x / denom, psum_tree(tree, axis_names))


# --------------------------------------------------------------------------- #
# Cross-process store-backed group (gloo analog)
# --------------------------------------------------------------------------- #


class StoreGroup:
    """Collectives across worker processes via the object store + head KV.

    Rendezvous and sequencing go through the head's KV (the analog of the
    reference's named-actor NCCLUniqueID store); payloads ride the shared
    object store. Correctness-oriented: used for host-side coordination, not
    the tensor hot path (which is jitted XLA inside each worker).
    """

    NS = "collective"

    def __init__(self, world_size: int, rank: int, group_name: str = "default"):
        from ray_tpu.core.runtime import get_current_runtime

        self.rt = get_current_runtime()
        if self.rt is None:
            raise RuntimeError("runtime not initialized")
        self.world_size = world_size
        self.rank = rank
        self.group_name = group_name
        self._seq = 0
        self._p2p: Dict[tuple, int] = {}
        # register membership (+ our node hex, so the src rank can scope
        # broadcast pushes to MEMBER nodes instead of the whole cluster)
        try:
            node_hex = self.rt.runtime_context()["node_id"]
        except Exception:
            node_hex = ""
        self._kv_put(f"member/{rank}",
                     node_hex.encode() if node_hex else b"1")
        deadline = time.monotonic() + 60
        while len(self._members()) < world_size:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"collective group {group_name}: only "
                    f"{len(self._members())}/{world_size} joined")
            time.sleep(0.02)

    def _key(self, suffix: str) -> bytes:
        return f"{self.group_name}/{suffix}".encode()

    def _kv_put(self, suffix: str, value: bytes):
        self.rt.kv("put", self._key(suffix), value, self.NS)

    def _kv_get(self, suffix: str) -> Optional[bytes]:
        return self.rt.kv("get", self._key(suffix), self.NS)

    def _members(self):
        return self.rt.kv("keys", self._key("member/"), self.NS)

    def _put_tensor(self, seq: int, rank: int, tensor):
        ref = self.rt.put(np.asarray(tensor))
        self._kv_put(f"t/{seq}/{rank}", ref.id.binary())
        return ref

    def _get_tensor(self, seq: int, rank: int, timeout: float = 120.0):
        from ray_tpu.core.ids import ObjectID
        from ray_tpu.core.object_ref import ObjectRef

        deadline = time.monotonic() + timeout
        while True:
            raw = self._kv_get(f"t/{seq}/{rank}")
            if raw is not None:
                ref = ObjectRef(ObjectID(raw), _register=False)
                return self.rt.get([ref])[0]
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"collective {self.group_name} seq={seq}: rank {rank} "
                    f"never contributed")
            time.sleep(0.005)

    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        seq = self._seq
        self._seq += 1
        self._put_tensor(seq, self.rank, tensor)
        parts = [self._get_tensor(seq, r) for r in range(self.world_size)]
        stack = np.stack(parts)
        if op == ReduceOp.SUM:
            return stack.sum(0)
        if op == ReduceOp.MEAN:
            return stack.mean(0)
        if op == ReduceOp.MAX:
            return stack.max(0)
        if op == ReduceOp.MIN:
            return stack.min(0)
        if op == ReduceOp.PRODUCT:
            return stack.prod(0)
        raise ValueError(op)

    def allgather(self, tensor):
        seq = self._seq
        self._seq += 1
        self._put_tensor(seq, self.rank, tensor)
        return [self._get_tensor(seq, r) for r in range(self.world_size)]

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        red = self.allreduce(tensor, op)
        flat = np.reshape(red, (-1,))
        k = flat.shape[0] // self.world_size
        return flat[self.rank * k:(self.rank + 1) * k]

    def _member_node_hexes(self):
        hexes = set()
        for r in range(self.world_size):
            raw = self._kv_get(f"member/{r}")
            if raw and raw != b"1":
                hexes.add(raw.decode())
        return hexes

    def broadcast(self, tensor, src_rank: int = 0):
        seq = self._seq
        self._seq += 1
        if self.rank == src_rank:
            arr = np.asarray(tensor)
            ref = self._put_tensor(seq, src_rank, arr)
            # large payloads ride the binomial push tree so N receivers
            # don't issue N serial pulls from this node — scoped to the
            # GROUP's nodes, not the whole cluster (reference:
            # push_manager.h broadcast; weight-sync hot path)
            if arr.nbytes > 1 << 20:
                try:
                    targets = list(self._member_node_hexes())
                    if hasattr(self.rt, "head"):
                        self.rt.head.broadcast_object(ref.id, targets or None)
                    else:
                        self.rt.rpc.call("rpc", "broadcast_object",
                                         ref.id, targets or None)
                except Exception:
                    pass  # best-effort prefetch; pulls still work
            return arr
        return self._get_tensor(seq, src_rank)

    def send(self, tensor, dst_rank: int):
        """P2P ops use a per-pair keyspace so collective _seq counters stay
        aligned across all ranks (pairwise traffic must not desynchronize
        group-wide sequencing)."""
        n = self._p2p.get((self.rank, dst_rank), 0)
        self._p2p[(self.rank, dst_rank)] = n + 1
        ref = self.rt.put(np.asarray(tensor))
        self._kv_put(f"p2p/{self.rank}/{dst_rank}/{n}", ref.id.binary())

    def recv(self, src_rank: int, timeout: float = 120.0):
        from ray_tpu.core.ids import ObjectID
        from ray_tpu.core.object_ref import ObjectRef

        n = self._p2p.get((src_rank, self.rank), 0)
        self._p2p[(src_rank, self.rank)] = n + 1
        deadline = time.monotonic() + timeout
        while True:
            raw = self._kv_get(f"p2p/{src_rank}/{self.rank}/{n}")
            if raw is not None:
                return self.rt.get([ObjectRef(ObjectID(raw), _register=False)])[0]
            if time.monotonic() > deadline:
                raise TimeoutError(f"recv from rank {src_rank} timed out")
            time.sleep(0.005)

    def barrier(self):
        self.allreduce(np.zeros(1))

    def destroy(self):
        # drop all of this group's KV keys so a recreated group under the
        # same name doesn't read stale tensors
        for key in self.rt.kv("keys", self._key(""), self.NS):
            self.rt.kv("del", key, self.NS)


# --------------------------------------------------------------------------- #
# Group manager + module API (reference: collective.py GroupManager :40)
# --------------------------------------------------------------------------- #


class GroupManager:
    _instance: Optional["GroupManager"] = None
    _lock = threading.Lock()

    def __init__(self):
        self.groups: Dict[str, Any] = {}

    @classmethod
    def instance(cls) -> "GroupManager":
        with cls._lock:
            if cls._instance is None:
                cls._instance = GroupManager()
            return cls._instance

    def create(self, backend: str, world_size: int, rank: Optional[int],
               group_name: str):
        backend = Backend.normalize(backend)
        if group_name in self.groups:
            raise ValueError(f"collective group {group_name!r} already exists")
        if backend == Backend.XLA:
            g = XlaGroup(world_size, group_name)
        else:
            if rank is None:
                raise ValueError("backend='store' requires a rank")
            g = StoreGroup(world_size, rank, group_name)
        self.groups[group_name] = g
        return g

    def get(self, group_name: str):
        g = self.groups.get(group_name)
        if g is None:
            raise ValueError(
                f"collective group {group_name!r} is not initialized; call "
                f"init_collective_group() first")
        return g

    def destroy(self, group_name: str):
        g = self.groups.pop(group_name, None)
        if g is not None:
            g.destroy()


def init_collective_group(world_size: int, rank: Optional[int] = None,
                          backend: str = "xla",
                          group_name: str = "default"):
    """Initialize a collective group in the calling process (reference:
    collective.py:120)."""
    return GroupManager.instance().create(backend, world_size, rank, group_name)


def create_collective_group(actors, world_size: int, ranks: List[int],
                            backend: str = "store",
                            group_name: str = "default"):
    """Declaratively set up a group across actors (reference:
    collective.py:151): each actor joins via an internally-handled method."""
    import ray_tpu

    refs = [
        a.__collective_init__.remote(world_size, r, backend, group_name)
        for a, r in zip(actors, ranks)
    ]
    ray_tpu.get(refs, timeout=120)


def destroy_collective_group(group_name: str = "default"):
    GroupManager.instance().destroy(group_name)


def get_group_handle(group_name: str = "default"):
    return GroupManager.instance().get(group_name)


def allreduce(tensor_or_list, group_name: str = "default",
              op: ReduceOp = ReduceOp.SUM):
    return get_group_handle(group_name).allreduce(tensor_or_list, op)


def reduce(tensor_or_list, dst_rank: int = 0, group_name: str = "default",
           op: ReduceOp = ReduceOp.SUM):
    """Implemented as allreduce (every rank gets the result); only dst_rank's
    value is meaningful per the reference contract — on TPU the ICI
    collective is all-to-all anyway, so there is no savings in a true
    single-destination reduce."""
    return get_group_handle(group_name).allreduce(tensor_or_list, op)


def broadcast(tensor_or_list, src_rank: int = 0, group_name: str = "default"):
    return get_group_handle(group_name).broadcast(tensor_or_list, src_rank)


def allgather(tensor_or_list, group_name: str = "default"):
    return get_group_handle(group_name).allgather(tensor_or_list)


def reducescatter(tensor_or_list, group_name: str = "default",
                  op: ReduceOp = ReduceOp.SUM):
    return get_group_handle(group_name).reducescatter(tensor_or_list, op)


def send(tensor, dst_rank: int, group_name: str = "default"):
    g = get_group_handle(group_name)
    if isinstance(g, XlaGroup):
        raise ValueError("use send_recv with explicit pairs for XlaGroup")
    return g.send(tensor, dst_rank)


def recv(src_rank: int, group_name: str = "default"):
    g = get_group_handle(group_name)
    if isinstance(g, XlaGroup):
        raise ValueError("use send_recv with explicit pairs for XlaGroup")
    return g.recv(src_rank)


def barrier(group_name: str = "default"):
    get_group_handle(group_name).barrier()
