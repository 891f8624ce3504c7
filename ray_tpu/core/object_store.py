"""Node-local object store: shared-memory arena + in-process memory store.

Analog of the reference's plasma store (``src/ray/object_manager/plasma/``) and
the CoreWorker in-process memory store (``store_provider/memory_store/``):

- Small objects (< ``max_direct_call_object_size``) live inline in the owner's
  memory store and travel inside RPC replies (reference: ray_config_def.h:199).
- Large objects are written into a node-wide mmap'd arena on /dev/shm so every
  worker process on the node reads them zero-copy (reference: plasma fd-passing
  via fling.cc; here all workers map the same session file).
- Allocation uses the native C++ allocator (``ray_tpu._native.plasma``) when
  built, else a Python first-fit free list (reference: dlmalloc arena).
- When the arena fills, sealed objects are spilled to disk files and restored
  on demand (reference: local_object_manager.h SpillObjects / fallback
  allocation plasma_allocator.h:83-97).
"""

from __future__ import annotations

import contextlib
import logging
import mmap
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .config import global_config
from .exceptions import ObjectStoreFullError, ObjectLostError
from .ids import ObjectID

logger = logging.getLogger(__name__)

# Store write traffic. The data-pipeline benches assert operator fusion
# reduces per-stage materialization through these (puts = inline + arena
# creations, bytes = payload bytes written). Imported lazily: this module
# loads inside the ray_tpu.core import chain, before ray_tpu.util's
# package __init__ (which needs ray_tpu.remote) can run. Both counters
# publish via ONE atomic global assignment — concurrent first puts must
# never observe a half-initialized pair.
_m_store_put = None


def _count_put(nbytes: int) -> None:
    global _m_store_put
    m = _m_store_put
    if m is None:
        from ray_tpu.util.metrics import Counter

        m = (Counter("ray_tpu_object_store_puts_total",
                     "Objects written into a local store"),
             Counter("ray_tpu_object_store_put_bytes_total",
                     "Bytes written into local stores"))
        _m_store_put = m
    m[0].inc()
    m[1].inc(nbytes)


# Store-pressure telemetry (the `ray memory` store half): lazily-created
# counter bundle shared by every store in the process, tagged {node}.
# Same one-shot atomic publish discipline as _m_store_put above.
_m_store_tel = None


def _telemetry():
    global _m_store_tel
    m = _m_store_tel
    if m is None:
        from ray_tpu.util.metrics import Counter, Gauge

        m = {
            "spilled": Counter("ray_tpu_object_store_spilled_objects_total",
                               "Objects spilled to disk under pressure"),
            "spilled_bytes": Counter(
                "ray_tpu_object_store_spilled_bytes_total",
                "Bytes spilled to disk under pressure"),
            "restored": Counter(
                "ray_tpu_object_store_restored_objects_total",
                "Spilled objects restored into the arena"),
            "restored_bytes": Counter(
                "ray_tpu_object_store_restored_bytes_total",
                "Bytes restored from spill files"),
            "evicted": Counter("ray_tpu_object_store_evicted_objects_total",
                               "Unreferenced objects evicted under pressure"),
            "evicted_bytes": Counter(
                "ray_tpu_object_store_evicted_bytes_total",
                "Bytes evicted under pressure"),
            "used": Gauge("ray_tpu_object_store_bytes_used",
                          "Arena bytes allocated"),
            "free": Gauge("ray_tpu_object_store_bytes_free",
                          "Arena bytes free"),
            "inline": Gauge("ray_tpu_object_store_inline_bytes",
                            "Bytes held inline in the memory store"),
            "frag": Gauge("ray_tpu_object_store_fragmentation_ratio",
                          "1 - largest free extent / total free bytes"),
        }
        _m_store_tel = m
    return m


# --------------------------------------------------------------------------- #
# Allocator
# --------------------------------------------------------------------------- #


class FreeListAllocator:
    """First-fit free-list allocator over a fixed arena (Python fallback)."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        # sorted list of (offset, size) free extents
        self._free: List[Tuple[int, int]] = [(0, capacity)]
        self._allocated: Dict[int, int] = {}
        self._lock = threading.Lock()

    def allocate(self, size: int) -> Optional[int]:
        size = max(8, (size + 63) & ~63)  # 64B alignment
        with self._lock:
            for i, (off, sz) in enumerate(self._free):
                if sz >= size:
                    if sz == size:
                        self._free.pop(i)
                    else:
                        self._free[i] = (off + size, sz - size)
                    self._allocated[off] = size
                    return off
        return None

    def free(self, offset: int) -> None:
        with self._lock:
            size = self._allocated.pop(offset)
            self._free.append((offset, size))
            self._free.sort()
            # coalesce
            merged: List[Tuple[int, int]] = []
            for off, sz in self._free:
                if merged and merged[-1][0] + merged[-1][1] == off:
                    merged[-1] = (merged[-1][0], merged[-1][1] + sz)
                else:
                    merged.append((off, sz))
            self._free = merged

    def bytes_allocated(self) -> int:
        with self._lock:
            return sum(self._allocated.values())

    def free_stats(self) -> Tuple[int, int, int]:
        """(free_bytes, free_extents, largest_free_extent) — the
        fragmentation signal behind the store gauges."""
        with self._lock:
            if not self._free:
                return (0, 0, 0)
            sizes = [sz for _off, sz in self._free]
            return (sum(sizes), len(sizes), max(sizes))


_allocator_kind: Optional[str] = None  # what _make_allocator last chose


def _make_allocator(capacity: int):
    """The native arena allocator (built from ``_native/plasma_alloc.cpp``
    on first use), else the Python free list. Why not the native one is
    said once; :func:`allocator_kind` reports the choice afterwards."""
    global _allocator_kind
    try:
        from ray_tpu._native.plasma import NativeAllocator

        alloc = NativeAllocator(capacity)
        _allocator_kind = "native"
        return alloc
    except Exception as e:  # noqa: BLE001 - no toolchain / build failure
        if _allocator_kind != "python":
            detail = getattr(e, "stderr", b"") or b""
            logger.warning(
                "native plasma allocator unavailable (%s: %s%s); using the "
                "Python free-list allocator", type(e).__name__, e,
                (": " + detail.decode(errors="replace")[-500:])
                if detail else "")
        _allocator_kind = "python"
        return FreeListAllocator(capacity)


def allocator_kind() -> Optional[str]:
    """"native" or "python": the arena allocator this process's stores
    got (None before any store was created here)."""
    return _allocator_kind


# --------------------------------------------------------------------------- #
# Arena (one per node, mapped by every worker on that node)
# --------------------------------------------------------------------------- #


class PlasmaArena:
    """A single mmap'd file on /dev/shm holding all large-object payloads."""

    def __init__(self, path: str, capacity: int, create: bool):
        self.path = path
        self.capacity = capacity
        flags = os.O_RDWR | (os.O_CREAT if create else 0)
        self._fd = os.open(path, flags, 0o600)
        if create:
            os.ftruncate(self._fd, capacity)
        self._mm = mmap.mmap(self._fd, capacity)
        self.allocator = _make_allocator(capacity) if create else None

    @property
    def fd(self) -> int:
        """Backing-file descriptor (os.sendfile source for zero-copy sends)."""
        return self._fd

    def view(self, offset: int, size: int) -> memoryview:
        return memoryview(self._mm)[offset : offset + size]

    def close(self, unlink: bool = False):
        # Zero-copy readers may still hold memoryviews into the map; in that
        # case leave the mapping to the GC and just unlink the backing file.
        try:
            self._mm.close()
        except BufferError:
            pass
        try:
            os.close(self._fd)
        except OSError:
            pass
        if unlink:
            try:
                os.unlink(self.path)
            except OSError:
                pass


# --------------------------------------------------------------------------- #
# Store
# --------------------------------------------------------------------------- #


class ReadHandle:
    """Pinned view of a sealed arena extent (see open_read): ``view`` for
    mmap reads, (``fd``, ``offset``) for os.sendfile zero-copy sends."""

    __slots__ = ("view", "fd", "offset")

    def __init__(self, view: memoryview, fd: int, offset: int):
        self.view = view
        self.fd = fd
        self.offset = offset


@dataclass
class ObjectEntry:
    object_id: ObjectID
    size: int = 0
    inline: Optional[bytes] = None  # small objects
    offset: int = -1  # arena offset for large objects
    sealed: bool = False
    is_error: bool = False  # payload is a serialized exception
    mapped: bool = False  # a zero-copy view was handed out; do not move
    spilled_path: Optional[str] = None
    owner_node: Optional[bytes] = None
    ref_count: int = 0
    last_access: float = field(default_factory=time.monotonic)
    creating: bool = False  # allocated, being written
    # transfer readers streaming this extent to a peer (open_read): the
    # extent must not move or free mid-send; unlike ``mapped`` the pin is
    # scoped — delete() during a send defers the free to the last release
    readers: int = 0
    pending_free: bool = False  # deleted while readers > 0
    created_ts: float = field(default_factory=time.time)  # wall-clock age
    # receive-side replica (node-to-node pull/push/subscription cache):
    # the bytes exist elsewhere, so eviction never destroys the only copy
    transfer: bool = False


class LocalObjectStore:
    """Node-local store combining inline memory store + shared arena.

    Thread-safe; the node's RPC threads and driver call into it concurrently.
    """

    def __init__(self, session_dir: str, node_hex: str, capacity: Optional[int] = None,
                 pin_check=None, pin_check_authoritative: bool = True):
        # pin_check(oid) -> bool: owner-side liveness (head ref counts). Read
        # lock-free by design: called under the store lock, and the head may
        # call into the store while holding its own lock (ABBA otherwise).
        # pin_check_authoritative=False (daemon stores, which only see the
        # node-local holder lease — the old per-object is_pinned head RPC
        # is gone): eviction is then restricted to TRANSFER copies; primary
        # copies spill to disk instead of being destroyed, since a remote
        # owner may still reference them.
        self._pin_check = pin_check or (lambda oid: False)
        self._pin_authoritative = pin_check_authoritative
        cfg = global_config()
        self.capacity = capacity or cfg.object_store_memory
        shm_dir = "/dev/shm" if os.path.isdir("/dev/shm") else session_dir
        self.arena_path = os.path.join(shm_dir, f"raytpu_plasma_{node_hex}")
        self.arena = PlasmaArena(self.arena_path, self.capacity, create=True)
        self.spill_dir = cfg.object_spilling_dir or os.path.join(session_dir, "spill")
        os.makedirs(self.spill_dir, exist_ok=True)
        self._entries: Dict[ObjectID, ObjectEntry] = {}
        from .lock_debug import tracked_rlock

        self._lock = tracked_rlock("LocalObjectStore._lock")
        self._sealed_cv = threading.Condition(self._lock)
        # telemetry state: one tag set per node, gauges rate-limited (the
        # put hot path must not pay a registry write per call)
        self._tag_key = (("node", node_hex[:12]),)
        self._inline_bytes = 0
        self._counters = {"spilled": 0, "spilled_bytes": 0, "restored": 0,
                          "restored_bytes": 0, "evicted": 0,
                          "evicted_bytes": 0}
        self._gauges_last = 0.0
        # high-watermark edge detector (+ periodic re-emit while above)
        self._above_watermark = False
        self._watermark_last_emit = 0.0

    # -- telemetry ---------------------------------------------------------

    def _publish_gauges(self, force: bool = False) -> None:
        """Refresh the store gauges, at most every 0.5 s (mutation sites
        call this opportunistically; the metrics sampler reads gauges on
        its own cadence, so sub-second staleness is invisible)."""
        now = time.monotonic()
        if not force and now - self._gauges_last < 0.5:
            return
        self._gauges_last = now
        try:
            m = _telemetry()
            alloc = self.arena.allocator
            used = alloc.bytes_allocated() if alloc is not None else 0
            tk = self._tag_key
            m["used"].set(float(used), tag_key=tk)
            m["free"].set(float(self.capacity - used), tag_key=tk)
            m["inline"].set(float(self._inline_bytes), tag_key=tk)
            free_stats = getattr(alloc, "free_stats", None)
            if free_stats is not None:
                total, _n, largest = free_stats()
                frag = (1.0 - largest / total) if total else 0.0
                m["frag"].set(frag, tag_key=tk)
        except Exception:
            pass  # metrics must never break the store

    def _count(self, key: str, n: int, nbytes: int) -> None:
        """Record a spill/restore/evict increment (rare path)."""
        with self._lock:  # RLock: callers may already hold it
            self._counters[key] += n
            self._counters[key + "_bytes"] += nbytes
        try:
            m = _telemetry()
            m[key].inc(float(n), tag_key=self._tag_key)
            m[key + "_bytes"].inc(float(nbytes), tag_key=self._tag_key)
        except Exception:
            pass

    def _check_watermark(self) -> None:
        """Emit a WARNING cluster event when arena usage crosses the high
        watermark, naming the top consumers by creation callsite (the
        owner-side ref tracker knows who minted each object). Edge-
        triggered, with a 30 s re-emit while the store stays above."""
        cfg = global_config()
        wm = cfg.object_store_high_watermark
        if wm <= 0 or self.arena.allocator is None:
            return
        used = self.arena.allocator.bytes_allocated()
        above = used >= wm * self.capacity
        now = time.monotonic()
        if not above:
            self._above_watermark = False
            return
        if self._above_watermark and now - self._watermark_last_emit < 30.0:
            return
        self._above_watermark = True
        self._watermark_last_emit = now
        with self._lock:
            top = sorted((e for e in self._entries.values()
                          if e.offset >= 0 and e.spilled_path is None),
                         key=lambda e: -e.size)[:5]
            top = [(e.object_id, e.size) for e in top]
        from ray_tpu.core import ref_tracker
        from ray_tpu.util import events as events_mod

        consumers = []
        for oid, size in top:
            info = ref_tracker.lookup(oid)
            consumers.append({
                "object_id": oid.hex(), "bytes": size,
                "callsite": (info[3] if info and info[3] else "<unknown>"),
                "kind": (info[1] if info else None),
            })
        names = ", ".join(f"{c['callsite']}={c['bytes']}B"
                          for c in consumers) or "none"
        events_mod.emit(
            "WARNING", events_mod.SOURCE_OBJECT_STORE,
            f"object store at {100.0 * used / self.capacity:.0f}% of "
            f"capacity ({used}/{self.capacity} bytes); top consumers: "
            f"{names}", entity_id=self.arena_path,
            used=used, capacity=self.capacity, watermark=wm,
            top_consumers=consumers)

    # -- creation ----------------------------------------------------------

    def put_inline(self, oid: ObjectID, payload: bytes, is_error: bool = False,
                   transfer: bool = False):
        with self._lock:
            e = self._entries.get(oid)
            if e is not None and e.sealed:
                return  # idempotent re-put (retries)
            if e is not None and e.inline is not None:
                self._inline_bytes -= len(e.inline)
            self._entries[oid] = ObjectEntry(
                oid, size=len(payload), inline=bytes(payload), sealed=True,
                is_error=is_error, transfer=transfer,
            )
            self._inline_bytes += len(payload)
            self._sealed_cv.notify_all()
        if not transfer:
            _count_put(len(payload))
        self._publish_gauges()

    def create(self, oid: ObjectID, size: int,
               transfer: bool = False) -> Tuple[int, memoryview]:
        """Allocate arena space; returns (offset, writable view). Spills/evicts
        under pressure (reference: create_request_queue.cc backpressure).

        ``transfer=True`` marks a receive-side allocation (node-to-node
        pull/push of bytes that already exist elsewhere): those are not
        counted as puts, so the put counters measure object
        MATERIALIZATIONS, not replication traffic (which has its own
        metrics in object_transfer)."""
        cfg = global_config()
        deadline = time.monotonic() + 30.0
        while True:
            off = self.arena.allocator.allocate(size)
            if off is not None:
                break
            if not self._reclaim(size):
                if time.monotonic() > deadline:
                    raise ObjectStoreFullError(
                        f"object store full: need {size} bytes "
                        f"(capacity {self.capacity})"
                    )
                time.sleep(cfg.object_store_full_delay_ms / 1000.0)
        with self._lock:
            stale = self._entries.get(oid)
            if stale is not None and stale.offset >= 0 and stale.spilled_path is None:
                if stale.readers > 0:  # open_read sender mid-stream
                    stale.pending_free = True
                else:
                    self.arena.allocator.free(stale.offset)  # retry overwrote entry
            self._entries[oid] = ObjectEntry(oid, size=size, offset=off,
                                             creating=True, transfer=transfer)
        if not transfer:
            _count_put(size)
        self._publish_gauges()
        self._check_watermark()
        return off, self.arena.view(off, size)

    def seal(self, oid: ObjectID, is_error: bool = False):
        with self._lock:
            e = self._entries[oid]
            e.sealed = True
            e.creating = False
            e.is_error = is_error
            self._sealed_cv.notify_all()

    # -- reads -------------------------------------------------------------

    def contains(self, oid: ObjectID) -> bool:
        with self._lock:
            e = self._entries.get(oid)
            return e is not None and e.sealed

    def wait_sealed(self, oid: ObjectID, timeout: Optional[float] = None) -> bool:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._sealed_cv:
            while True:
                e = self._entries.get(oid)
                if e is not None and e.sealed:
                    return True
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return False
                self._sealed_cv.wait(remaining if remaining is not None else 1.0)

    def get_payload(self, oid: ObjectID) -> Tuple[object, bool]:
        """Returns (buffer, is_error). Buffer is bytes (inline) or a zero-copy
        memoryview into the arena; restores from spill if needed."""
        with self._lock:
            e = self._entries.get(oid)
            if e is None or not e.sealed:
                raise ObjectLostError(oid, f"object {oid.hex()} not in local store")
            e.last_access = time.monotonic()
            if e.inline is not None:
                return e.inline, e.is_error
            if e.spilled_path is not None:
                self._restore_locked(e)
            e.mapped = True
            return self.arena.view(e.offset, e.size), e.is_error

    def read_meta(self, oid: ObjectID) -> Optional[Tuple[int, bool]]:
        """(size, is_error) for a sealed object, else None. Does not pin."""
        with self._lock:
            e = self._entries.get(oid)
            if e is None or not e.sealed:
                return None
            return e.size, e.is_error

    def read_chunk(self, oid: ObjectID, start: int, n: int) -> Optional[bytes]:
        """Copy out payload[start:start+n] for node-to-node transfer.

        Re-looks-up the entry per call so a transfer never pins the object:
        returns None if it was deleted/evicted mid-stream (puller retries
        with a fresh location). Serves spilled objects straight from disk.
        """
        with self._lock:
            e = self._entries.get(oid)
            if e is None or not e.sealed:
                return None
            e.last_access = time.monotonic()
            if e.inline is not None:
                return e.inline[start:start + n]
            if e.spilled_path is not None:
                try:
                    with open(e.spilled_path, "rb") as f:
                        f.seek(start)
                        return f.read(n)
                except OSError:
                    return None
            return bytes(self.arena.view(e.offset, e.size)[start:start + n])

    @contextlib.contextmanager
    def open_read(self, oid: ObjectID):
        """Zero-copy transfer read: yields a ``ReadHandle`` over the sealed
        arena extent, pinned against move/free for the duration (the
        node-to-node sender streams the payload straight out of the mmap —
        or via ``os.sendfile`` from the backing tmpfs fd). Yields None for
        inline/spilled/absent entries — caller falls back to the copying
        ``read_chunk`` path. A concurrent delete() defers the extent free
        to the last reader's release instead of yanking memory out from
        under an in-flight send."""
        with self._lock:
            e = self._entries.get(oid)
            if (e is None or not e.sealed or e.inline is not None
                    or e.spilled_path is not None or e.offset < 0):
                e = None
            else:
                e.readers += 1
                e.last_access = time.monotonic()
                handle = ReadHandle(self.arena.view(e.offset, e.size),
                                    self.arena.fd, e.offset)
        try:
            yield handle if e is not None else None
        finally:
            if e is not None:
                with self._lock:
                    e.readers -= 1
                    if (e.readers <= 0 and e.pending_free
                            and not e.mapped and e.offset >= 0):
                        self.arena.allocator.free(e.offset)
                        e.pending_free = False
                        e.offset = -1

    def entry_info(self, oid: ObjectID) -> Optional[Tuple[int, int, bool]]:
        """(offset, size, is_error) for sealed arena objects, for direct worker
        mmap reads; None if inline/absent/spilled."""
        with self._lock:
            e = self._entries.get(oid)
            if e is None or not e.sealed or e.inline is not None:
                return None
            if e.spilled_path is not None:
                self._restore_locked(e)
            e.last_access = time.monotonic()
            e.mapped = True
            return e.offset, e.size, e.is_error

    # -- lifetime ----------------------------------------------------------

    def add_ref(self, oid: ObjectID, n: int = 1):
        with self._lock:
            e = self._entries.get(oid)
            if e is not None:
                e.ref_count += n

    def remove_ref(self, oid: ObjectID, n: int = 1):
        with self._lock:
            e = self._entries.get(oid)
            if e is not None:
                e.ref_count = max(0, e.ref_count - n)

    def delete(self, oid: ObjectID):
        with self._lock:
            e = self._entries.pop(oid, None)
            if e is None:
                return
            if e.inline is not None:
                self._inline_bytes -= len(e.inline)
            # plasma lifetime contract: an extent whose zero-copy view was
            # handed out (mapped) is NEVER returned to the allocator — a
            # reader's array may still alias it, and reuse would silently
            # corrupt what it sees. The extent leaks until store close
            # (the reference frees plasma buffers only when all client
            # references release; we track at entry granularity).
            if e.offset >= 0 and e.spilled_path is None and not e.mapped:
                if e.readers > 0:
                    # an open_read sender is mid-stream over this extent:
                    # the last release frees it (see open_read)
                    e.pending_free = True
                else:
                    self.arena.allocator.free(e.offset)
            if e.spilled_path:
                try:
                    os.unlink(e.spilled_path)
                except OSError:
                    pass
        self._publish_gauges()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            spilled = [e for e in self._entries.values() if e.spilled_path]
            out = {
                "num_objects": len(self._entries),
                "bytes_allocated": self.arena.allocator.bytes_allocated(),
                "capacity": self.capacity,
                "num_spilled": len(spilled),
                "bytes_inline": self._inline_bytes,
                "bytes_spilled": sum(e.size for e in spilled),
            }
            out.update(self._counters)
        return out

    def object_infos(self) -> List[Tuple[ObjectID, int, bool, bool, float,
                                         int]]:
        """Per-object store dump for the cluster memory table
        (``Head.memory_table``): (oid, size, inline?, spilled?,
        created_ts, store_ref_count) for every sealed entry."""
        with self._lock:
            return [(e.object_id, e.size, e.inline is not None,
                     e.spilled_path is not None, e.created_ts, e.ref_count)
                    for e in self._entries.values() if e.sealed]

    # -- spilling / eviction ----------------------------------------------

    def _reclaim(self, need: int) -> bool:
        """Evict unreferenced sealed objects (LRU), then spill referenced ones."""
        cfg = global_config()
        evicted = evicted_bytes = spilled = spilled_bytes = 0
        with self._lock:
            candidates = sorted(
                (e for e in self._entries.values()
                 if e.sealed and e.offset >= 0 and e.spilled_path is None),
                key=lambda e: e.last_access,
            )
            freed = 0
            for e in candidates:
                if freed >= need:
                    break
                # never relocate/free an entry whose zero-copy view was handed
                # out (a reader may alias the arena range); explicit delete()
                # via refcount-0 is the user-driven path that still frees it
                if (e.ref_count <= 0 and not e.mapped and e.readers <= 0
                        and not self._pin_check(e.object_id)
                        and (self._pin_authoritative or e.transfer)):
                    self.arena.allocator.free(e.offset)
                    del self._entries[e.object_id]
                    freed += e.size
                    evicted += 1
                    evicted_bytes += e.size
            if freed < need and cfg.object_spilling_enabled:
                for e in candidates:
                    if freed >= need:
                        break
                    if (e.object_id not in self._entries or e.mapped
                            or e.readers > 0):
                        # never move an object a zero-copy reader may alias
                        continue
                    self._spill_locked(e)
                    freed += e.size
                    spilled += 1
                    spilled_bytes += e.size
            ok = freed > 0 or freed >= need
        if evicted:
            self._count("evicted", evicted, evicted_bytes)
        if spilled:
            self._count("spilled", spilled, spilled_bytes)
        self._publish_gauges(force=True)
        self._emit_pressure_events(evicted, evicted_bytes, spilled,
                                   spilled_bytes)
        return ok

    def _emit_pressure_events(self, evicted: int, evicted_bytes: int,
                              spilled: int, spilled_bytes: int) -> None:
        """Memory-pressure cluster events, emitted outside the store lock
        (reference: the 'object store is spilling' autoscaler warning).
        Rate-limited to one emit per second with counts aggregated in
        between — _reclaim sits on the allocation retry path, and a
        pressure wave must not turn into an event flood of blocking
        sends (same policy as node._emit_spillback)."""
        if not evicted and not spilled:
            return
        acc = getattr(self, "_pressure_acc", None)
        if acc is None:
            acc = self._pressure_acc = [0, 0, 0, 0]
            self._pressure_last_emit = 0.0
        acc[0] += evicted
        acc[1] += evicted_bytes
        acc[2] += spilled
        acc[3] += spilled_bytes
        now = time.monotonic()
        if now - self._pressure_last_emit < 1.0:
            return
        self._pressure_last_emit = now
        evicted, evicted_bytes, spilled, spilled_bytes = acc
        self._pressure_acc = [0, 0, 0, 0]
        from ray_tpu.util import events as events_mod

        if evicted:
            events_mod.emit(
                "INFO", events_mod.SOURCE_OBJECT_STORE,
                f"evicted {evicted} object(s) ({evicted_bytes} bytes) "
                f"under memory pressure", entity_id=self.arena_path,
                count=evicted, bytes=evicted_bytes)
        if spilled:
            events_mod.emit(
                "WARNING", events_mod.SOURCE_OBJECT_STORE,
                f"spilled {spilled} object(s) ({spilled_bytes} bytes) "
                f"to {self.spill_dir}", entity_id=self.arena_path,
                count=spilled, bytes=spilled_bytes)

    def _spill_locked(self, e: ObjectEntry):
        path = os.path.join(self.spill_dir, e.object_id.hex())
        with open(path, "wb") as f:
            f.write(self.arena.view(e.offset, e.size))
        self.arena.allocator.free(e.offset)
        e.spilled_path = path
        e.offset = -1

    def _restore_locked(self, e: ObjectEntry):
        off = self.arena.allocator.allocate(e.size)
        if off is None:
            self._reclaim(e.size)
            off = self.arena.allocator.allocate(e.size)
            if off is None:
                raise ObjectStoreFullError("cannot restore spilled object")
        with open(e.spilled_path, "rb") as f:
            data = f.read()
        self.arena.view(off, e.size)[:] = data
        try:
            os.unlink(e.spilled_path)
        except OSError:
            pass
        e.spilled_path = None
        e.offset = off
        self._count("restored", 1, e.size)
        # a restore allocates like a create does — a read-heavy workload
        # can cross the watermark with no create() in sight
        self._check_watermark()

    def close(self):
        self.arena.close(unlink=True)


class ArenaClient:
    """Worker-side read/write mapping of a node's arena (plasma client analog)."""

    def __init__(self, arena_path: str, capacity: int):
        self.arena = PlasmaArena(arena_path, capacity, create=False)

    def view(self, offset: int, size: int) -> memoryview:
        return self.arena.view(offset, size)

    def close(self):
        self.arena.close(unlink=False)
