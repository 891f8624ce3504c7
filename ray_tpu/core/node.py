"""Node — per-node daemon state: worker pool, local dispatch, object store.

Analog of the reference's raylet (``src/ray/raylet/node_manager.cc`` +
``worker_pool.cc``): owns the node's shared-memory store, spawns/leases worker
processes, dispatches tasks the cluster scheduler routed here, detects worker
death via connection EOF, and serves worker store/control RPCs (delegating
control-plane ops to the head, as raylets delegate to the GCS). In multi-node
tests several Node objects live in the driver process, each with its own
worker processes and arena — the analog of ``cluster_utils.Cluster`` running
several raylets on one machine.
"""

from __future__ import annotations

import os
import pickle
import subprocess
import sys
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from . import fault_injection
from .config import global_config
from .ids import NodeID, ObjectID, WorkerID
from .object_store import LocalObjectStore
from .protocol import Channel, make_listener
from .resources import NodeResources
from .task_spec import TaskSpec

# Waiting for a chip-bound worker to be gone (Node._wait_chip_proc_gone)
_CHIP_EXIT_GRACE_S = 10.0     # to leave by itself (flushes spans, metrics)
_CHIP_EXIT_POLL_S = 15.0      # between "still exiting" lines after the kill
_CHIP_EXIT_GIVE_UP_S = 300.0  # then its chips are released regardless


@dataclass
class WorkerHandle:
    worker_id: WorkerID
    channel: Channel
    pid: int
    proc: Optional[subprocess.Popen] = None
    state: str = "starting"  # starting | idle | busy | actor | dead
    # in-flight plain tasks staged on this worker (lease pipelining:
    # > 1 entry means the next task is already in the worker's memory
    # when the current one finishes); values are
    # (spec, binding, attempt-at-dispatch)
    assigned: Dict[object, Tuple[TaskSpec, dict, int]] = field(
        default_factory=dict)
    actor_id: Optional[object] = None
    reader: Optional[threading.Thread] = None
    # TPU chips this process was spawned for (its environment names them,
    # accelerators.worker_env); None = a worker of the CPU pool
    chips: Optional[Tuple[int, ...]] = None


class Node:
    def __init__(self, head, node_id: NodeID, resources: Dict[str, float],
                 session_dir: str, labels: Optional[Dict[str, str]] = None,
                 node_ip: str = "127.0.0.1"):
        cfg = global_config()
        self.head = head
        self.node_id = node_id
        self.hex = node_id.hex()
        self.session_dir = session_dir
        self.labels = labels or {}
        # routable address of this host, advertised to workers (Train
        # coordinator bootstrap) and in the object-server address; loopback
        # for in-process nodes (reference: raylet node_ip_address)
        self.node_ip = node_ip
        unit_names = set(cfg.unit_instance_resources.split(","))
        self.resources = NodeResources(resources, unit_instance_names=unit_names)
        self.resources.labels = self.labels
        self.store = LocalObjectStore(
            session_dir, self.hex,
            pin_check=self._store_pin_check,
            # daemons only see the local holder lease (no head pin view):
            # their stores must spill — never evict — primary copies
            pin_check_authoritative=hasattr(head, "nodes"))
        self.max_workers = max(1, int(resources.get("CPU", 1)))
        self._workers: Dict[WorkerID, WorkerHandle] = {}
        self._idle: deque = deque()
        self._local_queue: deque = deque()  # (spec, binding) waiting for a worker
        # pending node->worker stack-dump rounds (collect_worker_stacks):
        # req_id -> [event, reply, worker_id]
        self._stack_seq = 0
        self._stack_pending: Dict[int, list] = {}
        from .lock_debug import tracked_rlock

        self._lock = tracked_rlock("Node._lock")
        self._handler_pool = ThreadPoolExecutor(
            max_workers=32, thread_name_prefix=f"node-{self.hex[:6]}"
        )
        self.alive = True
        # set by shutdown(); paced loops (steal ticker) wait on it so
        # they exit the instant the node dies instead of a sleep later
        self._stop_event = threading.Event()
        self._authkey = os.urandom(16)
        self._sock_path = os.path.join(session_dir, f"node_{self.hex[:12]}.sock")
        self._listener = make_listener(self._sock_path, self._authkey)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True, name=f"accept-{self.hex[:6]}"
        )
        self._accept_thread.start()
        self._num_starting = 0
        self._tail_files: Dict[str, list] = {}  # path -> [offset, pid, dead_ts]
        self._log_tailer_started = False
        # pids spawned but not yet counted down — the countdown happens
        # exactly once, on whichever of (registration, process exit)
        # happens first
        self._starting_pids: set = set()
        # ---- chip-bound workers -----------------------------------------
        # libtpu gives a chip to ONE process until that process exits, and
        # a process picks its platform when it imports jax. So a chip-bound
        # spec never goes to a pooled worker: it gets a process spawned for
        # exactly its chips, that process serves that one spec (a task, or
        # an actor for its lifetime) and then exits, and the head hears of
        # the exit (and frees the chips) only once the process is gone.
        self._chip_starting: Dict[int, Tuple[int, ...]] = {}  # pid -> chips
        self._chip_ready: Dict[Tuple[int, ...], WorkerHandle] = {}
        self._chip_procs: Dict[int, subprocess.Popen] = {}    # pid -> proc
        # ---- direct (head-bypass) task path state -----------------------
        # Holder-side owner leases: while a direct task is in flight
        # through this node (queued locally or forwarded to a peer), its
        # pinned ref args may not be evicted from — or deleted out of —
        # the local store. The lease is the holder's half of the OWNER'S
        # arg pin (DirectTaskManager._pin_counts) and releases on the
        # same reply chain that settles the task; no head RPC is involved
        # (replaces the old per-task pin_delta / is_pinned head ops).
        self._arg_leases: Dict[ObjectID, int] = {}
        self._leased_tasks: Dict[object, tuple] = {}
        self._deferred_deletes: set = set()
        # locally-executing direct tasks: task_id -> (origin, spec)
        self._direct: Dict[object, Tuple[tuple, TaskSpec]] = {}
        # stream-item oids sealed locally for a direct streaming task;
        # they ride the task's completion devent so the head's object
        # directory learns their location in one batched report
        self._direct_stream_oids: Dict[object, List[ObjectID]] = {}
        # actors hosted on this node: actor_id -> worker_id (the routing
        # table for direct actor calls; reference: the actor's RPC address
        # cached by ActorTaskSubmitter)
        self._actor_workers: Dict[object, WorkerID] = {}
        # tasks forwarded to a peer: task_id -> (origin, spec, peer_hex)
        self._forwarded: Dict[object, Tuple[tuple, TaskSpec, str]] = {}
        self._peers: Dict[str, Channel] = {}      # peer_hex -> channel
        # optimistic in-flight counts per peer: reported queue depths lag
        # by a syncer period, so without this a submission burst dogpiles
        # whichever peer last reported the lowest load
        self._peer_inflight: Dict[str, int] = {}
        # peer-gossiped load: hex -> (version, queue_depth, recv_ts).
        # Fresh entries overlay the head's cluster-view queue numbers,
        # which lag by a report period (reference: RaySyncer peer bidi
        # streams vs star rebroadcast — round-3 audit weak #10)
        self._peer_loads: Dict[str, tuple] = {}
        self._gossip_version = 0
        self._peer_lock = threading.Lock()
        self._peer_key: Optional[bytes] = None    # set by start_object_server
        # stream_sub round-trips in flight: req_id -> [Event, reply,
        # owner_worker_id | None] (replies arrive as "srep" from a local
        # owner worker or "psubrep" from a peer node)
        self._ssub_pending: Dict[int, list] = {}
        self._ssub_seq = 0
        self._ssub_lock = threading.Lock()
        self._devents: List[tuple] = []           # batched head event reports
        self._dev_lock = threading.Lock()
        self._dev_first: float = 0.0
        self._dev_flusher_started = False
        with self._lock:
            for _ in range(min(cfg.worker_prestart_count, self.max_workers)):
                self._start_worker_locked()
            self._ensure_prewarm_locked()
        self._steal_thread = None
        if cfg.direct_steal_enabled:
            # idle nodes get no pump events: a slow heartbeat re-evaluates
            # stealing (rate-limited + cheap-idle-checked inside)
            self._steal_thread = threading.Thread(
                target=self._steal_ticker, daemon=True,
                name=f"steal-{self.hex[:6]}")
            self._steal_thread.start()

    # ------------------------------------------------------------ dispatch

    def dispatch(self, spec: TaskSpec, binding: dict) -> None:
        """Called by the cluster scheduler once resources are acquired."""
        with self._lock:
            if not self.alive:
                raise RuntimeError("node is dead")
            self._local_queue.append((spec, binding))
        self._pump()

    def dispatch_to_worker(self, worker_id: WorkerID, spec: TaskSpec) -> bool:
        """Direct dispatch to a specific (actor) worker, bypassing leasing."""
        # chaos point: "node.dispatch_worker=fail@N" bounces this dispatch
        # as if the worker were already gone (provably-undelivered path)
        if fault_injection.fire("node.dispatch_worker") == "fail":
            return False
        with self._lock:
            w = self._workers.get(worker_id)
            if w is None or w.state == "dead":
                return False
        try:
            w.channel.send("exec", pickle.dumps(spec), {})
            return True
        except OSError:
            return False

    # ---------------------------------------------------- direct task path
    # (reference: normal_task_submitter.cc — submitter leases from its
    # LOCAL raylet and pushes directly; the GCS sees only async events)

    def submit_direct(self, spec: TaskSpec, origin: tuple) -> None:
        """Execute an eligible plain task (or route an actor call)
        without head involvement.

        ``origin`` routes the completion reply:
          ("worker", worker_id)      — a worker on this node submitted it
          ("driver", done_cb, stream_cb) — the in-process driver submitted it
          ("peer", channel)          — a peer node forwarded it here
          ("node", node, inner)      — in-process peer hop: reply via node
        """
        if not self.alive:
            self._reply_direct(origin, spec.task_id, "NodeDiedError", [])
            return
        if spec.actor_id is not None and not spec.is_actor_creation:
            self._submit_direct_actor(spec, origin)
            return
        if spec.direct_hops == 0:
            # locality first (reference: lease_policy.h:56
            # LocalityAwareLeasePolicy — lease from the node holding the
            # largest args): store-resident args are >100KB by definition
            # while inline args ride in the spec, so the node hinted by
            # the most store-resident args holds the most arg bytes.
            loc = self._locality_target(spec)
            if loc is not None and self._forward_direct(spec, origin, loc):
                return
        if spec.direct_hops <= 1 and self._maybe_spill(spec, origin):
            # hop cap 2 (locality + one spill): a saturated arg-holder
            # node sheds locality-forwarded fan-out to its peers instead
            # of serializing the whole wave (reference: spillback applies
            # at the lease target too)
            return
        with self._lock:
            self._direct[spec.task_id] = (origin, spec, time.time())
            self._lease_args_locked(spec)
        self._ensure_direct_flusher()
        try:
            self.dispatch(spec, {})
        except RuntimeError:
            with self._lock:
                self._direct.pop(spec.task_id, None)
            self._task_departed(spec.task_id)
            self._reply_direct(origin, spec.task_id, "NodeDiedError", [])

    def _finish_direct(self, origin: tuple, spec: TaskSpec, task_id,
                       results, err_name: Optional[str],
                       t_start: Optional[float] = None) -> None:
        """Executor-side completion: seal inline results locally, batch the
        event report to the head, reply straight to the owner."""
        sealed = []
        for oid, payload, is_err in results:
            if payload is not None:
                try:
                    self.store.put_inline(oid, payload, is_err)
                    sealed.append(oid)
                except Exception:
                    # store full: the owner still gets the inline payload,
                    # but head-path consumers (ref args, borrowers) need a
                    # resolvable location — seal in the head store instead
                    try:
                        self.head.on_sealed_payload(oid, payload, is_err)
                    except Exception:
                        pass
        with self._lock:
            stream_oids = self._direct_stream_oids.pop(task_id, None)
        if stream_oids:
            sealed.extend(stream_oids)
        self._append_devent(spec, err_name, sealed, t_start)
        self._reply_direct(origin, task_id, err_name, results, self.hex)

    def _reply_stream_item(self, origin: tuple, task_id, index: int,
                           data: Optional[bytes],
                           exec_hex: Optional[str]) -> None:
        """Route a stream-item announcement back along the same chain as
        the eventual completion reply (FIFO on every hop, so the owner
        always sees items before the final ddone)."""
        kind = origin[0]
        try:
            if kind == "worker":
                with self._lock:
                    w = self._workers.get(origin[1])
                if w is not None:
                    w.channel.send("dstream", task_id, index, data,
                                   exec_hex)
            elif kind == "driver":
                origin[2](task_id, index, data, exec_hex)
            elif kind == "peer":
                origin[1].send("pstream", task_id, index, data, exec_hex)
            elif kind == "node":
                origin[1]._reply_stream_item(origin[2], task_id, index,
                                             data, exec_hex)
        except (OSError, EOFError):
            pass  # owner gone: items die with it (owner-died semantics)

    def _reply_direct(self, origin: tuple, task_id, err_name,
                      results, exec_hex: Optional[str] = None) -> None:
        kind = origin[0]
        try:
            if kind == "worker":
                with self._lock:
                    w = self._workers.get(origin[1])
                if w is not None:
                    w.channel.send("ddone", task_id, err_name, results,
                                   exec_hex)
            elif kind == "driver":
                origin[1](task_id, err_name, results, exec_hex)
            elif kind == "peer":
                origin[1].send("pdone", task_id, err_name, results, exec_hex)
            elif kind == "node":
                peer = origin[1]
                with peer._lock:
                    peer._forwarded.pop(task_id, None)
                peer._task_departed(task_id)
                peer._reply_direct(origin[2], task_id, err_name, results,
                                   exec_hex)
        except (OSError, EOFError):
            pass  # owner gone: its results die with it (owner-died semantics)

    def _submit_direct_actor(self, spec: TaskSpec, origin: tuple) -> None:
        """Route a direct actor call: dispatch to the local actor worker,
        or forward one hop to the node the owner believes hosts the actor
        (reference: ActorTaskSubmitter::PushActorTask — caller to actor
        process, the control plane never sees the call)."""
        with self._lock:
            wid = self._actor_workers.get(spec.actor_id)
        if wid is not None:
            with self._lock:
                self._direct[spec.task_id] = (origin, spec, time.time())
                self._lease_args_locked(spec)
            self._ensure_direct_flusher()
            if not self.dispatch_to_worker(wid, spec):
                with self._lock:
                    self._direct.pop(spec.task_id, None)
                self._task_departed(spec.task_id)
                # delivery provably failed (worker gone or send raised
                # before the call hit the wire): a location error — the
                # owner re-resolves and resubmits without consuming the
                # max_task_retries budget (never-executed is always safe)
                self._reply_direct(origin, spec.task_id,
                                   "ActorMissingError", [])
            return
        target = spec.actor_node_hex
        if (target is None or target == self.hex or origin[0] == "peer"
                or spec.direct_hops >= 1
                or not self._forward_direct(spec, origin, target)):
            # stale owner location (or already forwarded once, or the
            # peer is unreachable): bounce so the owner re-resolves via
            # the head's actor FSM
            self._reply_direct(origin, spec.task_id, "ActorMissingError", [])

    def _forward_direct(self, spec: TaskSpec, origin: tuple,
                        target: str) -> bool:
        """Ship a direct task one hop to ``target``'s node (actor routing,
        locality dispatch, spillback all ride this). False = unreachable
        (caller decides the fallback)."""
        handle = self._peer_handle_for(target)
        if handle is None:
            return False
        spec.direct_hops = 1
        if not isinstance(handle, tuple):
            # in-process peer Node
            with self._lock:
                self._forwarded[spec.task_id] = (origin, spec, handle)
                self._lease_args_locked(spec)
            handle.submit_direct(spec, ("node", self, origin))
            return True
        ch = self._peer_channel(target, handle)
        if ch is None:
            spec.direct_hops = 0
            return False
        with self._lock:
            self._forwarded[spec.task_id] = (origin, spec, target)
            self._lease_args_locked(spec)
        try:
            ch.send("psubmit", pickle.dumps(spec))
        except (OSError, EOFError):
            with self._lock:
                self._forwarded.pop(spec.task_id, None)
            self._task_departed(spec.task_id)
            self._drop_peer(target)
            spec.direct_hops = 0
            return False
        return True

    def _locality_target(self, spec: TaskSpec) -> Optional[str]:
        """Peer node holding the most store-resident args, if not us.
        An explicit ``spec.locality_hex`` (caller-provided hint, e.g. the
        data executor targeting a block holder) is the fallback when the
        arg hints don't name a node — small blocks ride inline and leave
        no store hint, but the caller still knows where they live."""
        hints = spec.arg_hints
        counts: Dict[str, int] = {}
        for h in (hints or {}).values():
            if h[0] == "node":
                counts[h[1]] = counts.get(h[1], 0) + 1
        if counts:
            best = max(counts, key=lambda k: counts[k])
        else:
            best = spec.locality_hex
        if best is None or best == self.hex:
            return None
        # don't ship work to a node we can't see or that already left
        return best

    def _peer_handle_for(self, peer_hex: str):
        """Node object (in-process) or (host, port) for a peer's object/
        control server, from the head table or the syncer cluster view."""
        head = self.head
        if hasattr(head, "nodes"):  # in-process side
            n = head.nodes.get(peer_hex)
            if n is None:
                return None
            if hasattr(n, "store"):
                return n
            return tuple(n.object_addr)
        for e in head.cluster_view:
            if e.get("hex") == peer_hex and e.get("addr"):
                return tuple(e["addr"])
        return None

    def cancel_direct(self, task_id, force: bool = False) -> None:
        """Owner-initiated cancel of a direct task: drop it from the local
        queue if not started, interrupt the worker if running, or forward
        the cancel to the peer executing it (reference:
        CoreWorker::CancelTask -> executor interrupt)."""
        peer_hex = None
        with self._lock:
            fwd = self._forwarded.get(task_id)
            if fwd is not None:
                peer_hex = fwd[2]
            elif task_id in self._direct:
                for i, (spec, binding) in enumerate(self._local_queue):
                    if spec.task_id == task_id:
                        del self._local_queue[i]
                        origin, spec, _t = self._direct.pop(task_id)
                        break
                else:
                    origin = None
            else:
                return
        if peer_hex is not None:
            if isinstance(peer_hex, tuple) and peer_hex[0] == "_stolen":
                # stolen over TCP: the victim's server conn is duplex —
                # forward the cancel to the thief
                try:
                    peer_hex[1].send("pcancel", task_id, force)
                except (OSError, EOFError):
                    pass
                return
            if not isinstance(peer_hex, str):
                # in-process peer Node: cancel it there directly
                peer_hex.cancel_direct(task_id, force)
                return
            with self._peer_lock:
                ch = self._peers.get(peer_hex)
            if ch is not None:
                try:
                    ch.send("pcancel", task_id, force)
                except (OSError, EOFError):
                    pass
            return
        if origin is not None:  # was still queued: never ran
            self._task_departed(task_id)
            self._reply_direct(origin, task_id, "TaskCancelledError", [])
            return
        # running (or staged) on a worker: interrupt it. Actor calls are
        # not in w.assigned — route the cancel via the actor index (and
        # never force-kill: that would kill the actor, not the call).
        with self._lock:
            entry = self._direct.get(task_id)
            awid = (self._actor_workers.get(entry[1].actor_id)
                    if entry is not None and entry[1].actor_id is not None
                    else None)
        if awid is not None:
            self.cancel_task(task_id, awid, False)
        else:
            self.cancel_task(task_id, None, force)

    # ---- holder-side owner leases ---------------------------------------
    # (the node-local half of owner-side arg pinning: no head traffic)

    def _lease_args_locked(self, spec: TaskSpec) -> None:
        """Take a store lease on the task's pinned ref args (idempotent
        per task). Caller holds self._lock."""
        if not spec.pinned_args or spec.task_id in self._leased_tasks:
            return
        self._leased_tasks[spec.task_id] = tuple(spec.pinned_args)
        for oid in spec.pinned_args:
            self._arg_leases[oid] = self._arg_leases.get(oid, 0) + 1

    def _task_departed(self, task_id) -> None:
        """A direct task left this node (settled, forwarded away and
        replied, or failed): release its arg leases, apply any store
        deletes that were deferred while the lease was held, and let an
        in-process head retry a cluster-wide delete it deferred behind
        this lease."""
        to_delete = []
        released = []
        with self._lock:
            if task_id in self._direct or task_id in self._forwarded:
                return  # still tracked under the other map
            oids = self._leased_tasks.pop(task_id, None)
            if not oids:
                return
            for oid in oids:
                n = self._arg_leases.get(oid, 0) - 1
                if n > 0:
                    self._arg_leases[oid] = n
                else:
                    self._arg_leases.pop(oid, None)
                    released.append(oid)
                    if oid in self._deferred_deletes:
                        self._deferred_deletes.discard(oid)
                        to_delete.append(oid)
        for oid in to_delete:
            try:
                self.store.delete(oid)
            except Exception:
                pass
        if released and hasattr(self.head, "release_holder_lease"):
            # in-process head: retry cluster-wide deletes deferred behind
            # this node's lease (daemon-side leases only guard their own
            # store; the daemon's copy is the one the lease protects)
            try:
                self.head.release_holder_lease(released)
            except Exception:
                pass

    def replay_snapshot(self) -> dict:
        """What this node replays to a RESTARTED head at re-registration
        (node_daemon rejoin): the store manifest (rebuilds the object
        directory), live holder leases (re-guards deferred deletes), and
        hosted actors (revives their ALIVE records + routing). All of it
        is node-resident state the head merely mirrors — the same tables
        the 1 s syncer keeps fresh, shipped once, in full."""
        with self._lock:
            actors = list(self._actor_workers.items())
            leases = list(self._arg_leases.keys())
        objects = [row[0] for row in self.store.object_infos()]
        return {"objects": objects, "leases": leases, "actors": actors}

    def has_lease(self, oid: ObjectID) -> bool:
        """Lock-free: an in-flight direct task through this node leases
        ``oid`` (consulted by the in-process head's delete decisions)."""
        return self._arg_leases.get(oid, 0) > 0

    def lease_snapshot(self) -> list:
        """Current leased arg oids (piggybacked on the daemon's periodic
        sync snapshot so the HEAD's delete decisions can defer behind a
        daemon-held lease without any per-task wire traffic; staleness is
        one sync period — the same window the old one-way pin_delta
        messages had in flight). Never truncated: a dropped lease would
        silently disable delete protection, so an abnormally large set
        only costs a bigger sync message (and warns once per minute)."""
        with self._lock:
            leases = list(self._arg_leases.keys())
        if len(leases) > 4096:
            now = time.monotonic()
            if now - getattr(self, "_lease_warn_ts", 0.0) > 60.0:
                self._lease_warn_ts = now
                from ray_tpu.util import events as events_mod

                events_mod.emit(
                    "WARNING", events_mod.SOURCE_NODE,
                    f"node {self.hex[:8]} holds {len(leases)} in-flight "
                    "arg leases; sync snapshots are growing large",
                    entity_id=self.hex, leases=len(leases))
        return leases

    def _store_pin_check(self, oid: ObjectID) -> bool:
        """Store eviction guard: leased args, head-path pins, and the
        driver's owner-side pins all protect an object. Lock-free dict
        reads (same benign-race contract the head ref_counts check had);
        daemons have no head tables and rely on the local lease alone."""
        if self._arg_leases.get(oid, 0) > 0:
            return True
        rc = getattr(self.head, "ref_counts", None)
        if rc is not None and rc.get(oid, 0) > 0:
            return True
        epc = getattr(self.head, "extra_pin_check", None)
        if epc is not None:
            try:
                return bool(epc(oid))
            except Exception:
                return True  # fail pinned: never evict on a glitch
        return False

    def delete_from_store(self, oid: ObjectID) -> None:
        """Store deletion that honors holder leases: while an in-flight
        direct task leases ``oid``, the delete is deferred until the
        lease releases (owner-release-then-delete ordering)."""
        with self._lock:
            if self._arg_leases.get(oid, 0) > 0:
                self._deferred_deletes.add(oid)
                return
        self.store.delete(oid)

    # ---- stream subscriptions (owner-side published streams) -------------
    # A consumer holding a serialized generator handle subscribes to the
    # OWNER along the worker<->node<->peer reply channels; the head is
    # never involved (reference: streaming generator reports are
    # submitter-side, core_worker.h TryReadObjectRefStream).

    def _ssub_slot(self, worker_id=None):
        with self._ssub_lock:
            self._ssub_seq += 1
            req_id = self._ssub_seq
            slot = [threading.Event(), None, worker_id]
            self._ssub_pending[req_id] = slot
        return req_id, slot

    def _ssub_reply(self, req_id: int, rep) -> None:
        with self._ssub_lock:
            slot = self._ssub_pending.pop(req_id, None)
        if slot is not None:
            slot[1] = rep
            slot[0].set()

    def _fail_worker_ssubs(self, worker_id, pid=None) -> None:
        """The owner worker died: its parked subscribers learn now."""
        from .exceptions import format_death_cause

        with self._ssub_lock:
            gone = [(rid, s) for rid, s in self._ssub_pending.items()
                    if s[2] == worker_id]
            for rid, _s in gone:
                self._ssub_pending.pop(rid, None)
        cause = format_death_cause("stream owner worker died", self.hex, pid)
        for _rid, slot in gone:
            slot[1] = ("gone", cause)
            slot[0].set()

    def serve_stream_sub(self, owner, task_id, index: int,
                         timeout: float):
        """One bounded subscription round against the stream's owner.
        Routes: driver-owned -> the driver's manager (in-process hook or
        peer hop to the head node); worker-owned -> the owner worker via
        its node (local ``ssub`` round-trip or peer ``psub`` hop).
        Inline item payloads are sealed into THIS node's store before the
        reply so the consumer's get resolves locally."""
        rep = self._route_stream_sub(owner, task_id, index, timeout)
        if rep is None:
            rep = ("gone", "stream owner no longer holds the stream")
        # not a wire-op ladder: rep is stream_next_remote's RETURN tuple
        # (in-process call or already-framed psubrep payload)
        # graftlint: ignore[protocol-completeness]
        if rep[0] == "item" and len(rep) > 2 and rep[2] is not None:
            oid, payload = rep[1], rep[2]
            sealed = False
            try:
                if not self.store.contains(oid):
                    self.store.put_inline(oid, payload, False,
                                          transfer=True)
                    if hasattr(self.head, "nodes"):
                        # in-process: registering the cache copy is a
                        # method call (daemons skip — no per-item sends)
                        self.head.on_object_sealed(oid, self.hex)
                sealed = True
            except Exception:
                pass  # store full: fall back to the executor-node hint
            # keep the owner's location hint when the local seal failed —
            # inline items also have a store copy at the executor node
            return ("item", oid,
                    None if sealed else (rep[3] if len(rep) > 3 else None))
        # graftlint: ignore[protocol-completeness]
        if rep[0] == "item":
            return ("item", rep[1], rep[3] if len(rep) > 3 else None)
        # graftlint: ignore[protocol-completeness]
        if rep[0] == "error" and len(rep) > 1 and rep[1] is not None:
            # owner-sealed failure: seal the primary's error payload
            # locally so the consumer's follow-up get can raise it
            try:
                prim = ObjectID.for_task_return(task_id, 0)
                if not self.store.contains(prim):
                    self.store.put_inline(prim, rep[1], True,
                                          transfer=True)
            except Exception:
                pass
            return ("error",)
        return rep

    def _route_stream_sub(self, owner, task_id, index, timeout):
        kind = owner[0] if owner else None
        head = self.head
        in_process = hasattr(head, "nodes")
        if kind == "d":
            if in_process:
                hook = getattr(head, "owner_stream_next", None)
                if hook is None:
                    return ("gone", "driver stream owner gone")
                return hook(task_id, index, timeout)
            return self._stream_sub_via_peer(owner, owner[1], task_id,
                                             index, timeout)
        if kind == "w":
            node_hex, wid = owner[1], owner[2]
            if node_hex == self.hex:
                return self._stream_sub_local(wid, task_id, index, timeout)
            if in_process:
                peer = head.nodes.get(node_hex)
                if peer is not None and hasattr(peer, "store"):
                    # in-process peer node: ask its worker directly
                    return peer._stream_sub_local(wid, task_id, index,
                                                  timeout)
            return self._stream_sub_via_peer(owner, node_hex, task_id,
                                             index, timeout)
        return ("gone", "unroutable stream owner")

    def serve_stream_sub_local(self, owner, task_id, index, timeout):
        """Peer-facing entry: serve a subscription whose owner lives in
        THIS process (the terminal hop of a psub)."""
        kind = owner[0] if owner else None
        if kind == "d" and hasattr(self.head, "nodes"):
            hook = getattr(self.head, "owner_stream_next", None)
            if hook is None:
                return ("gone", "driver stream owner gone")
            return hook(task_id, index, timeout)
        if kind == "w" and owner[1] == self.hex:
            return self._stream_sub_local(owner[2], task_id, index, timeout)
        return ("gone", "stream owner not on this node")

    def _stream_sub_local(self, worker_id, task_id, index, timeout):
        """Round-trip to the owner worker on THIS node over its channel."""
        if isinstance(worker_id, bytes):
            worker_id = WorkerID(worker_id)  # routes carry raw id bytes
        from .exceptions import format_death_cause

        with self._lock:
            w = self._workers.get(worker_id)
        if w is None or w.state == "dead":
            return ("gone", format_death_cause("stream owner worker died",
                                               self.hex))
        req_id, slot = self._ssub_slot(worker_id)
        try:
            w.channel.send("ssub", req_id, task_id, index, timeout)
        except OSError:
            self._ssub_reply(req_id, None)
            return ("gone", format_death_cause("stream owner worker died",
                                               self.hex, w.pid))
        if not slot[0].wait((timeout or 0) + 5.0):
            with self._ssub_lock:
                self._ssub_pending.pop(req_id, None)
            return ("wait",)
        return slot[1]

    def _stream_sub_via_peer(self, owner, target_hex, task_id, index,
                             timeout):
        """Forward the subscription one hop to the owner's node."""
        handle = self._peer_handle_for(target_hex)
        if handle is None:
            return ("gone", "stream owner node gone")
        if not isinstance(handle, (tuple, list)):
            return handle.serve_stream_sub_local(owner, task_id, index,
                                                 timeout)
        ch = self._peer_channel(target_hex, tuple(handle))
        if ch is None:
            return ("gone", "stream owner node unreachable")
        req_id, slot = self._ssub_slot()
        try:
            ch.send("psub", req_id, owner, task_id, index, timeout)
        except (OSError, EOFError):
            self._ssub_reply(req_id, None)
            self._drop_peer(target_hex)
            return ("gone", "stream owner node unreachable")
        if not slot[0].wait((timeout or 0) + 5.0):
            with self._ssub_lock:
                self._ssub_pending.pop(req_id, None)
            return ("wait",)
        rep = slot[1]
        return rep if rep is not None else (
            "gone", "stream owner node unreachable")

    def _serve_peer_stream_sub(self, ch: Channel, req_id, owner, task_id,
                               index, timeout) -> None:
        """Server side of a peer 'psub'. Driver-owned streams probe
        inline first (steady state: the item is already in the owner
        table — no thread spawn per item); worker-owned streams and
        parking rounds go off-thread (the ssub round-trip / wait must
        not block the peer reader)."""
        hook = getattr(self.head, "owner_stream_next", None)
        if (owner and owner[0] == "d" and hook is not None
                and hasattr(self.head, "nodes")):
            try:
                rep = hook(task_id, index, 0)
            except Exception:
                rep = None
            if rep is not None and rep[0] != "wait":
                try:
                    ch.send("psubrep", req_id, rep)
                except (OSError, EOFError):
                    pass
                return

        def run():
            try:
                rep = self.serve_stream_sub_local(owner, task_id, index,
                                                  timeout)
            except Exception:
                rep = ("gone", "stream owner errored")
            try:
                ch.send("psubrep", req_id, rep)
            except (OSError, EOFError):
                pass  # subscriber's node gone

        threading.Thread(target=run, daemon=True,
                         name=f"psub-{self.hex[:6]}").start()

    # ---- spillback -------------------------------------------------------

    def _maybe_spill(self, spec: TaskSpec, origin: tuple) -> bool:
        cfg = global_config()
        with self._lock:
            depth = len(self._local_queue)
        if depth <= cfg.direct_spill_queue_factor * self.max_workers:
            return False
        cands = self._peer_candidates()
        if not cands:
            return False
        with self._peer_lock:
            now = time.monotonic()
            fresh = {h: q for h, (v, q, ts) in self._peer_loads.items()
                     if now - ts < 2.0}
            cands = [(h, handle,
                      fresh.get(h, q) + self._peer_inflight.get(h, 0))
                     for h, handle, q in cands]
        cands.sort(key=lambda c: c[2])
        peer_hex, handle, queue = cands[0]
        if queue >= depth:
            return False  # everyone is as busy as we are
        if not isinstance(handle, (tuple, list)):
            # in-process peer Node: direct call, reply hops back through us.
            # Tracked in _forwarded (peer stored as the Node object) so
            # cancel_direct can reach the peer's queue/worker.
            spec.direct_hops += 1
            with self._lock:
                self._forwarded[spec.task_id] = (origin, spec, handle)
                self._lease_args_locked(spec)
            handle.submit_direct(spec, ("node", self, origin))
            self._emit_spillback(spec, handle.hex, depth)
            return True
        ch = self._peer_channel(peer_hex, handle)
        if ch is None:
            return False
        # Stamp the hop only once delivery is committed — a failed spill
        # must leave the task eligible for later stealing/rebalancing.
        spec.direct_hops += 1
        with self._lock:
            self._forwarded[spec.task_id] = (origin, spec, peer_hex)
            self._lease_args_locked(spec)
        with self._peer_lock:
            self._peer_inflight[peer_hex] = \
                self._peer_inflight.get(peer_hex, 0) + 1
        try:
            ch.send("psubmit", pickle.dumps(spec))
        except (OSError, EOFError):
            spec.direct_hops -= 1
            with self._lock:
                self._forwarded.pop(spec.task_id, None)
            self._task_departed(spec.task_id)
            self._drop_peer(peer_hex)
            return False
        self._emit_spillback(spec, peer_hex, depth)
        return True

    def _emit_spillback(self, spec, peer_hex: str, depth: int) -> None:
        """Cluster event for a direct-task spillback, rate-limited to one
        per peer per second (spill waves are bursty)."""
        now = time.monotonic()
        last = getattr(self, "_spill_event_last", None)
        if last is None:
            last = self._spill_event_last = {}
        if now - last.get(peer_hex, 0.0) < 1.0:
            return
        last[peer_hex] = now
        from ray_tpu.util import events as events_mod

        events_mod.emit(
            "INFO", events_mod.SOURCE_SCHEDULER,
            f"spillback: node {self.hex[:8]} (queue depth {depth}) "
            f"forwarded {spec.function_name} to peer {peer_hex[:8]}",
            entity_id=self.hex, peer=peer_hex, queue_depth=depth,
            function=spec.function_name)

    def _peer_candidates(self) -> List[tuple]:
        """[(hex, Node | addr, queue_depth)] of alive CPU peers."""
        head = self.head
        out: List[tuple] = []
        view = getattr(head, "cluster_view", None)
        if view is not None:  # daemon side (RemoteHead)
            for e in view:
                if (e.get("hex") != self.hex and e.get("alive")
                        and e.get("addr")
                        and e.get("resources", {}).get("CPU", 0) > 0):
                    out.append((e["hex"], tuple(e["addr"]),
                                e.get("queue", 0)))
            return out
        # in-process side: peers straight off the head's node table
        with head._lock:
            items = list(head.nodes.items())
        for h, n in items:
            if h == self.hex or not getattr(n, "alive", False):
                continue
            if hasattr(n, "store"):  # local Node
                if n.resources.total.get("CPU") > 0:
                    out.append((h, n, len(n._local_queue)))
            else:  # NodeProxy: reach the daemon via its object server
                load = head.node_loads.get(h, {})
                if n.resources_total.get("CPU", 0) > 0:
                    out.append((h, tuple(n.object_addr),
                                load.get("queue_depth", 0)))
        return out

    def _peer_channel(self, peer_hex: str, addr) -> Optional[Channel]:
        with self._peer_lock:
            ch = self._peers.get(peer_hex)
            if ch is not None:
                return ch
        key = self._peer_key or getattr(self.head, "cluster_key", None) \
            or getattr(self.head, "_cluster_key", None)
        if key is None:
            return None
        import multiprocessing.connection as mpc
        import socket

        try:
            # mpc.Client has no connect timeout (~2 min OS default on a
            # partitioned host, which would stall the submitter's reader
            # loop): probe reachability with a bounded connect first
            socket.create_connection(tuple(addr), timeout=2.0).close()
            conn = mpc.Client(address=tuple(addr), family="AF_INET",
                              authkey=key)
            from .protocol import set_nodelay

            set_nodelay(conn)
            conn.send(("peer_hello", self.hex))
            ch = Channel(conn)
        except Exception:
            return None
        with self._peer_lock:
            cur = self._peers.get(peer_hex)
            if cur is not None:
                ch.close()
                return cur
            self._peers[peer_hex] = ch
        threading.Thread(target=self._peer_reader, args=(peer_hex, ch),
                         daemon=True, name=f"peer-{peer_hex[:6]}").start()
        return ch

    def _peer_reader(self, peer_hex: str, ch: Channel) -> None:
        while True:
            try:
                tag, payload = ch.recv()
            except (EOFError, OSError, TypeError):
                break
            if tag == "pload":
                self.on_peer_load(*payload)
                continue
            if tag == "pstolen":
                # work we asked to steal: execute here, reply over ch
                try:
                    spec = pickle.loads(payload[0])
                except Exception:
                    continue
                self.submit_direct(spec, ("peer", ch))
                continue
            if tag == "pstream":
                self.on_peer_stream_item(*payload)
                continue
            if tag == "psub":
                # stream subscription for an owner living in this process
                self._serve_peer_stream_sub(ch, *payload)
                continue
            if tag == "psubrep":
                self._ssub_reply(*payload)
                continue
            if tag == "pdone":
                try:
                    task_id, err_name, results, exec_hex = payload
                except ValueError:
                    break  # malformed/mixed-version peer: drop it
                with self._lock:
                    entry = self._forwarded.pop(task_id, None)
                self._task_departed(task_id)
                with self._peer_lock:
                    n = self._peer_inflight.get(peer_hex, 0)
                    if n > 0:
                        self._peer_inflight[peer_hex] = n - 1
                if entry is not None:
                    self._reply_direct(entry[0], task_id, err_name, results,
                                       exec_hex)
        self._drop_peer(peer_hex)

    def _drop_peer(self, peer_hex: str) -> None:
        """Peer channel died: fail its forwarded tasks (owners retry)."""
        with self._peer_lock:
            ch = self._peers.pop(peer_hex, None)
            self._peer_inflight.pop(peer_hex, None)
        if ch is not None:
            ch.close()
        with self._lock:
            lost = [(tid, e) for tid, e in self._forwarded.items()
                    if e[2] == peer_hex]
            for tid, _ in lost:
                self._forwarded.pop(tid, None)
        for tid, (origin, spec, _) in lost:
            self._task_departed(tid)
            self._reply_direct(origin, tid, "NodeDiedError", [])

    # ---- batched head events --------------------------------------------

    def _append_devent(self, spec: TaskSpec, err_name, sealed_oids,
                       t_start: Optional[float] = None) -> None:
        cfg = global_config()
        ev = (spec.task_id.binary(), spec.function_name, err_name,
              sealed_oids, t_start or time.time(), time.time())
        if hasattr(self.head, "nodes"):
            # in-process node: the head is a method call away — publish
            # synchronously so state API / timeline / waiters see the task
            # immediately (batching only pays off across a daemon link)
            self._publish_devents([ev])
            return
        flush = None
        with self._dev_lock:
            if not self._devents:
                self._dev_first = time.monotonic()
            self._devents.append(ev)
            if len(self._devents) >= cfg.direct_event_batch_size:
                flush, self._devents = self._devents, []
        if flush:
            self._publish_devents(flush)

    def _publish_devents(self, batch) -> None:
        try:
            self.head.publish_direct_events(self.hex, batch)
        except Exception:
            pass  # head link lost: daemon is shutting down

    def _ensure_direct_flusher(self) -> None:
        if hasattr(self.head, "nodes"):
            return  # in-process node: events publish synchronously
        with self._dev_lock:
            if self._dev_flusher_started:
                return
            self._dev_flusher_started = True
        cfg = global_config()
        interval = max(0.005, cfg.direct_event_flush_ms / 1000.0)

        def loop():
            while self.alive:
                time.sleep(interval)
                flush = None
                with self._dev_lock:
                    if self._devents and (time.monotonic() - self._dev_first
                                          >= interval):
                        flush, self._devents = self._devents, []
                if flush:
                    self._publish_devents(flush)

        threading.Thread(target=loop, daemon=True,
                         name=f"devents-{self.hex[:6]}").start()

    def _pump(self) -> None:
        """Match queued tasks with idle workers; start workers as needed.

        When no worker is idle, plain unbound tasks are staged onto a busy
        plain-task worker up to ``worker_pipeline_depth`` deep (reference:
        normal_task_submitter lease pipelining) so the worker starts the
        next task without waiting out the done->dispatch round trip.
        """
        cfg = global_config()
        depth = max(1, cfg.worker_pipeline_depth)
        direct_cap = max(1, int(self.max_workers * cfg.direct_slot_fraction))
        to_send: List[Tuple[WorkerHandle, TaskSpec, dict]] = []
        with self._lock:
            # one scan per pump (not per task): assignments made in this
            # call adjust the cached count below
            direct_running = self._direct_running_locked()
            while self._local_queue:
                idx = 0
                spec, binding = self._local_queue[0]
                if (spec.task_id in self._direct
                        and direct_running >= direct_cap):
                    # direct tasks at their slot cap: let a waiting
                    # head-dispatched (resource-bound) task leapfrog so the
                    # scheduler's placements aren't starved by a direct
                    # flood (priority-inversion guard). With no head task
                    # waiting the cap does not apply (work conservation).
                    for j in range(1, len(self._local_queue)):
                        s2, b2 = self._local_queue[j]
                        if s2.task_id not in self._direct:
                            idx, spec, binding = j, s2, b2
                            break
                w = None
                chips = tuple((binding or {}).get("TPU") or ())
                if chips:
                    w = self._chip_ready.pop(chips, None)
                    if w is None or w.state != "idle":
                        if chips not in self._chip_starting.values():
                            self._start_worker_locked(chips)
                        break  # its registration pumps again
                while w is None and self._idle:
                    cand = self._idle.popleft()
                    if cand.state == "idle":
                        w = cand
                if w is None:
                    # Prefer starting a new worker while under the limit —
                    # staging must never strand a task behind a long task
                    # when free capacity exists. Queued actor creations
                    # each get a dedicated worker beyond the pool.
                    active = self._pool_active_locked()
                    limit = self.max_workers + sum(
                        1 for s, _ in self._local_queue if s.is_actor_creation)
                    if active < limit:
                        self._start_worker_locked()
                        break
                    # at capacity: stage onto a busy plain-task worker
                    if not spec.is_actor_creation and not binding:
                        for cand in self._workers.values():
                            if (cand.state == "busy" and cand.chips is None
                                    and len(cand.assigned) < depth
                                    and all(not s.is_actor_creation and not b
                                            for s, b, _ in
                                            cand.assigned.values())):
                                w = cand
                                break
                    if w is None:
                        break
                del self._local_queue[idx]
                if spec.task_id in self._direct:
                    direct_running += 1
                w.state = "busy"
                # stamp the attempt at assignment: spec objects are shared
                # with the head and mutate on retry, so a late finish must
                # carry the attempt it actually ran
                w.assigned[spec.task_id] = (spec, binding, spec.attempt)
                to_send.append((w, spec, binding))
            # rescue: a worker sits idle with nothing queued while another
            # has staged-unstarted tasks — ask for one back so it isn't
            # stuck behind a long/blocked task. (Not triggered by workers
            # merely starting, and never for tasks staged in this call —
            # both would ping-pong stage/unstage.)
            unstage: List[Tuple[WorkerHandle, object]] = []
            just_staged = {spec.task_id for _, spec, _ in to_send}
            if not self._local_queue and self._idle:
                for cand in self._workers.values():
                    if cand.state == "busy" and len(cand.assigned) > 1:
                        last_tid = next(reversed(cand.assigned))
                        if last_tid not in just_staged:
                            unstage.append((cand, last_tid))
            # refill the prewarmed pool: assignments above may have just
            # consumed idle workers (a serve scale-out claims one warm
            # process per new replica) — fork replacements NOW so the
            # next ramp step finds the pool full again
            self._ensure_prewarm_locked()
        for w, spec, binding in to_send:
            try:
                w.channel.send("exec", pickle.dumps(spec), binding)
            except OSError:
                self._on_worker_dead(w)
        for w, tid in unstage:
            try:
                w.channel.send("unstage", tid)
            except OSError:
                self._on_worker_dead(w)
        if not to_send and not unstage:
            # nothing to do locally: try pulling work from a loaded peer
            self._maybe_steal()

    # ---- work stealing ---------------------------------------------------
    # (round 4, audit weak #7: spillback was submit-time-only — a task
    # queued behind a long task was never re-balanced. Idle nodes now PULL
    # queued direct tasks from the deepest-queued peer over the same mesh
    # the spill push uses; reference analog: LocalTaskManager spillback
    # re-evaluation, inverted into a thief-initiated protocol.)

    def _steal_ticker(self) -> None:
        while not self._stop_event.wait(0.5):
            try:
                self._gossip_load()
                self._maybe_steal()
            except Exception:
                pass

    def _gossip_load(self) -> None:
        """Push this node's queue depth to every established peer
        channel (one-way). Only connected peers hear it — exactly the
        nodes actively exchanging work, where freshness matters."""
        with self._peer_lock:
            chans = list(self._peers.items())
        if not chans:
            return
        self._gossip_version += 1
        with self._lock:
            depth = len(self._local_queue)
        for peer_hex, ch in chans:
            try:
                ch.send("pload", self.hex, depth, self._gossip_version)
            except (OSError, EOFError):
                pass  # peer death handled by its reader

    def on_peer_load(self, peer_hex: str, depth: int,
                     version: int) -> None:
        with self._peer_lock:
            cur = self._peer_loads.get(peer_hex)
            if cur is None or version >= cur[0]:
                self._peer_loads[peer_hex] = (version, depth,
                                              time.monotonic())

    def _maybe_steal(self) -> None:
        cfg = global_config()
        if not cfg.direct_steal_enabled:
            return
        now = time.monotonic()
        if now - getattr(self, "_last_steal", 0.0) < \
                cfg.direct_steal_interval_ms / 1000.0:
            return
        self._last_steal = now
        with self._lock:
            if self._local_queue or not self._idle:
                return
            free = sum(1 for w in self._workers.values()
                       if w.state == "idle" and w.chips is None)
        cands = self._peer_candidates()
        if not cands:
            return
        with self._peer_lock:
            now = time.monotonic()
            fresh = {h: q for h, (v, q, ts) in self._peer_loads.items()
                     if now - ts < 2.0}
        cands = [(h, handle, fresh.get(h, q)) for h, handle, q in cands]
        cands.sort(key=lambda c: -c[2])
        peer_hex, handle, queue = cands[0]
        if queue < cfg.direct_steal_min_queue:
            return
        want = max(1, min(free, queue // 2))
        if not isinstance(handle, (tuple, list)):
            # in-process peer: pop eligible tasks directly
            for spec, origin in handle._pop_stealable(want):
                with handle._lock:
                    handle._forwarded[spec.task_id] = (origin, spec, self)
                self.submit_direct(spec, ("node", handle, origin))
            return
        ch = self._peer_channel(peer_hex, handle)
        if ch is None:
            return
        try:
            ch.send("psteal", want)
        except (OSError, EOFError):
            self._drop_peer(peer_hex)

    def _pop_stealable(self, k: int):
        """Victim side: hand over up to k queued, unstarted direct plain
        tasks (skip actor creations, resource-bound, already-hopped-out
        tasks). Returns [(spec, origin)] with the _direct entries removed
        — the caller forwards them and owns the reply routing."""
        out = []
        with self._lock:
            keep = deque()
            while self._local_queue and len(out) < k:
                spec, binding = self._local_queue.pop()  # steal the TAIL
                entry = self._direct.get(spec.task_id)
                if (entry is None or binding or spec.is_actor_creation
                        or spec.actor_id is not None
                        or spec.direct_hops >= 2):
                    keep.appendleft((spec, binding))
                    continue
                # NOTE: the arg lease (_leased_tasks) intentionally stays:
                # every caller immediately re-tracks the task in
                # _forwarded (reply still routes through this victim), so
                # the lease releases on the normal pdone/depart path
                del self._direct[spec.task_id]
                spec.direct_hops += 1
                out.append((spec, entry[0]))
            self._local_queue.extend(keep)
        return out

    def _serve_steal(self, ch: Channel, k: int) -> None:
        """Victim side of a remote steal: ship tasks; replies come back
        over the same channel ('pdone' handled by _serve_peer)."""
        marker = ("_stolen", ch)
        stolen = self._pop_stealable(int(k))
        for i, (spec, origin) in enumerate(stolen):
            with self._lock:
                self._forwarded[spec.task_id] = (origin, spec, marker)
            try:
                ch.send("pstolen", pickle.dumps(spec))
            except (OSError, EOFError):
                # thief gone: run the rest ourselves (every popped task
                # must land somewhere — a dropped one hangs its owner)
                for spec2, origin2 in stolen[i:]:
                    with self._lock:
                        self._forwarded.pop(spec2.task_id, None)
                        spec2.direct_hops -= 1
                        self._direct[spec2.task_id] = (origin2, spec2,
                                                       time.time())
                    self.dispatch(spec2, {})
                return

    def on_peer_session_closed(self, ch) -> None:
        """A peer session (thief) died: fail its in-flight stolen tasks
        back to their owners (they retry per max_retries)."""
        marker = ("_stolen", ch)
        with self._lock:
            lost = [(tid, e) for tid, e in self._forwarded.items()
                    if e[2] == marker]
            for tid, _e in lost:
                self._forwarded.pop(tid, None)
        for tid, (origin, spec, _m) in lost:
            self._task_departed(tid)
            self._reply_direct(origin, tid, "NodeDiedError", [])

    def on_peer_done(self, task_id, err_name, results, exec_hex) -> None:
        """A completion for a task we handed to a peer (stolen or
        spilled) arriving over either peer-session direction."""
        with self._lock:
            entry = self._forwarded.pop(task_id, None)
        self._task_departed(task_id)
        if entry is not None:
            self._reply_direct(entry[0], task_id, err_name, results,
                               exec_hex)

    def on_peer_stream_item(self, task_id, index: int,
                            data: Optional[bytes], exec_hex) -> None:
        """A stream-item announcement for a task we handed to a peer:
        pass it along toward the owner (the forwarding entry stays — the
        completion is still to come, FIFO behind the items)."""
        with self._lock:
            entry = self._forwarded.get(task_id)
        if entry is not None:
            self._reply_stream_item(entry[0], task_id, index, data,
                                    exec_hex)

    def _direct_running_locked(self) -> int:
        """Worker slots currently held by direct (head-bypass) tasks."""
        n = 0
        for w in self._workers.values():
            for s, _, _ in w.assigned.values():
                if s.task_id in self._direct:
                    n += 1
        return n

    # ------------------------------------------------------------ workers

    def _ensure_prewarm_locked(self) -> None:
        """Keep ``serve_prewarm_pool_size`` idle (or starting) workers on
        standby beyond current demand, so a scale-out consumes a warm
        pre-forked process instead of paying the fork+import cold start
        on the ramp step (the scale-out p99 tail killer). Bounded: never
        pushes total workers past max_workers + pool size."""
        target = global_config().serve_prewarm_pool_size
        if target <= 0 or not self.alive:
            return
        warm = sum(1 for w in self._idle if w.state == "idle") \
            + self._num_starting
        active = self._pool_active_locked()
        cap = self.max_workers + target
        while warm < target and active < cap:
            self._start_worker_locked()
            warm += 1
            active += 1

    def _pool_active_locked(self) -> int:
        """Live or starting workers of the CPU pool (chip-bound workers
        are outside it: they neither fill it nor are limited by it)."""
        return sum(1 for x in self._workers.values()
                   if x.state in ("idle", "busy") and x.chips is None) \
            + self._num_starting

    def _start_worker_locked(
            self, chips: Optional[Tuple[int, ...]] = None) -> None:
        """Spawn a pool worker, or (``chips``) the one process that will
        own those TPU chips. The platform is fixed here, by environment,
        before the child can import jax."""
        from .accelerators import worker_env

        if not chips:
            self._num_starting += 1
        env = worker_env(os.environ, chips,
                         int(self.resources.total.get("TPU")))
        if not chips and self.resources.total.get("GPU"):
            # a pooled worker takes its GPU binding at exec time
            # (CUDA_VISIBLE_DEVICES), so this pool cannot be held to the CPU
            env["JAX_PLATFORMS"] = os.environ.get("JAX_PLATFORMS", "")
        env["RAY_TPU_NODE_HEX"] = self.hex
        # make ray_tpu importable in the worker regardless of driver cwd
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        log_path = os.path.join(self.session_dir, "logs")
        os.makedirs(log_path, exist_ok=True)
        log_file = os.path.join(log_path, f"worker-{time.time_ns()}.log")
        out = open(log_file, "ab")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.worker_runtime",
             "--address", self._sock_path, "--authkey", self._authkey.hex()],
            env=env, stdout=out, stderr=subprocess.STDOUT,
            cwd=os.getcwd(),
        )
        if chips:
            self._chip_starting[proc.pid] = tuple(chips)
            self._chip_procs[proc.pid] = proc
        else:
            self._starting_pids.add(proc.pid)
        self._tail_files[log_file] = [0, proc.pid, None]
        self._ensure_log_tailer()
        # handle registered on accept
        threading.Thread(
            target=self._reap, args=(proc,), daemon=True
        ).start()

    def _reap(self, proc: subprocess.Popen) -> None:
        proc.wait()
        # a worker that died before registering would leak _num_starting
        # (and with it a phantom slot in _pump's active count) forever
        died_starting = False
        with self._lock:
            if proc.pid in self._starting_pids:
                self._starting_pids.discard(proc.pid)
                self._num_starting = max(0, self._num_starting - 1)
                died_starting = True
            if self._chip_starting.pop(proc.pid, None) is not None:
                died_starting = True
            for st in self._tail_files.values():
                if st[1] == proc.pid and st[2] is None:
                    st[2] = time.monotonic()  # tailer drops it after a
                    # final read window
        if died_starting and self.alive:
            # the freed capacity must re-pump NOW: with all tasks already
            # queued, no future event would ever start a replacement
            # worker and the queue would strand forever
            self._pump()

    def _accept_loop(self) -> None:
        import multiprocessing.context as _mpctx

        while self.alive:
            try:
                conn = self._listener.accept()
            except _mpctx.AuthenticationError:
                # worker killed mid-handshake (node/cluster shutdown race)
                continue
            except (OSError, EOFError):
                return
            # handshake off-thread: a slow registrant must not hold up
            # accept() (concurrent prestarts would pile into the backlog)
            threading.Thread(target=self._register_worker, args=(conn,),
                             daemon=True,
                             name=f"register-{self.hex[:6]}").start()

    def _register_worker(self, conn) -> None:
        channel = Channel(conn)
        try:
            tag, (pid,) = channel.recv()
            assert tag == "register"
        except Exception:
            channel.close()
            return
        self._finish_register(channel, pid)

    def _finish_register(self, channel, pid) -> None:
        wid = WorkerID.from_random()
        w = WorkerHandle(worker_id=wid, channel=channel, pid=pid, state="idle")
        with self._lock:
            if pid in self._starting_pids:
                self._starting_pids.discard(pid)
                self._num_starting = max(0, self._num_starting - 1)
            w.chips = self._chip_starting.pop(pid, None)
            self._workers[wid] = w
            if w.chips:
                self._chip_ready[w.chips] = w
            else:
                self._idle.append(w)
        init_info = {
            "worker_id": wid.binary(),
            "node_hex": self.hex,
            "node_ip": self.node_ip,
            "job_id": self.head.job_id.binary(),
            "arena_path": self.store.arena_path,
            "arena_capacity": self.store.capacity,
            "session_dir": self.session_dir,
            "tpu_chips": list(w.chips) if w.chips else None,
            "config": global_config().to_json(),
            # driver-visible import roots: functions pickled BY REFERENCE
            # against modules the driver loaded from script-local dirs
            # (pytest rootdir inserts, sys.path hacks) must resolve in the
            # worker too (reference: ray ships the driver's sys.path via
            # the runtime env's working_dir/py_modules mechanism)
            "sys_path": [p for p in sys.path if p],
        }
        channel.send("init", init_info)
        w.reader = threading.Thread(
            target=self._reader_loop, args=(w,), daemon=True,
            name=f"reader-{wid.hex()[:6]}",
        )
        w.reader.start()
        self._pump()

    def _reader_loop(self, w: WorkerHandle) -> None:
        try:
            self._reader_loop_inner(w)
        except Exception:
            # a message-processing bug must NEVER silently kill this
            # thread: the worker's done/rpc messages would go unread and
            # its tasks hang forever. Log loudly and declare the worker
            # dead so its work is retried.
            import traceback

            print(f"[ray_tpu] node {self.hex[:6]} worker-reader crashed:\n"
                  + traceback.format_exc(), file=sys.stderr, flush=True)
            self._on_worker_dead(w)

    def _reader_loop_inner(self, w: WorkerHandle) -> None:
        while True:
            try:
                tag, payload = w.channel.recv()
            except (EOFError, OSError):
                self._on_worker_dead(w)
                return
            if tag == "done":
                task_id, results, err_name = payload
                self._on_task_done(w, task_id, results, err_name)
            elif tag == "store":
                req_id, op, *args = payload
                if op in ("get", "wait", "create"):
                    self._handler_pool.submit(self._handle_store, w, req_id, op, args)
                else:
                    self._handle_store(w, req_id, op, args)
            elif tag == "rpc":
                req_id, op, *args = payload
                if op in ("pub_poll", "stream_sub"):
                    # long-parking rounds (pubsub polls, stream
                    # subscriptions) get their own thread — they must not
                    # starve the bounded shared pool
                    threading.Thread(
                        target=self._handle_rpc, args=(w, req_id, op, args),
                        daemon=True, name="pub-poll").start()
                else:
                    self._handler_pool.submit(self._handle_rpc, w, req_id,
                                              op, args)
            elif tag == "pub1":
                # one-way fire-and-forget publish (tracing hot path)
                try:
                    self.head.publish_oneway(payload[0], payload[1])
                except Exception:
                    pass
            elif tag == "dsubmit":
                # direct (head-bypass) submission from this worker
                spec = pickle.loads(payload[0])
                self.submit_direct(spec, ("worker", w.worker_id))
            elif tag == "dcancel":
                self.cancel_direct(payload[0], payload[1])
            elif tag == "srep":
                # owner worker's reply to a stream_sub round ("ssub")
                self._ssub_reply(*payload)
            elif tag == "stream":
                task_id, index, data = payload
                self._on_worker_stream_item(task_id, index, data)
            elif tag == "metrics":
                self.head.on_worker_metrics(
                    f"{self.hex[:6]}:{w.pid}", payload[0])
            elif tag == "spans":
                # worker flight-recorder batch -> head span store; the
                # node stamps source AND its node hex (the head keys
                # clock offsets by node)
                try:
                    self.head.on_worker_spans(
                        f"{self.hex[:6]}:{w.pid}",
                        dict(payload[0], node_hex=self.hex))
                except Exception:
                    pass
            elif tag == "cevents":
                # worker cluster events -> head event ring (one-way)
                try:
                    self.head.record_cluster_events(payload[0])
                except Exception:
                    pass
            elif tag == "refs":
                # worker ref-table report -> head ownership table; the
                # node stamps the source id (same keying as metrics)
                try:
                    self.head.on_ref_report(f"{self.hex[:6]}:{w.pid}",
                                            payload[0])
                except Exception:
                    pass
            elif tag == "stack_rep":
                # worker's collapsed-stack reply to a "stack" round
                req_id, text = payload
                slot = self._stack_pending.get(req_id)
                if slot is not None:
                    slot[1] = text
                    slot[0].set()
            elif tag == "unstaged":
                # worker handed back a staged-unstarted task: requeue it
                tid = payload[0]
                with self._lock:
                    entry = w.assigned.pop(tid, None)
                    if entry is not None:
                        self._local_queue.appendleft(entry[:2])
                        if w.state == "busy" and not w.assigned:
                            w.state = "idle"
                            self._idle.append(w)
                if entry is not None:
                    self._pump()
            elif tag == "exit":
                # graceful actor exit
                self._on_worker_exit(w)
                return

    def _on_worker_stream_item(self, task_id, index: int,
                               data: Optional[bytes]) -> None:
        """A worker announced stream item ``index``. Direct tasks route it
        straight to the owner over the reply chain (zero head records);
        head-path tasks keep the head stream-record protocol. Inline
        payloads are also sealed locally so the object stays directory-
        resolvable for borrowers (location rides the completion devent on
        the direct path)."""
        oid = ObjectID.for_stream(task_id, index)
        with self._lock:
            entry = self._direct.get(task_id)
        if entry is not None:
            if data is not None:
                try:
                    self.store.put_inline(oid, data, False)
                    with self._lock:
                        self._direct_stream_oids.setdefault(
                            task_id, []).append(oid)
                except Exception:
                    pass  # store full: the owner's inline copy suffices
            self._reply_stream_item(entry[0], task_id, index, data,
                                    self.hex)
            return
        # head path: seal + register the location, then announce
        if data is not None:
            try:
                self.store.put_inline(oid, data, False)
                self.head.on_object_sealed(oid, self.hex)
            except Exception:
                pass
        self.head.on_stream_item(task_id, index)

    def _reply(self, w: WorkerHandle, req_id: int, ok: bool, value) -> None:
        try:
            w.channel.send("rep", req_id, ok, value)
        except OSError:
            pass

    def _handle_store(self, w: WorkerHandle, req_id: int, op: str, args) -> None:
        try:
            if op == "get":
                oid, timeout, *rest = args
                hint = rest[0] if rest else None
                rep = self.head.get_object_for_node(self, oid, timeout,
                                                    hint=hint)
                self._reply(w, req_id, True, rep)
            elif op == "wait":
                oids, num_returns, timeout, *rest = args
                fetch_local = rest[0] if rest else False
                ready = self.head.wait_objects(oids, num_returns, timeout,
                                               fetch_local)
                self._reply(w, req_id, True, ready)
            elif op == "create":
                oid, size = args
                offset, _ = self.store.create(oid, size)
                self._reply(w, req_id, True, offset)
            elif op == "seal":
                oid, is_error = args
                self.store.seal(oid, is_error)
                self.head.on_object_sealed(oid, self.hex)
                self._reply(w, req_id, True, None)
            elif op == "put_inline":
                oid, data, is_error = args
                self.store.put_inline(oid, data, is_error)
                self.head.on_object_sealed(oid, self.hex)
                self._reply(w, req_id, True, None)
            else:
                self._reply(w, req_id, False, ValueError(f"bad store op {op}"))
        except Exception as e:  # noqa: BLE001
            self._reply(w, req_id, False, e)

    def _handle_rpc(self, w: WorkerHandle, req_id: int, op: str, args) -> None:
        try:
            if op == "stream_sub":
                # owner-routed stream subscription: served by this node's
                # routing (worker/peer/driver channels) — the head never
                # sees it
                result = self.serve_stream_sub(*args)
            else:
                result = self.head.handle_worker_rpc(self, w, op, args)
            self._reply(w, req_id, True, result)
        except Exception as e:  # noqa: BLE001
            self._reply(w, req_id, False, e)

    # ------------------------------------------------------------ lifecycle

    def _on_task_done(self, w: WorkerHandle, task_id, results, err_name) -> None:
        retire = False
        with self._lock:
            entry = w.assigned.pop(task_id, None)
            direct = self._direct.pop(task_id, None)
            if entry is not None:
                spec, binding, attempt = entry
                if spec.is_actor_creation and err_name is None:
                    w.state = "actor"
                    w.actor_id = spec.actor_id
                    # direct actor-call routing table (set BEFORE the head
                    # learns ALIVE, so owners resolving via the head never
                    # race ahead of this index)
                    self._actor_workers[spec.actor_id] = w.worker_id
                elif w.state == "busy" and not w.assigned:
                    if w.chips:
                        retire = True  # it may hold the chips: never pooled
                    else:
                        w.state = "idle"
                        self._idle.append(w)
            else:
                # actor task done (worker stays "actor") or stale
                spec, binding, attempt = None, None, None
        if retire:
            # BEFORE the head hears the task finished and frees the chips
            self._retire_chip_worker(w)
        if direct is not None:
            # head-bypass path: owner settles (retries live there)
            self._finish_direct(direct[0], direct[1], task_id, results,
                                err_name, t_start=direct[2])
            self._task_departed(task_id)
        else:
            # The head decides whether to seal results (it may retry).
            self.head.on_task_finished(self, task_id, err_name, spec, binding,
                                       results, worker_id=w.worker_id,
                                       attempt=attempt)
        self._pump()

    def _retire_chip_worker(self, w: WorkerHandle) -> None:
        """A chip-bound worker has served its one spec: ask it to exit
        (the graceful path flushes its spans and metrics), wait until the
        process is gone, and only then let anyone learn it is."""
        try:
            w.channel.send("shutdown")
        except OSError:
            pass
        self._on_worker_exit(w)

    def _await_chip_worker_exit(self, w: WorkerHandle) -> None:
        proc = self._chip_procs.pop(w.pid, None)
        if proc is not None:
            self._wait_chip_proc_gone(proc, w.chips, _CHIP_EXIT_GRACE_S)

    def _wait_chip_proc_gone(self, proc: subprocess.Popen, chips,
                             grace_s: float) -> None:
        """Return when the process that held ``chips`` is gone. It gets
        ``grace_s`` to leave by itself, then SIGKILL. A kill is not refused,
        but a process inside the chip driver's release of its device
        mappings cannot be interrupted and is gone when the kernel is done
        with it: over 20 s for a worker that held four v5e chips (chip run,
        PR 21). The chips are not free before that, so this waits for as
        long as it takes, says so, and never raises (it runs on the
        worker's reader thread, ahead of the task's result). Past
        ``_CHIP_EXIT_GIVE_UP_S`` it gives up with an ERROR event: the next
        owner of these chips then fails loudly if they are still held."""
        t0 = time.monotonic()
        try:
            proc.wait(timeout=grace_s)
            return
        except subprocess.TimeoutExpired:
            proc.kill()
        from ray_tpu.util import events as events_mod

        while True:
            try:
                proc.wait(timeout=_CHIP_EXIT_POLL_S)
                return
            except subprocess.TimeoutExpired:
                waited = time.monotonic() - t0
                gave_up = waited >= _CHIP_EXIT_GIVE_UP_S
                msg = (f"node {self.hex[:8]}: chip worker pid {proc.pid} "
                       f"(chips {list(chips or ())}) was killed and is "
                       f"still exiting after {waited:.0f} s; "
                       + ("giving up on it, its chips may still be held"
                          if gave_up else
                          "its chips stay bound until it is gone"))
                print(f"[ray_tpu] {msg}", file=sys.stderr, flush=True)
                events_mod.emit("ERROR" if gave_up else "WARNING",
                                events_mod.SOURCE_NODE, msg,
                                entity_id=self.hex, pid=proc.pid,
                                waited_s=round(waited, 1))
                if gave_up:
                    return

    def _on_worker_exit(self, w: WorkerHandle) -> None:
        # the head frees this worker's chips when it hears of the exit:
        # not before the process that held them is gone
        self._await_chip_worker_exit(w)
        with self._lock:
            w.state = "dead"
            self._workers.pop(w.worker_id, None)
            lost = self._drop_actor_direct_locked(w)
        self._fail_worker_ssubs(w.worker_id, w.pid)
        self._fail_worker_stack_waiters(w.worker_id)
        # head first (same reasoning as _on_worker_dead): owners failing
        # these calls read the FSM for the attributed death cause
        self.head.on_worker_exit(self, w)
        for origin, spec, err in lost:
            self._task_departed(spec.task_id)
            self._reply_direct(origin, spec.task_id, err, [])

    def _drop_actor_direct_locked(self, w: WorkerHandle):
        """Remove a dead actor worker from the routing index and collect
        its in-flight direct calls as (origin, spec, err_name).

        Every ``_direct`` actor entry was already channel-sent to the
        worker process (``_submit_direct_actor`` dispatches immediately),
        so any of them MAY have executed: at-most-once demands
        ActorDiedError (retries consume max_task_retries). The
        provably-undelivered case — dispatch_to_worker failing — bounces
        ActorMissingError at submit time instead (never-executed ->
        always safe to resubmit, direct.py protocol)."""
        if w.actor_id is None:
            return []
        if self._actor_workers.get(w.actor_id) == w.worker_id:
            del self._actor_workers[w.actor_id]
        lost = []
        for tid, (origin, spec, _t0) in list(self._direct.items()):
            if spec.actor_id == w.actor_id:
                del self._direct[tid]
                self._direct_stream_oids.pop(tid, None)
                lost.append((origin, spec, "ActorDiedError"))
        return lost

    def _on_worker_dead(self, w: WorkerHandle) -> None:
        with self._lock:
            if w.state == "dead":
                return
            prev_state = w.state
            w.state = "dead"
            self._workers.pop(w.worker_id, None)
            assigned = list(w.assigned.values())
            w.assigned.clear()
            direct = [self._direct.pop(s.task_id)
                      for s, _, _ in assigned
                      if s.task_id in self._direct]
            direct_ids = {spec.task_id for _, spec, _ in direct}
            for tid in direct_ids:
                self._direct_stream_oids.pop(tid, None)
            lost_actor = self._drop_actor_direct_locked(w)
            if w.chips and self._chip_ready.get(w.chips) is w:
                del self._chip_ready[w.chips]
        w.channel.close()
        self._await_chip_worker_exit(w)  # as in _on_worker_exit
        self._fail_worker_ssubs(w.worker_id, w.pid)
        self._fail_worker_stack_waiters(w.worker_id)
        head_assigned = [e for e in assigned if e[0].task_id not in direct_ids]
        # head FIRST, owner replies second: the owner's failure handling
        # (possibly inline on THIS thread for an in-process driver)
        # consults the actor FSM for the attributed death cause and the
        # restart decision — reporting the crash after the replies would
        # make it read a stale ALIVE
        if head_assigned:
            for spec, binding, _attempt in head_assigned:
                self.head.on_worker_crashed(self, w, spec, binding, prev_state)
        else:
            self.head.on_worker_crashed(self, w, None, None, prev_state)
        # direct tasks: the OWNER retries — report the crash straight back
        for origin, spec, _t0 in direct:
            self._task_departed(spec.task_id)
            self._reply_direct(origin, spec.task_id, "WorkerCrashedError", [])
        for origin, spec, err in lost_actor:
            self._task_departed(spec.task_id)
            self._reply_direct(origin, spec.task_id, err, [])
        self._pump()

    def cancel_task(self, task_id, worker_id: Optional[WorkerID],
                    force: bool) -> None:
        """Forward a cancel to the worker running ``task_id`` (or the given
        actor worker). Reference: CoreWorker::CancelTask -> executor interrupt."""
        with self._lock:
            target = None
            if worker_id is not None:
                target = self._workers.get(worker_id)
            else:
                for w in self._workers.values():
                    if task_id in w.assigned:
                        target = w
                        break
        if target is None:
            return
        try:
            target.channel.send("cancel", task_id)
        except OSError:
            pass
        if force:
            self.kill_worker(target.worker_id)

    def _ensure_log_tailer(self) -> None:
        """Tail worker log files -> head -> driver stderr (reference:
        log_monitor.py:581 tails per-proc files to the driver)."""
        if self._log_tailer_started or not global_config().log_to_driver:
            return
        self._log_tailer_started = True

        def tail():
            while self.alive:
                now = time.monotonic()
                for path, st in list(self._tail_files.items()):
                    try:
                        with open(path, "rb") as f:
                            f.seek(st[0])
                            data = f.read()
                    except OSError:
                        self._tail_files.pop(path, None)
                        continue
                    if data:
                        st[0] += len(data)
                        try:
                            self.head.on_worker_log(
                                self.hex, st[1],
                                data.decode("utf-8", "replace"))
                        except Exception:
                            pass
                    if st[2] is not None and now - st[2] > 2.0:
                        self._tail_files.pop(path, None)  # worker gone
                time.sleep(0.5)

        threading.Thread(target=tail, daemon=True,
                         name=f"logtail-{self.hex[:6]}").start()

    def push_object_to(self, oid, targets) -> int:
        """Broadcast-tree hop: deliver ``oid`` from this node's store to
        every (hex, addr) in ``targets`` (binomial fan-out)."""
        from .object_transfer import fan_out_push

        key = self._peer_key or getattr(self.head, "cluster_key", None) \
            or getattr(self.head, "_cluster_key", None)
        if key is None:
            return 0
        return fan_out_push(self.store, key, oid,
                            [t for t in targets if t[0] != self.hex])

    def update_node_ip(self, ip: str) -> None:
        """Upgrade this node's advertised IP and push it to every
        already-registered worker. Workers prestarted in __init__ received
        init_info with the loopback IP before start_node_server() learned
        the routable one; without this push an actor matched to such a
        worker would advertise 127.0.0.1 as its coordinator address in a
        multi-host Train bootstrap."""
        self.node_ip = ip
        with self._lock:
            workers = list(self._workers.values())
        for w in workers:
            try:
                w.channel.send("node_ip", ip)
            except OSError:
                pass

    def start_object_server(self, authkey: bytes, host: Optional[str] = None):
        """Start the node-to-node chunk server (multi-host mode).

        Binds all interfaces when the node has a non-loopback ``node_ip``
        and advertises that IP, so cross-host pulls get a routable address.
        """
        from .object_transfer import ObjectServer

        if getattr(self, "object_server", None) is None:
            if host is None:
                host = ("127.0.0.1" if self.node_ip.startswith("127.")
                        else "0.0.0.0")
            self._peer_key = authkey
            self.object_server = ObjectServer(
                self.store, authkey, host,
                advertise_host=self.node_ip, node=self)
        return self.object_server

    def kill_worker(self, worker_id: WorkerID) -> None:
        with self._lock:
            w = self._workers.get(worker_id)
        if w is None:
            return
        try:
            w.channel.send("shutdown")
        except OSError:
            pass
        try:
            os.kill(w.pid, 9)
        except (OSError, ProcessLookupError):
            pass

    def num_workers(self) -> int:
        with self._lock:
            return len(self._workers)

    def collect_worker_stacks(self, duration_s: float,
                              timeout: float = 3.0) -> Dict[str, str]:
        """One bounded ``stack`` round per live worker: each samples its
        own threads for ``duration_s`` and replies one-way. Returns
        {"<node6>:<pid>": collapsed text}; dead/slow workers are simply
        absent (their pending slots are failed by _on_worker_dead)."""
        waiters = []
        with self._lock:
            workers = list(self._workers.values())
        for w in workers:
            with self._lock:
                self._stack_seq += 1
                req_id = self._stack_seq
                slot = [threading.Event(), None, w.worker_id]
                self._stack_pending[req_id] = slot
            try:
                w.channel.send("stack", req_id,
                               int(duration_s * 1000))
            except OSError:
                self._stack_pending.pop(req_id, None)
                continue
            waiters.append((w, req_id, slot))
        out: Dict[str, str] = {}
        deadline = time.monotonic() + timeout + duration_s
        for w, req_id, slot in waiters:
            slot[0].wait(max(0.0, deadline - time.monotonic()))
            self._stack_pending.pop(req_id, None)
            if slot[1] is not None:
                out[f"{self.hex[:6]}:{w.pid}"] = slot[1]
        return out

    def _fail_worker_stack_waiters(self, worker_id) -> None:
        """Death path for the stack round: a dead worker's pending
        collectors wake now with no reply."""
        with self._lock:
            gone = [(rid, s) for rid, s in self._stack_pending.items()
                    if len(s) > 2 and s[2] == worker_id]
            for rid, _s in gone:
                self._stack_pending.pop(rid, None)
        for _rid, slot in gone:
            slot[0].set()

    def shutdown(self) -> None:
        self.alive = False
        self._stop_event.set()
        with self._lock:
            workers = list(self._workers.values())
        for w in workers:
            try:
                w.channel.send("shutdown")
            except OSError:
                pass
            try:
                os.kill(w.pid, 9)
            except (OSError, ProcessLookupError):
                pass
        chip_procs = list(self._chip_procs.values())
        for proc in chip_procs:
            proc.kill()
        for proc in chip_procs:
            # chip owners (registered or still starting) are gone, not
            # merely signalled, when shutdown returns
            self._wait_chip_proc_gone(proc, None, 0.0)
        self._chip_procs.clear()
        from .protocol import close_listener

        close_listener(self._listener)  # wakes the parked accept()
        # reap the accept loop and the steal ticker so shutdown leaves
        # no threads behind
        self._accept_thread.join(timeout=2.0)
        if self._steal_thread is not None:
            self._steal_thread.join(timeout=2.0)
        if getattr(self, "object_server", None) is not None:
            self.object_server.close()
            # drop pooled transfer connections: this node's outbound conns
            # are dead weight now, and peers' conns to it will fail health
            # checks. Coarse (the pool is process-global; co-resident nodes
            # re-dial on their next pull) but leak-free.
            from .object_transfer import close_pool

            close_pool()
        self.store.close()
        self._handler_pool.shutdown(wait=False)
