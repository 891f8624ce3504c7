"""Always-on flight recorder: per-process lock-free span rings + the
cross-host trace merge behind ``python -m ray_tpu timeline``.

Every process (driver/head, node daemon, worker) keeps ONE preallocated
ring of fixed-size span records. The hot path is two monotonic-clock
reads and one tuple store (~100 ns): ``itertools.count().__next__`` is
GIL-atomic, so concurrent emitters never lock, and a slot store is a
single list assignment — a torn read on the drain side is detected by
the seq stamped inside the record. Instrumented seams: ring-channel
waits (``experimental/channel.py``, ``core/net_ring.py``), compiled-DAG
driver dispatch and executor loops (``dag/__init__.py``,
``core/worker_runtime.py``), per-microbatch pipeline spans
(``train/pipeline.py``), SPMD step phases (``train/spmd.py``), the
serve compiled lane (``serve/compiled_dispatch.py``,
``serve/replica.py``), and a process's SET-UP, one record a process or a
compiled program (:data:`SETUP_SPANS`: the driver's ``init`` / ``fit`` /
``serve.run``, a worker's boot, the jax import, the backend's bring-up, the
loop's and the engine's builds, and a compile's trace / lower / cache load /
backend compile from ``util/device_telemetry.py``'s duration listener),
which :func:`attribute_trace` cuts by innermost span.

Cross-host merge: timestamps are process-local ``time.monotonic()``
plus a per-process ``(anchor_mono, anchor_wall)`` pair captured at
import, so any record converts to wall time locally; the head then
subtracts a per-node wall-clock offset estimated over the health-prober
pings (:class:`ClockOffsetEstimator`, min-RTT midpoint — NTP's
classic estimator) before emitting one Chrome/Perfetto trace
(:func:`build_span_events` / :func:`cluster_trace`).

Span names are REGISTERED, not free-form: :func:`register_span` is a
static registration site graftlint's metrics-hygiene check indexes
(one name, one tag set, registered once), keeping tag cardinality and
the trace vocabulary reviewable.

The recorder doubles as a crash flight recorder: :func:`dump` writes
the last N seconds of spans to ``session_dir/logs/flightrec/`` and is
called from the chaos harness (``fault_injection.fire``) and the
compiled-graph attributed-death path, so every ``ActorDiedError``
comes with a timeline. Gated by the ``RAY_TPU_FLIGHT_RECORDER`` config
knob; spans shorter than ``flight_recorder_min_span_us`` (default
500 us) stop at the duration compare so microsecond-rate dispatch pays
only the clock reads — the on/off overhead is bench-gated in
BENCH_TRACE.json (``bench_core.py --trace-bench``). Spans registered
``floor_exempt`` (one per REQUEST, per engine call or per train STEP, not
per dispatch: a request's queue waits, what is left of the decode engine's
copies, the host's phases of a step) are recorded however short, so their
median is over every request or step and not over the ones that waited.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import zlib
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "ClockOffsetEstimator",
    "SETUP_SPANS",
    "attribute_trace",
    "build_span_events",
    "cluster_span_payloads",
    "cluster_trace",
    "configure",
    "cut_innermost",
    "drain",
    "dump",
    "enabled",
    "now",
    "register_span",
    "set_dump_dir",
    "set_process_label",
    "snapshot_payload",
]

# kinds stored in a record slot
KIND_SPAN = 0
KIND_INSTANT = 1

# per-process wall anchors: monotonic is the recording clock (immune to
# wall steps); the pair converts any record to wall time at export
_ANCHOR_MONO = time.monotonic()
_ANCHOR_WALL = time.time()

_DEF_LOCK = threading.Lock()
_DEFS: Dict[str, "Span"] = {}

# the ring: preallocated slots, GIL-atomic seq allocation. _hi is a
# store-only high-water mark (reading itertools.count would consume).
_DEFAULT_CAPACITY = 65536
_capacity = _DEFAULT_CAPACITY
_mask = _capacity - 1
_slots: List[Optional[tuple]] = [None] * _capacity
_seq = itertools.count()
_hi = [-1]
_drained = [0]
_on = [None]  # None = resolve lazily from config/env on first use
_proc_label = [f"pid{os.getpid()}"]
_dump_dir: List[Optional[str]] = [None]
_dump_window_s = [10.0]
# duration floor (seconds): sub-floor spans cost only the clock reads.
# Stall COUNTERS (channel.STALLS) still see every wait; instants are
# exempt (parks already imply a ms-scale spin elapsed), and so are spans
# registered floor_exempt.
_min_dur = [500e-6]


def _resolve_enabled() -> bool:
    """Lazy gate: the config may not exist yet at import time (the
    channel layer imports this module before ``init()``), so the flag
    resolves from the global Config on first use and is cached. The
    ``RAY_TPU_FLIGHT_RECORDER`` env override rides the Config field
    (Config.__post_init__ applies RAY_TPU_* per field), so the snapshot
    stays authoritative cluster-wide."""
    if _on[0] is None:
        try:
            from ray_tpu.core.config import global_config

            _on[0] = bool(global_config().flight_recorder)
        except Exception:
            _on[0] = True
    return _on[0]


def enabled() -> bool:
    on = _on[0]
    return _resolve_enabled() if on is None else on


def configure(enabled: Optional[bool] = None,
              capacity: Optional[int] = None,
              dump_window_s: Optional[float] = None,
              min_span_us: Optional[float] = None) -> None:
    """Runtime (re)configuration — also the adoption hook when a daemon
    or worker receives the cluster Config. Changing capacity rebuilds
    the ring (drops unread records; callers do this at startup)."""
    global _capacity, _mask, _slots
    if enabled is not None:
        _on[0] = bool(enabled)
    if dump_window_s is not None:
        _dump_window_s[0] = float(dump_window_s)
    if min_span_us is not None:
        _min_dur[0] = float(min_span_us) / 1e6
    if capacity is not None and capacity != _capacity:
        cap = 1
        while cap < max(1024, int(capacity)):
            cap <<= 1
        with _DEF_LOCK:
            _capacity = cap
            _mask = cap - 1
            _slots = [None] * cap
            _drained[0] = max(0, _hi[0] + 1)


def adopt_config(cfg) -> None:
    """Apply the relevant knobs of a (possibly remote) Config."""
    try:
        configure(enabled=bool(cfg.flight_recorder),
                  capacity=int(cfg.flight_recorder_events),
                  dump_window_s=float(cfg.flight_recorder_dump_window_s),
                  min_span_us=float(cfg.flight_recorder_min_span_us))
    except Exception:
        pass


def set_process_label(label: str) -> None:
    _proc_label[0] = str(label)


def set_dump_dir(session_dir: Optional[str]) -> None:
    """Arm crash dumps: faults write to <session_dir>/logs/flightrec/."""
    if session_dir:
        _dump_dir[0] = os.path.join(session_dir, "logs", "flightrec")


# bound once: skips the module-attribute lookup on every hot-path call
_mono = time.monotonic


def now(_mono=_mono) -> float:
    """Span start stamp; 0.0 when the recorder is off so a disabled
    begin/end pair costs one flag test per side."""
    on = _on[0]
    if on is None:
        on = _resolve_enabled()
    return _mono() if on else 0.0


def _record(sid: int, kind: int, t0: float, dur: float,
            tags: tuple) -> None:
    i = next(_seq)
    _slots[i & _mask] = (i, sid, kind, t0, dur, tags)
    _hi[0] = i


class Span:
    """One registered span name. ``end(t0, *tags)`` records a duration
    span closed now; ``end_at`` takes a caller-measured duration (the
    ring-wait paths time their stall anyway for the stall counters);
    ``instant`` records a point event."""

    __slots__ = ("name", "tag_keys", "sid", "floor_exempt")

    def __init__(self, name: str, tag_keys: Tuple[str, ...], sid: int,
                 floor_exempt: bool = False):
        self.name = name
        self.tag_keys = tag_keys
        self.sid = sid
        self.floor_exempt = floor_exempt

    def end(self, t0: float, *tags, _mono=_mono) -> None:
        # _record() inlined and the clock bound as a default: this and
        # end_at are THE hot path against the <=3% bench-gated budget.
        # Sub-floor spans stop at the duration compare: at microsecond
        # dispatch rates the clock reads are all the recorder may cost.
        if t0 and _on[0]:
            dur = _mono() - t0
            if dur >= _min_dur[0] or self.floor_exempt:
                i = next(_seq)
                _slots[i & _mask] = (i, self.sid, KIND_SPAN, t0, dur,
                                     tags)
                _hi[0] = i

    def end_at(self, t0: float, dur: float, *tags) -> None:
        on = _on[0]
        if on is None:
            on = _resolve_enabled()
        if on and (dur >= _min_dur[0] or self.floor_exempt):
            i = next(_seq)
            _slots[i & _mask] = (i, self.sid, KIND_SPAN, t0, dur, tags)
            _hi[0] = i

    def instant(self, *tags) -> None:
        on = _on[0]
        if on is None:
            on = _resolve_enabled()
        if on:
            _record(self.sid, KIND_INSTANT, time.monotonic(), 0.0, tags)


def _sid_for(name: str) -> int:
    """Stable span id derived from the NAME, identical in every
    process. Registration order must not matter: actor classes can be
    cloudpickled by value, shipping the defining module's Span objects
    inside method globals — an order-based sid minted in the driver
    would collide with a different name in the executing worker's
    table. crc32 of the name is order-free; :func:`register_span`
    rejects the (vanishingly unlikely) cross-name collision."""
    return zlib.crc32(name.encode())


def register_span(name: str, tag_keys: Tuple[str, ...] = (),
                  floor_exempt: bool = False) -> Span:
    """Register one span name with its (fixed) tag key set. Idempotent
    for an identical re-registration (module reload); a conflicting tag
    set raises — one name, one tag set, registered once (enforced
    statically by graftlint metrics-hygiene as well). ``floor_exempt``
    records the span however short it is: for spans that occur once per
    request, where dropping the short ones would bias every statistic
    toward the requests that waited."""
    tag_keys = tuple(tag_keys)
    with _DEF_LOCK:
        have = _DEFS.get(name)
        if have is not None:
            if have.tag_keys != tag_keys \
                    or have.floor_exempt != floor_exempt:
                raise ValueError(
                    f"span {name!r} already registered with tag_keys="
                    f"{have.tag_keys!r}, floor_exempt="
                    f"{have.floor_exempt} (got {tag_keys!r}, "
                    f"{floor_exempt})")
            return have
        sid = _sid_for(name)
        for sp in _DEFS.values():
            if sp.sid == sid:
                raise ValueError(
                    f"span id collision: {name!r} vs {sp.name!r}")
        sp = Span(name, tag_keys, sid, floor_exempt)
        _DEFS[name] = sp
        return sp


# --------------------------------------------------------------------------- #
# Drain / snapshot / payloads
# --------------------------------------------------------------------------- #


def _collect(lo: int, hi: int) -> List[tuple]:
    out = []
    for i in range(max(lo, hi - _mask), hi + 1):
        rec = _slots[i & _mask]
        if rec is not None and rec[0] == i:  # torn/overwritten guard
            out.append(rec)
    return out


def _names_table() -> Dict[int, dict]:
    with _DEF_LOCK:
        return {sp.sid: {"name": sp.name, "tag_keys": list(sp.tag_keys)}
                for sp in _DEFS.values()}


def _payload(events: List[tuple]) -> dict:
    return {
        "pid": os.getpid(),
        "proc": _proc_label[0],
        "anchor_mono": _ANCHOR_MONO,
        "anchor_wall": _ANCHOR_WALL,
        "names": _names_table(),
        "events": [list(r) for r in events],
    }


def drain() -> Optional[dict]:
    """Consume records since the last drain (the worker/daemon report
    path). None when nothing new."""
    hi = _hi[0]
    if hi < _drained[0]:
        return None
    events = _collect(_drained[0], hi)
    _drained[0] = hi + 1
    if not events:
        return None
    return _payload(events)


def snapshot_payload(window_s: Optional[float] = None) -> dict:
    """Non-consuming view of everything still in the ring (the export
    path for the local process); optionally clipped to the last
    ``window_s`` seconds."""
    events = _collect(0, _hi[0])
    if window_s is not None:
        cutoff = time.monotonic() - window_s
        events = [r for r in events if r[3] + r[4] >= cutoff]
    return _payload(events)


def snapshot_payload_since(seq: int) -> dict:
    """Non-consuming view of local records with seq >= ``seq``. The
    incremental-fold path: a periodic reader (the health monitor)
    remembers the highest seq it folded and pays O(new records) per
    tick instead of O(ring)."""
    return _payload(_collect(max(0, seq), _hi[0]))


def reset_for_tests() -> None:
    global _seq
    _seq = itertools.count()
    _hi[0] = -1
    _drained[0] = 0
    for i in range(len(_slots)):
        _slots[i] = None


# --------------------------------------------------------------------------- #
# Crash flight recorder
# --------------------------------------------------------------------------- #


def dump(reason: str, window_s: Optional[float] = None) -> Optional[str]:
    """Write the last N seconds of local spans to
    ``<session_dir>/logs/flightrec/`` (armed via :func:`set_dump_dir`).
    Best-effort by contract: the callers are death paths."""
    d = _dump_dir[0]
    if d is None or not enabled():
        return None
    try:
        os.makedirs(d, exist_ok=True)
        payload = snapshot_payload(window_s or _dump_window_s[0])
        payload["reason"] = reason
        payload["wall_ts"] = time.time()
        path = os.path.join(
            d, f"{_proc_label[0].replace(':', '_').replace('/', '_')}"
               f"-{os.getpid()}-{int(time.time() * 1000)}.json")
        with open(path, "w") as f:
            json.dump(payload, f)
        return path
    except Exception:
        return None


# --------------------------------------------------------------------------- #
# Clock-offset estimation (head side, over the health-prober pings)
# --------------------------------------------------------------------------- #


class ClockOffsetEstimator:
    """Min-RTT wall-clock offset of one remote node against this
    process. Each ping round contributes ``offset = remote_wall -
    (send_wall + recv_wall) / 2`` with its RTT; the estimate is the
    offset of the minimum-RTT sample in a sliding window (asymmetric
    queueing inflates RTT, so the tightest round is the most trusted —
    its error is bounded by rtt/2). Re-estimated continuously: a
    stepped/drifting remote clock ages out with the window."""

    def __init__(self, window: int = 64):
        self._samples: deque = deque(maxlen=max(2, int(window)))

    def add(self, offset_s: float, rtt_s: float) -> None:
        self._samples.append((float(offset_s), max(0.0, float(rtt_s))))

    def add_ping(self, send_wall: float, recv_wall: float,
                 remote_wall: float) -> None:
        self.add(remote_wall - (send_wall + recv_wall) / 2.0,
                 recv_wall - send_wall)

    def offset(self) -> float:
        if not self._samples:
            return 0.0
        return min(self._samples, key=lambda s: s[1])[0]

    def rtt(self) -> Optional[float]:
        if not self._samples:
            return None
        return min(s[1] for s in self._samples)

    def error_bound(self) -> Optional[float]:
        """Half the best RTT: the classic bound on the midpoint
        estimator's error under asymmetric path delay."""
        r = self.rtt()
        return None if r is None else r / 2.0


# --------------------------------------------------------------------------- #
# Trace export: payloads -> Chrome/Perfetto events -> attribution
# --------------------------------------------------------------------------- #


def build_span_events(payloads: List[dict]) -> List[Dict[str, Any]]:
    """Chrome-trace events from collected span payloads. Each payload
    carries its process anchors plus ``source`` / ``node_hex`` /
    ``offset_s`` stamped by the collector; the per-node offset merges
    every clock onto the head's wall timeline. Tracks: one pid per
    node, one tid per (process, span-or-channel)."""
    events: List[Dict[str, Any]] = []
    for p in payloads:
        names = {int(k): v for k, v in (p.get("names") or {}).items()}
        base = (p.get("anchor_wall", 0.0) - p.get("anchor_mono", 0.0)
                - p.get("offset_s", 0.0))
        pid = f"node:{(p.get('node_hex') or 'head')[:6]}"
        proc = p.get("proc") or f"pid{p.get('pid', '?')}"
        for rec in p.get("events") or ():
            seq, sid, kind, t0, dur, tags = rec
            d = names.get(sid)
            if d is None:
                continue
            name = d["name"]
            args = dict(zip(d.get("tag_keys") or (), tags or ()))
            # channels get their own track (per-channel lanes make
            # backpressure visible); everything else tracks per span
            # name within the process
            chan = args.get("channel")
            tid = (f"{proc} {name} {chan}" if chan
                   else f"{proc} {name}")
            ev = {"cat": "span", "name": name,
                  "ts": (t0 + base) * 1e6,
                  "pid": pid, "tid": tid,
                  "args": dict(args, source=p.get("source", proc))}
            if kind == KIND_INSTANT:
                ev.update({"ph": "i", "s": "t"})
            else:
                ev.update({"ph": "X", "dur": max(0.0, dur * 1e6)})
            events.append(ev)
    return events


def cluster_span_payloads(head,
                          since: Optional[Dict[str, int]] = None
                          ) -> List[dict]:
    """Head-side collection: the local (driver/head) snapshot plus every
    buffered worker/daemon payload, each stamped with its node's
    estimated clock offset (0 for head-host sources — CLOCK_MONOTONIC
    differs per process but the wall anchors already line same-host
    processes up).

    ``since`` maps source label -> highest seq already consumed; when
    given, payloads carry only records past each cursor (seqs are
    monotonic per recording process, and retained worker chunks are
    drained batches in seq order), so a periodic caller pays for new
    records only."""
    head_hex = getattr(getattr(head, "head_node", None), "hex", None)
    offsets: Dict[str, float] = {}
    for proxy in list(getattr(head, "nodes", {}).values()):
        est = getattr(proxy, "clock_est", None)
        hx = getattr(proxy, "hex", None)
        if est is not None and hx:
            offsets[hx] = est.offset()
    out: List[dict] = []
    local_src = f"head:{_proc_label[0]}"
    local = snapshot_payload() if since is None \
        else snapshot_payload_since(since.get(local_src, -1) + 1)
    local.update({"source": local_src,
                  "node_hex": head_hex, "offset_s": 0.0})
    out.append(local)
    for source, chunks in list(getattr(head, "flight_spans",
                                       {}).items()):
        cur = since.get(source, -1) if since is not None else -1
        for p in list(chunks):
            evs = p.get("events") or []
            if cur >= 0:
                if not evs or evs[-1][0] <= cur:
                    continue  # chunk fully consumed (records seq-sorted)
                if evs[0][0] <= cur:
                    p = dict(p, events=[r for r in evs if r[0] > cur])
            hx = p.get("node_hex")
            q = dict(p)
            q["source"] = source
            q["offset_s"] = offsets.get(hx, 0.0) \
                if hx and hx != head_hex else 0.0
            out.append(q)
    return out


def cluster_trace(head, include_tasks: bool = True) -> List[Dict[str, Any]]:
    """ONE merged Chrome-trace event list for the whole cluster: task
    slices via the same ``util.timeline`` builder ``state.timeline()``
    uses (single source of truth for task events) plus the span plane."""
    from ray_tpu.util.timeline import _build_chrome_trace, raw_events_for_head

    events: List[Dict[str, Any]] = []
    if include_tasks:
        try:
            events.extend(_build_chrome_trace(raw_events_for_head(head)))
        except Exception:
            pass
    events.extend(build_span_events(cluster_span_payloads(head)))
    return events


# span-name groups the attribution folds over
_PIPE_BUSY = ("pipe.fwd", "pipe.bwd", "pipe.loss_bwd")
_RING_WAIT = ("ring.wait_read", "ring.wait_write")
# a request's way to its first token inside a decode replica, in order:
# (report key, span, human label). The three engine parts lie inside
# serve.prefill.
_TTFT_PARTS = (
    ("ingress", "dag.stream_ingress", "in the lane's ring"),
    ("sched_wait", "serve.sched_wait", "scheduler wait"),
    ("prefill", "serve.prefill", "prefill"),
    ("prefill_program", "engine.prefill_program", "  device program"),
    ("prefill_kv", "engine.prefill_kv", "  keys/values to pages"),
    ("prefill_logits", "engine.prefill_logits", "  logits to host"),
    ("first_token_hold", "serve.first_token_hold", "first token held"),
)


def _serving_attribution(by_name: Dict[str, List[dict]]
                         ) -> Dict[str, Dict[str, Any]]:
    """Per decode deployment: median and p95 (ms) of each stop on a
    request's way to its first token, and tokens per decode step. The
    scheduler's spans name their deployment; the stream loop's and the
    engine's do not, and go to the deployment whose scheduler recorded
    in the same process (a replica serves one)."""
    dep_of: Dict[Any, str] = {}
    for name in ("serve.sched_wait", "serve.prefill", "serve.decode_step"):
        for ev in by_name.get(name, ()):
            args = ev.get("args") or {}
            dep_of.setdefault(args.get("source"),
                              str(args.get("deployment", "")))
    out: Dict[str, Dict[str, Any]] = {}
    for key, name, _label in _TTFT_PARTS:
        durs: Dict[str, List[float]] = {}
        for ev in by_name.get(name, ()):
            args = ev.get("args") or {}
            dep = args.get("deployment")
            if dep is None:
                dep = dep_of.get(args.get("source"))
            if dep is not None:
                durs.setdefault(str(dep), []).append(
                    ev.get("dur", 0.0) / 1e3)
        for dep, ms in durs.items():
            ms.sort()
            out.setdefault(dep, {})[key] = {
                "n": len(ms),
                "p50_ms": round(ms[(len(ms) - 1) // 2], 3),
                "p95_ms": round(ms[min(len(ms) - 1,
                                       int(0.95 * len(ms)))], 3)}
    for ev in by_name.get("serve.decode_step", ()):
        args = ev.get("args") or {}
        rec = out.setdefault(str(args.get("deployment", "")), {})
        rec["decode_steps"] = rec.get("decode_steps", 0) + 1
        rec["decode_tokens"] = rec.get("decode_tokens", 0) + int(
            args.get("tokens") or 0)
    return out


# the host's phases of an SPMD train step, in the loop's order:
# (report key, span, human label)
_SPMD_PHASES = (
    ("ingest_wait", "spmd.ingest_wait", "batch wait"),
    ("dispatch", "spmd.dispatch", "dispatch"),
    ("ready_wait", "spmd.ready_wait", "device wait"),
    ("fetch", "spmd.fetch", "fetch"),
    ("report", "spmd.report", "report"),
)


def _slowest_spmd_step(by_name: Dict[str, List[dict]],
                       instants: List[dict]) -> Optional[Dict[str, Any]]:
    """The SPMD loop's slowest step by wall, joined on ``step``: its
    number, its phases, its ``moe.*`` values. The phases are one thread's
    work and disjoint, so every millisecond of the loop belongs to one
    step's phases, and a step's wall is their sum: about a step's time
    while the host keeps ahead (``ready_wait`` is what the rest leaves of
    it). Long ``ready_wait`` with the rest normal is the device's, a long
    ``dispatch`` a retrace, a compile or a full queue, a long ``report``
    the runtime's; the step AFTER a stalled one was ready before the host
    came to wait for it, and shows a ``ready_wait`` near zero. A process
    may run the loop again and repeat its step numbers: a step number that
    falls back starts another run."""
    marks = [(key, ev) for key, name, _label in _SPMD_PHASES
             for ev in by_name.get(name, ())]
    marks += [(None, ev) for ev in instants]
    runs: Dict[Any, List[int]] = {}   # source -> [run, highest step in it]
    found: Dict[tuple, dict] = {}
    for key, ev in sorted(marks, key=lambda m: m[1]["ts"]):
        args = ev.get("args") or {}
        step, source = args.get("step"), args.get("source")
        if step is None:
            continue  # a trace from before the spans carried ``step``
        run = runs.setdefault(source, [0, step])
        if step < run[1] - 1 or step == 1 < run[1]:
            run[:] = [run[0] + 1, step]
        run[1] = max(run[1], step)
        rec = found.setdefault((source, run[0], step),
                               {"phases": {}, "router": {}})
        if key is None:
            rec["router"][ev["name"]] = float(args.get("value", 0.0))
        else:
            rec["phases"][key] = (rec["phases"].get(key, 0.0)
                                  + ev.get("dur", 0.0) / 1e3)
    if not found:
        return None
    (source, _run, step), rec = max(
        found.items(), key=lambda kv: sum(kv[1]["phases"].values()))
    out = {"step": int(step),
           "wall_ms": round(sum(rec["phases"].values()), 3),
           "phases_ms": {k: round(v, 3) for k, v in rec["phases"].items()}}
    if source is not None:
        out["source"] = source
    if rec["router"]:
        out["router"] = rec["router"]
    return out


# A process's way from its start to its first productive span, once a process
# or once a compiled program each. The four jax.* are the duration listener's
# (util/device_telemetry.py); the two engine.* carry a warm-up call's first
# run around its ``xla.compile``.
_RUN_CARRIERS = ("engine.prefill_program", "engine.decode_program")
SETUP_SPANS = (
    "runtime.init", "trainer.place", "serve.deploy", "dag.lane_build",
    "worker.boot", "jax.import", "jax.backend_init", "spmd.build",
    "spmd.init_state", "engine.build", "engine.weights", "engine.stores",
    "spmd.compile", "xla.compile", *_RUN_CARRIERS,
    "jax.trace", "jax.lower", "jax.cache_load", "jax.backend_compile")
# what the block calls a span whose line is what the spans inside it leave
_SETUP_LABELS = {
    "engine.build": "engine.build (rest)",
    "xla.compile": "xla.compile (rest)",
    "spmd.compile": "first step's run",
    "engine.prefill_program": "first runs: prefill",
    "engine.decode_program": "first runs: decode",
    "jax.backend_compile": "compiled (less loads)",
}
# a process that records none of these brought nothing up: no block
_SETUP_ONLY = frozenset(SETUP_SPANS) - {"spmd.compile", "xla.compile",
                                        *_RUN_CARRIERS}
# where set-up ends: the start of the first of these (a train step that did
# not compile, a request's prefill), else of the first request in the lane
_PRODUCTIVE = (("spmd.compute", "serve.prefill"), ("dag.stream_ingress",))
# a program's row: the span whose cut seconds go to which column
_PROGRAM_COLUMNS = {
    "jax.trace": "trace_s", "jax.lower": "lower_s",
    "jax.cache_load": "load_s", "jax.backend_compile": "compiled_s",
    "spmd.compile": "first_run_s",
    **{name: "first_run_s" for name in _RUN_CARRIERS}}


def cut_innermost(spans, lo: float, hi: float) -> Dict[str, float]:
    """``[lo, hi]`` cut by INNERMOST span: every instant goes to the span
    covering it that started last (of two that started together, the one
    that ends first), an instant nobody spans to ``"unattributed"``.
    ``spans`` are ``(name, start, end)`` on one clock, in any unit; the
    result, ``{name: length}``, sums to ``hi - lo``."""
    import heapq

    todo = sorted(((max(s, lo), min(e, hi), name) for name, s, e in spans
                   if e > lo and s < hi and e > s), key=lambda t: t[:2])
    edges = sorted({lo, hi, *(s for s, _e, _n in todo),
                    *(e for _s, e, _n in todo)})
    out: Dict[str, float] = {}
    open_: List[tuple] = []  # (-start, end, k): the top is the innermost
    k = 0
    for a, b in zip(edges, edges[1:]):
        while k < len(todo) and todo[k][0] <= a:
            heapq.heappush(open_, (-todo[k][0], todo[k][1], k))
            k += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        name = todo[open_[0][2]][2] if open_ else "unattributed"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def _setup_attribution(events: List[Dict[str, Any]]
                       ) -> Dict[str, Dict[str, Any]]:
    """Per source process that recorded a set-up span: the stretch from its
    first record to its first productive span (none: to its last set-up
    span's end), cut by innermost span of :data:`SETUP_SPANS` into seconds
    that sum to the stretch (``parts_s``), and the same cut by compiled
    program (``programs``: how many, and the seconds of tracing, lowering,
    loading from the persistent cache, compiling and of the first run).

    The listener that records the ``jax.*`` spans is told no program, so
    each is put down to the tightest ``xla.compile`` it lies in (its
    ``program``), else to the ``spmd.compile`` it lies in (``"first
    step"``), else to ``"other"`` (the weights' jit, a store's zeros, a
    caller's own programs). ``compiled_s`` is ``jax.backend_compile`` less
    the loads nested in it (in this jax a load runs inside it); a trace
    nested in a trace (a jit called under a jit) counts once. A first run is
    what the call AROUND a compile leaves when everything inside it is taken
    out: ``spmd.compile``, or the engine's call around an ``xla.compile``."""
    by_source: Dict[Any, List[dict]] = {}
    for ev in events:
        if ev.get("ph") == "X" and ev.get("cat") == "span":
            by_source.setdefault((ev.get("args") or {}).get("source"),
                                 []).append(ev)
    slack = 2e3  # us: a jax.* span's start is its end less a duration

    def end(ev) -> float:
        return ev["ts"] + ev.get("dur", 0.0)

    def tightest(ev, outers):
        return min((o for o in outers if o["ts"] - slack <= ev["ts"]
                    and end(ev) <= end(o) + slack),
                   key=lambda o: o.get("dur", 0.0), default=None)

    def program_of(ev) -> str:
        return str((ev.get("args") or {}).get("program", "?"))

    out: Dict[str, Dict[str, Any]] = {}
    for source, evs in by_source.items():
        if not any(ev["name"] in _SETUP_ONLY for ev in evs):
            continue
        lo = min(ev["ts"] for ev in evs)
        hi, until = None, None
        for names in _PRODUCTIVE:
            first = min((ev for ev in evs if ev["name"] in names),
                        key=lambda ev: ev["ts"], default=None)
            if first is not None:
                hi, until = first["ts"], first["name"]
                break
        if hi is None:
            hi = max(end(ev) for ev in evs if ev["name"] in _SETUP_ONLY)
        inside = [ev for ev in evs if ev["name"] in SETUP_SPANS
                  and ev["ts"] < hi and end(ev) > lo]
        compiles = [ev for ev in inside if ev["name"] == "xla.compile"]
        steps = [ev for ev in inside if ev["name"] == "spmd.compile"]
        rows: Dict[str, Dict[str, float]] = {}

        def row(program: str) -> Dict[str, float]:
            return rows.setdefault(program, dict(
                {"programs": 0},
                **{key: 0.0 for key in _PROGRAM_COLUMNS.values()}))

        spans = []  # ((span name, its program or None), start, end)
        for ev in inside:
            name, program = ev["name"], None
            if name == "xla.compile":
                row(program_of(ev))["programs"] += 1
            elif name.startswith("jax."):
                holder = tightest(ev, compiles)
                program = (program_of(holder) if holder is not None else
                           "first step" if tightest(ev, steps) is not None
                           else "other")
                if program == "other" and name == "jax.backend_compile":
                    row(program)["programs"] += 1
            elif name in _PROGRAM_COLUMNS:  # a call around a compile
                held = [c for c in compiles if tightest(c, [ev]) is not None]
                if held:
                    program = program_of(held[0])
                elif name == "spmd.compile":  # the observatory is off
                    program = "first step"
                    row(program)["programs"] += 1
            spans.append(((name, program), ev["ts"], end(ev)))
        parts: Dict[str, float] = {}
        for key, v in cut_innermost(spans, lo, hi).items():
            name, program = key if isinstance(key, tuple) else (key, None)
            parts[name] = parts.get(name, 0.0) + v / 1e6
            if program is not None and name in _PROGRAM_COLUMNS:
                row(program)[_PROGRAM_COLUMNS[name]] += v / 1e6
        out[str(source)] = {
            "stretch_s": round((hi - lo) / 1e6, 6), "until": until,
            "parts_s": {name: round(v, 6) for name, v in parts.items()},
            "programs": {
                program: {key: int(v) if key == "programs" else round(v, 6)
                          for key, v in rec.items()}
                for program, rec in sorted(rows.items())}}
    return out


def attribute_trace(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold a merged trace into a per-step budget: where did the step
    time go. Pipeline busy/bubble mirrors ``pipeline_stats()`` exactly
    — busy is the sum of fwd/bwd/loss_bwd span durations inside the
    stepped window, wall is the ``pipe.step`` driver spans, stages are
    the distinct ``stage`` tags — so the reported bubble_fraction is
    the *explained* version of the measured one."""
    by_name: Dict[str, List[dict]] = {}
    for ev in events:
        if ev.get("ph") == "X" and ev.get("cat") == "span":
            by_name.setdefault(ev["name"], []).append(ev)

    def total_s(names) -> float:
        return sum(ev.get("dur", 0.0) for n in names
                   for ev in by_name.get(n, ())) / 1e6

    steps = by_name.get("pipe.step", [])
    wall_s = sum(ev.get("dur", 0.0) for ev in steps) / 1e6
    # clip stage busy to the stepped window: warmup/compile microbatches
    # run before the first pipe.step begins and are not in the stats
    t_lo = min((ev["ts"] for ev in steps), default=None)
    busy_s = 0.0
    per_stage: Dict[str, float] = {}
    for n in _PIPE_BUSY:
        for ev in by_name.get(n, ()):
            if t_lo is not None and ev["ts"] < t_lo - 1e3:
                continue
            d = ev.get("dur", 0.0) / 1e6
            busy_s += d
            stage = str((ev.get("args") or {}).get("stage", "?"))
            per_stage[stage] = per_stage.get(stage, 0.0) + d
    k = len([s for s in per_stage if s != "?"]) or len(per_stage) or 1
    eff = busy_s / (k * wall_s) if wall_s > 0 else 0.0

    ring_stall_s = total_s(_RING_WAIT)
    ingest_s = total_s(("spmd.ingest_wait",))
    spmd_compute_s = total_s(("spmd.compute",))
    exec_s = total_s(("dag.exec",))
    serve_s = total_s(("serve.batch_drain",))
    compile_s = total_s(("spmd.compile",))
    ckpt_s = total_s(("ckpt.save", "ckpt.restore"))
    # per-program XLA compile rows (observatory xla.compile spans carry a
    # `program` tag) — where the compile seconds went, by executable
    xla_compile: Dict[str, Dict[str, float]] = {}
    for ev in by_name.get("xla.compile", ()):
        prog = str((ev.get("args") or {}).get("program", "?"))
        rec = xla_compile.setdefault(prog, {"compiles": 0, "compile_s": 0.0})
        rec["compiles"] += 1
        rec["compile_s"] += ev.get("dur", 0.0) / 1e6
    # all but the wait for a batch, which has its line: ingest_wait_s
    spmd_phases = {key: total_s((name,))
                   for key, name, _label in _SPMD_PHASES[1:]}
    denom = wall_s or (spmd_compute_s + ingest_s) or None
    report: Dict[str, Any] = {
        "step_wall_s": round(wall_s, 6),
        "steps": len(steps),
        "num_stages": k if per_stage else 0,
        "pipeline_busy_s": round(busy_s, 6),
        "per_stage_busy_s": {s: round(v, 6)
                             for s, v in sorted(per_stage.items())},
        "pipeline_efficiency": round(eff, 4) if per_stage else None,
        "bubble_fraction": round(1.0 - eff, 4) if per_stage else None,
        "ring_stall_s": round(ring_stall_s, 6),
        "ingest_wait_s": round(ingest_s, 6),
        "spmd_compute_s": round(spmd_compute_s, 6),
        **{f"spmd_{key}_s": round(v, 6) for key, v in spmd_phases.items()},
        "dag_exec_s": round(exec_s, 6),
        "serve_batch_s": round(serve_s, 6),
        "compile_s": round(compile_s, 6),
        "checkpoint_s": round(ckpt_s, 6),
        "xla_compile_s": {
            p: {"compiles": int(r["compiles"]),
                "compile_s": round(r["compile_s"], 6)}
            for p, r in sorted(xla_compile.items())},
    }
    if denom:
        report["compute_pct"] = round(100.0 * eff, 2) if per_stage else \
            round(100.0 * spmd_compute_s / denom, 2)
        report["ring_stall_pct"] = round(
            100.0 * ring_stall_s / (k * denom), 2)
        report["ingest_pct"] = round(100.0 * ingest_s / denom, 2)
    serving = _serving_attribution(by_name)
    if serving:
        report["serving"] = serving
    setup = _setup_attribution(events)
    if setup:
        report["setup"] = setup
    # a routed model's router (train/spmd.py: one instant a report)
    instants = [ev for ev in events if ev.get("ph") == "i"
                and str(ev.get("name", "")).startswith(("moe.", "mtp."))]
    router: Dict[str, List[float]] = {}
    for ev in instants:
        router.setdefault(ev["name"], []).append(
            float((ev.get("args") or {}).get("value", 0.0)))
    slowest = _slowest_spmd_step(by_name, instants)
    if slowest:
        report["slowest_step"] = slowest
    if router:
        report["router"] = {
            name: {"n": len(v), "last": v[-1], "max": max(v)}
            for name, v in sorted(router.items())}
    return report


def format_attribution(report: Dict[str, Any]) -> str:
    """Human-readable ``timeline --attribute`` rendering: the step budget,
    a block a decode deployment ("where did the time to first token go"),
    a block a process that brought something up ("set-up : N s on
    <source>": the stretch to its first productive span cut by innermost
    span, a line a part, largest first, then a line a program), the
    router's scalars. The step budget's "compile (1st step)" and "xla"
    rows are totals over the whole trace; a set-up block's lines are a cut
    of its stretch, each second in one line."""
    lines = ["where did my step time go", "-" * 26]
    if report.get("steps"):
        lines.append(f"steps observed     : {report['steps']} "
                     f"({report['step_wall_s']:.4f}s wall)")
    if report.get("bubble_fraction") is not None:
        lines.append(f"pipeline stages    : {report['num_stages']}")
        lines.append(f"pipeline busy      : {report['pipeline_busy_s']:.4f}s"
                     f"  (efficiency {report['pipeline_efficiency']:.2%})")
        lines.append(f"bubble fraction    : {report['bubble_fraction']:.4f}")
        for s, v in report.get("per_stage_busy_s", {}).items():
            lines.append(f"  stage {s:<12}: {v:.4f}s busy")
    for key, label in (("compute_pct", "compute %"),
                       ("ring_stall_pct", "ring-stall %"),
                       ("ingest_pct", "ingest %")):
        if report.get(key) is not None:
            lines.append(f"{label:<19}: {report[key]:.2f}%")
    lines.append(f"ring stall         : {report['ring_stall_s']:.4f}s")
    if report.get("ingest_wait_s"):
        lines.append(f"ingest wait        : {report['ingest_wait_s']:.4f}s")
    if report.get("spmd_compute_s"):
        lines.append(f"spmd compute       : {report['spmd_compute_s']:.4f}s")
    for key, _name, label in _SPMD_PHASES[1:]:
        if report.get(f"spmd_{key}_s"):
            lines.append(f"  {label:<17}: {report[f'spmd_{key}_s']:.4f}s")
    slow = report.get("slowest_step")
    if slow:
        lines.append(
            f"slowest step       : {slow['step']}, {slow['wall_ms']:.3f} ms "
            f"wall" + (f" on {slow['source']}" if slow.get("source") else ""))
        lines.append("  " + ", ".join(
            f"{label} {slow['phases_ms'][key]:.3f} ms"
            for key, _name, label in _SPMD_PHASES
            if key in slow["phases_ms"]))
        if slow.get("router"):
            lines.append("  " + ", ".join(
                f"{name} {v:.6g}" for name, v in slow["router"].items()))
    if report.get("compile_s"):
        lines.append(f"compile (1st step) : {report['compile_s']:.4f}s")
    for prog, rec in (report.get("xla_compile_s") or {}).items():
        lines.append(f"  xla {prog:<14}: {rec['compile_s']:.4f}s "
                     f"({rec['compiles']} compile(s))")
    if report.get("checkpoint_s"):
        lines.append(f"checkpoint io      : {report['checkpoint_s']:.4f}s")
    if report.get("dag_exec_s"):
        lines.append(f"dag executor busy  : {report['dag_exec_s']:.4f}s")
    if report.get("serve_batch_s"):
        lines.append(f"serve batch drain  : {report['serve_batch_s']:.4f}s")
    for dep, rec in sorted((report.get("serving") or {}).items()):
        lines += ["", f"where did the time to first token go: {dep}",
                  "-" * 38]
        for key, _name, label in _TTFT_PARTS:
            part = rec.get(key)
            if part:
                lines.append(f"{label:<24}: p50 {part['p50_ms']:9.3f} ms"
                             f"  p95 {part['p95_ms']:9.3f} ms"
                             f"  (n={part['n']})")
        if rec.get("decode_steps"):
            lines.append(
                f"{'decode steps':<24}: {rec['decode_steps']}, "
                f"{rec['decode_tokens'] / rec['decode_steps']:.2f} "
                f"tokens a step")
    for source, rec in sorted((report.get("setup") or {}).items()):
        head = (f"set-up : {rec['stretch_s']:.3f} s on {source}"
                + (f" (to its first {rec['until']})" if rec.get("until")
                   else " (to its last set-up span's end)"))
        lines += ["", head, "-" * len(head)]
        for name, v in sorted(rec["parts_s"].items(),
                              key=lambda kv: -kv[1]):
            lines.append(f"{_SETUP_LABELS.get(name, name):<24}: {v:10.3f} s")
        for program, row in rec["programs"].items():
            lines.append(
                f"  {program:<22}: {row['programs']} program(s), trace "
                f"{row['trace_s']:.3f} lower {row['lower_s']:.3f} load "
                f"{row['load_s']:.3f} compiled {row['compiled_s']:.3f} "
                f"first run {row['first_run_s']:.3f} s")
    if report.get("router"):
        lines += ["", "the router (routed experts)", "-" * 27]
        for name, rec in report["router"].items():
            lines.append(f"{name:<24}: last {rec['last']:.6g}  "
                         f"max {rec['max']:.6g}  (n={rec['n']})")
    return "\n".join(lines)
