"""TPU/JAX device telemetry: HBM gauges + XLA compile activity counters.

The reference never had TPU-native signals; this collector publishes, per
process (worker / node daemon / driver):

- ``ray_tpu_device_bytes_in_use`` / ``ray_tpu_device_peak_bytes_in_use``
  gauges from ``device.memory_stats()`` with node/device tags, and
- ``ray_tpu_jax_events_total`` counters plus
  ``ray_tpu_jax_event_duration_seconds`` histograms from ``jax.monitoring``
  listeners (JIT compilations, compilation-cache hits/misses, ...).

The listener of durations also records four of them as flight-recorder
spans (``jax.trace``, ``jax.lower``, ``jax.cache_load``,
``jax.backend_compile``), and this module stamps ``jax.import`` and
``jax.backend_init``: a process's set-up, which ``python -m ray_tpu timeline
--attribute`` cuts by them.

Everything feeds the existing worker->head metrics channel (the local
registry flushed by ``start_report_thread``), so the head's /metrics and
/api/metrics/history expose cluster-wide device state with zero new wires.

Laziness is load-bearing twice over. The collector never imports jax
itself: it waits until user code has (``"jax" in sys.modules``), so
CPU-only workers that never touch jax pay nothing. And it never
initialises a backend: on libtpu the first process to do that takes every
chip it can see and keeps them until it exits, so a telemetry thread in
the driver, the node daemon or a pooled worker calling ``jax.devices()``
would take the chip from the worker the runtime bound to it. Devices are
read only once user code has brought a backend up
(:func:`jax_with_backend`). Event listeners are nonetheless installed at
jax-import time (``observe_jax_import``'s meta-path hook), not on the first
collection tick: compiles that fire between import and the first tick —
the first train step's JIT, typically — would otherwise never be counted.
"""

from __future__ import annotations

import sys
import threading
from typing import List, Optional

from ray_tpu.util import flight_recorder as _fr
from ray_tpu.util.metrics import Counter, Gauge, Histogram, registry

_BYTES_IN_USE = Gauge("ray_tpu_device_bytes_in_use",
                      "accelerator memory currently allocated (bytes)")
_PEAK_BYTES = Gauge("ray_tpu_device_peak_bytes_in_use",
                    "peak accelerator memory allocated (bytes)")
_JAX_EVENTS = Counter("ray_tpu_jax_events_total",
                      "jax.monitoring events (compilations, cache misses)")
_JAX_DURATIONS = Histogram(
    "ray_tpu_jax_event_duration_seconds",
    "jax.monitoring event durations (e.g. JIT compile time)",
    boundaries=[0.01, 0.1, 1, 10, 60])

# Set-up's spans that this module stamps, one record a process or a compiled
# program (``timeline --attribute`` cuts a process's set-up by them). The four
# duration events are spans too: jax tells the listener a duration when the
# work has ENDED, so the span ends at the listener's call and began
# ``duration`` before it. They nest inside ``xla.compile{program}`` /
# ``spmd.compile{step}``, which is how a trace, a lowering or a load is put
# down to its program. ``backend_compile_duration`` covers a load from the
# persistent cache too (``compile_or_get_cached`` runs inside it), so
# ``jax.cache_load`` nests in ``jax.backend_compile``.
_sp_jax_import = _fr.register_span("jax.import")
_sp_backend_init = _fr.register_span("jax.backend_init")
_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration":
        _fr.register_span("jax.trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        _fr.register_span("jax.lower"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        _fr.register_span("jax.cache_load"),
    "/jax/core/compile/backend_compile_duration":
        _fr.register_span("jax.backend_compile"),
}

_listener_lock = threading.Lock()
_listeners_installed = False

# node hex prefix stamped onto the jax event series: counters SUM across
# sources at the head, so without this tag two workers' compile counts
# merge into one anonymous series
_node_tag = [""]


def set_node_tag(node_hex: str) -> None:
    if node_hex:
        _node_tag[0] = node_hex[:8]


def _event_tags(event: str) -> dict:
    tags = {"event": str(event)}
    if _node_tag[0]:
        tags["node"] = _node_tag[0]
    return tags


def _on_jax_event(event: str, *args, **kwargs) -> None:
    try:
        _JAX_EVENTS.inc(1.0, tags=_event_tags(event))
    except Exception:
        pass


def _on_jax_event_duration(event: str, duration: float,
                           *args, **kwargs) -> None:
    try:
        _JAX_DURATIONS.observe(float(duration), tags=_event_tags(event))
        span = _DURATION_SPANS.get(event)
        if span is not None:
            t1 = _fr.now()
            if t1:
                span.end_at(t1 - float(duration), float(duration))
    except Exception:
        pass


def _imported_jax():
    """The jax module once its import has COMPLETED, else None.
    ``sys.modules`` holds a module from the moment its import starts, and
    a runtime thread that reaches into jax's submodules while user code
    is still inside ``import jax`` on another thread breaks that import
    (the import system resolves the lock cycle by handing one thread a
    half-initialised module)."""
    jax = sys.modules.get("jax")
    if jax is None or getattr(jax.__spec__, "_initializing", False):
        return None
    return jax


def install_jax_listeners(import_just_finished: bool = False) -> bool:
    """Register jax.monitoring listeners once per process. Returns True if
    listeners are (already) installed; False when jax is absent (or, seen
    from another thread, still being imported) or its monitoring seam
    moved (the API lives in jax._src.monitoring).
    ``import_just_finished``: the caller is the import hook, on the
    importing thread, right after jax's module body ran."""
    global _listeners_installed
    with _listener_lock:
        if _listeners_installed:
            return True
        if not import_just_finished and _imported_jax() is None:
            return False
        try:
            from jax._src import monitoring as _mon

            reg_ev = getattr(_mon, "register_event_listener", None)
            reg_dur = getattr(_mon, "register_event_duration_secs_listener",
                              None)
            if reg_ev is None:
                return False
            reg_ev(_on_jax_event)
            if reg_dur is not None:
                reg_dur(_on_jax_event_duration)
            _listeners_installed = True
            return True
        except Exception:
            return False


class _ListenerInstallingLoader:
    """Loader proxy: run the real jax exec_module, then install the
    monitoring listeners before anyone gets to call into jax."""

    def __init__(self, loader):
        self._loader = loader

    def __getattr__(self, name):
        return getattr(self._loader, name)

    def create_module(self, spec):
        return self._loader.create_module(spec)

    def exec_module(self, module):
        _t = _fr.now()
        try:
            self._loader.exec_module(module)
        finally:
            _unobserve_jax_import()
        _sp_jax_import.end(_t)
        install_jax_listeners(import_just_finished=True)


class _JaxImportObserver:
    """Meta-path finder that observes (never itself loads) the top-level
    ``jax`` import, so the jax.monitoring listeners install the moment
    jax finishes importing — not on the first telemetry tick."""

    def __init__(self):
        self._in_find = False

    def find_spec(self, fullname, path=None, target=None):
        if fullname != "jax" or self._in_find:
            return None
        import importlib.util

        self._in_find = True
        try:
            spec = importlib.util.find_spec(fullname)
        finally:
            self._in_find = False
        if spec is None or spec.loader is None:
            return None
        spec.loader = _ListenerInstallingLoader(spec.loader)
        return spec


_observer_lock = threading.Lock()
_import_observer: Optional[_JaxImportObserver] = None


def observe_jax_import() -> bool:
    """Arm listener installation at the instant jax gets imported.

    The collector thread only installs listeners on its periodic tick,
    which misses every compile that fires before the first tick — the
    common case, since the first train step compiles immediately after
    jax import. Called at worker/daemon/driver runtime start: if jax is
    already loaded the listeners install now (returns True); otherwise
    a meta-path observer installs them the moment the ``jax`` import
    completes (returns False). Processes that never import jax never
    trigger it — laziness stays load-bearing."""
    global _import_observer
    if install_jax_listeners():
        return True
    with _observer_lock:
        if _import_observer is None:
            _import_observer = _JaxImportObserver()
            sys.meta_path.insert(0, _import_observer)
    return False


def _unobserve_jax_import() -> None:
    global _import_observer
    with _observer_lock:
        if _import_observer is not None:
            try:
                sys.meta_path.remove(_import_observer)
            except ValueError:
                pass
            _import_observer = None


def collect_device_stats(devices: List, node_hex: str = "") -> int:
    """Publish memory gauges for the given device objects; returns how many
    devices reported stats (CPU devices typically report none)."""
    node = node_hex[:8] or "local"
    n = 0
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        tags = {"node": node,
                "device": f"{getattr(d, 'platform', 'dev')}:"
                          f"{getattr(d, 'id', n)}"}
        in_use = stats.get("bytes_in_use")
        if in_use is not None:
            _BYTES_IN_USE.set(float(in_use), tags=tags)
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            _PEAK_BYTES.set(float(peak), tags=tags)
        n += 1
    return n


def jax_with_backend():
    """The jax module once user code in this process has imported it AND
    initialised a backend, else None. Asking is never what initialises
    one: this is the only way the runtime's own threads look at jax."""
    jax = _imported_jax()
    if jax is None:
        return None
    bridge = sys.modules["jax._src.xla_bridge"]  # loaded by ``import jax``
    return jax if bridge.backends_are_initialized() else None


def backend_devices() -> List:
    """``jax.devices()`` for code that may be the first in its process to
    touch a backend (the train loop's mesh, the decode engine): the call
    that brings the backend up lies under ``jax.backend_init``. On libtpu
    that call takes the chips and holds the GIL for most of its seconds."""
    import jax

    if jax_with_backend() is not None:
        return jax.devices()
    _t = _fr.now()
    devices = jax.devices()
    _sp_backend_init.end(_t)
    return devices


def collect_once(node_hex: str = "") -> int:
    """One collection tick: install listeners if jax showed up, then read
    the memory stats of the devices this process already holds. A no-op
    until user code has initialised a backend."""
    if "jax" not in sys.modules:
        return 0
    install_jax_listeners()
    jax = jax_with_backend()
    if jax is None:
        return 0
    return collect_device_stats(jax.local_devices(), node_hex)


# jax.monitoring event names the compile-cache and compile-time columns of
# process_device_report() read
_EV_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_EV_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_EV_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def process_device_report() -> dict:
    """What THIS process ran on and what compiling cost it so far: the
    platform, device kind and device count as JAX reports them,
    per-device memory stats, and the persistent
    compile-cache hits / misses and backend compile seconds the
    ``jax.monitoring`` listeners have counted. For a process that has
    already initialised its backend (it raises otherwise, rather than be
    the call that takes a chip)."""
    jax = jax_with_backend()
    if jax is None:
        raise RuntimeError(
            "process_device_report() is for a process whose JAX backend is "
            "already initialised; it will not initialise one")
    devices = jax.local_devices()
    reg = registry()

    def of_event(metric: str, event: str) -> list:
        return [v for tags, v in reg.local_values(metric).items()
                if dict(tags).get("event") == event]

    def event_count(event: str) -> int:
        return int(sum(of_event("ray_tpu_jax_events_total", event)))

    compile_s = sum(h["sum"] for h in of_event(
        "ray_tpu_jax_event_duration_seconds", _EV_BACKEND_COMPILE))
    memory = []
    for d in devices:
        stats = d.memory_stats() or {}
        memory.append({"id": int(d.id),
                       "bytes_in_use": stats.get("bytes_in_use"),
                       "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
        "cache_hits": event_count(_EV_CACHE_HIT),
        "cache_misses": event_count(_EV_CACHE_MISS),
        "compile_s": round(compile_s, 3),
        "memory": memory,
    }


def start_device_telemetry(node_hex: str = "",
                           interval_s: Optional[float] = None
                           ) -> threading.Event:
    """Start the per-process collector thread; returns its stop event."""
    set_node_tag(node_hex)
    if interval_s is None:
        from ray_tpu.core.config import global_config

        interval_s = max(
            0.05, global_config().device_telemetry_interval_ms / 1000.0)
    stop = threading.Event()

    def loop():
        while not stop.wait(interval_s):
            try:
                collect_once(node_hex)
            except Exception:
                pass  # telemetry must never take a worker down

    threading.Thread(target=loop, daemon=True,
                     name="device-telemetry").start()
    return stop
