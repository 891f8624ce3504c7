"""Public driver API: init/shutdown/remote/get/put/wait/kill/cancel/...

Analog of ``python/ray/_private/worker.py`` (ray.init :1227, get/put/wait
wrappers) in the reference, minus process spawning for the control plane —
the head runs in the driver process and worker processes are forked per node
(see node.py).
"""

from __future__ import annotations

import inspect
import os
from typing import Any, Dict, List, Optional, Sequence, Union

from . import object_ref as object_ref_mod
from . import runtime as runtime_mod
from .actor import ActorClass, ActorHandle, method  # noqa: F401
from .exceptions import GetTimeoutError
from .ids import ActorID
from .object_ref import ObjectRef
from .remote_function import RemoteFunction
from .runtime import DriverRuntime, Head


_head: Optional[Head] = None
_namespace: str = "default"


def is_initialized() -> bool:
    return runtime_mod.get_current_runtime() is not None


def init(
    num_cpus: Optional[int] = None,
    num_tpus: Optional[int] = None,
    num_gpus: Optional[int] = None,
    resources: Optional[Dict[str, float]] = None,
    object_store_memory: Optional[int] = None,
    namespace: str = "default",
    labels: Optional[Dict[str, str]] = None,
    ignore_reinit_error: bool = False,
    address: Optional[str] = None,
    cluster_key: Optional[str] = None,
    storage: Optional[str] = None,
    local_mode: bool = False,
    **_kwargs,
):
    """Start a single-node cluster in-process and connect the driver —
    or, with ``address="ray_tpu://host:port"``, connect this process as a
    *remote* driver to a running head (Ray Client analog; reference:
    ``ray.init(address="ray://...")``). ``cluster_key`` (hex; or env
    ``RAY_TPU_CLUSTER_KEY``) authenticates the channel."""
    global _head, _namespace
    # imported here: ray_tpu.util's package imports this module's ``remote``
    from ray_tpu.util import flight_recorder as _fr

    _t_init = _fr.now()
    if is_initialized():
        if ignore_reinit_error:
            return runtime_mod.get_current_runtime()
        raise RuntimeError("ray_tpu.init() called twice")
    if local_mode:
        # inline debugging mode (reference: ray.init(local_mode=True)) —
        # tasks/actors execute synchronously in this process
        from .local_mode import LocalModeRuntime

        _namespace = namespace
        rt = LocalModeRuntime(namespace)
        runtime_mod.set_current_runtime(rt)
        object_ref_mod.set_runtime(rt)
        return rt
    address = address or os.environ.get("RAY_TPU_ADDRESS")
    if address and address not in ("local", "auto"):
        from .client_runtime import ClientRuntime

        if address.startswith("ray_tpu://"):
            address = address[len("ray_tpu://"):]
        key_hex = cluster_key or os.environ.get("RAY_TPU_CLUSTER_KEY", "")
        if not key_hex:
            raise ValueError(
                "connecting to a remote head requires cluster_key= or "
                "RAY_TPU_CLUSTER_KEY")
        _namespace = namespace
        rt = ClientRuntime(address, bytes.fromhex(key_hex))
        runtime_mod.set_current_runtime(rt)
        object_ref_mod.set_runtime(rt)
        return rt
    from .config import global_config
    from .accelerators import detect_resources
    from ray_tpu.util.compile_cache import configure as configure_cache

    # one compile-cache directory for this driver (should it import jax)
    # and for every process it spawns
    configure_cache()
    if object_store_memory:
        global_config().object_store_memory = int(object_store_memory)
    total = detect_resources(num_cpus=num_cpus, num_tpus=num_tpus,
                             num_gpus=num_gpus, extra=resources)
    _namespace = namespace
    from ray_tpu.util.usage_stats import mark_session_started

    mark_session_started()  # no-op unless RAY_TPU_USAGE_STATS_ENABLED=1
    _head = Head(total, labels=labels, storage=storage)
    rt = DriverRuntime(_head)
    runtime_mod.set_current_runtime(rt)
    object_ref_mod.set_runtime(rt)
    if global_config().device_telemetry_enabled:
        # driver-process JAX device gauges land in the head registry
        from ray_tpu.util.device_telemetry import (observe_jax_import,
                                                    start_device_telemetry)

        observe_jax_import()  # compile events from process start, not tick 1
        _head._device_telemetry_stop = start_device_telemetry(
            node_hex=_head.head_node.hex)
    # the head started in this process, entry to return: one record a
    # process (``timeline --attribute``'s set-up block)
    _fr.register_span("runtime.init").end(_t_init)
    return rt


def shutdown():
    global _head
    rt = runtime_mod.get_current_runtime()
    if rt is None:
        return
    runtime_mod.set_current_runtime(None)
    object_ref_mod.set_runtime(None)
    if getattr(rt, "mode", None) in ("CLIENT", "LOCAL"):
        rt.disconnect()
        return
    if _head is not None:
        cs = getattr(_head, "_client_server", None)
        if cs is not None:
            cs.stop()
            _head._client_server = None
        _head.shutdown()
        _head = None
        try:
            from ray_tpu.util.usage_stats import flush

            flush()  # local-only, opt-in (RAY_TPU_USAGE_STATS_ENABLED)
        except Exception:
            pass  # telemetry must never break shutdown


def start_client_server(host: str = "127.0.0.1", port: int = 0):
    """Start the head-side remote-driver server (Ray Client analog).

    Returns ((host, port), cluster_key_hex) — hand these to remote
    drivers: ``ray_tpu.init(address=f"ray_tpu://{host}:{port}",
    cluster_key=key)``.
    """
    head = _get_head()
    from .client_server import ClientServer

    if getattr(head, "_client_server", None) is None:
        head._client_server = ClientServer(head, host, port)
    return head._client_server.address, head.cluster_key_hex


def _get_head() -> Head:
    if _head is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    return _head


def remote(*args, **options):
    """``@remote`` decorator for functions and classes (reference:
    python/ray/_private/worker.py remote)."""

    def make(obj):
        if inspect.isclass(obj):
            return ActorClass(obj, options)
        return RemoteFunction(obj, options)

    if len(args) == 1 and not options and (inspect.isfunction(args[0])
                                           or inspect.isclass(args[0])):
        return make(args[0])
    if args:
        raise TypeError("@remote takes keyword options only")
    return make


def get(refs: Union[ObjectRef, Sequence[ObjectRef]],
        *, timeout: Optional[float] = None):
    rt = runtime_mod.get_current_runtime()
    if rt is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    single = isinstance(refs, ObjectRef)
    lst = [refs] if single else list(refs)
    for r in lst:
        if not isinstance(r, ObjectRef):
            raise TypeError(f"get() expects ObjectRefs, got {type(r)}")
    values = rt.get(lst, timeout=timeout)
    return values[0] if single else values


def put(value: Any) -> ObjectRef:
    rt = runtime_mod.get_current_runtime()
    if rt is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    if isinstance(value, ObjectRef):
        raise TypeError("put() on an ObjectRef is not allowed")
    return rt.put(value)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    rt = runtime_mod.get_current_runtime()
    lst = list(refs)
    if num_returns > len(lst):
        raise ValueError("num_returns exceeds number of refs")
    return rt.wait(lst, num_returns=num_returns, timeout=timeout,
                   fetch_local=fetch_local)


def get_object_locations(refs: Sequence[ObjectRef]) -> Dict[ObjectRef, List[str]]:
    """Node hexes currently holding each object (may be empty for inline
    or in-flight objects). The data plane uses this for locality-aware
    dispatch and split dealing; works from the driver and from workers
    (reference: ray.experimental.get_object_locations)."""
    rt = runtime_mod.get_current_runtime()
    if rt is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    lst = list(refs)
    for r in lst:
        if not isinstance(r, ObjectRef):
            raise TypeError(
                f"get_object_locations() expects ObjectRefs, got {type(r)}")
    lookup = getattr(rt, "object_locations", None)
    if lookup is None:  # e.g. local_mode: everything is in-process
        return {r: [] for r in lst}
    locs = lookup([r.id for r in lst])
    return {r: list(ls) for r, ls in zip(lst, locs)}


def kill(actor: ActorHandle, *, no_restart: bool = True):
    rt = runtime_mod.get_current_runtime()
    rt.kill_actor(actor._actor_id, no_restart)


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True):
    rt = runtime_mod.get_current_runtime()
    rt.cancel_task(ref.id, force)


def get_actor(name: str, namespace: Optional[str] = None) -> ActorHandle:
    rt = runtime_mod.get_current_runtime()
    info = rt.get_actor_info(name, namespace or _namespace)
    if info is None:
        raise ValueError(f"Failed to look up actor {name!r}")
    return ActorHandle(info["actor_id"], info["class_name"],
                       max_task_retries=info.get("max_task_retries", 0) or 0)


def available_resources() -> Dict[str, float]:
    return runtime_mod.get_current_runtime().available_resources()


def cluster_resources() -> Dict[str, float]:
    return runtime_mod.get_current_runtime().cluster_resources()


def nodes() -> List[dict]:
    return runtime_mod.get_current_runtime().nodes()
