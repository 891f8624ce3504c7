"""Accelerator detection and per-process chip ownership, TPU-first.

Analog of ``python/ray/_private/accelerators/`` in the reference, with the
TPU manager (``tpu.py:71 TPUAcceleratorManager``) as the primary citizen.

Detection counts the host's chips from its device files, without importing
jax: libtpu gives every chip it can see to the first process that
initialises a JAX backend, and that process keeps them until it exits, so
the driver and the node daemon must never be that process.

Ownership is decided when a worker process is spawned, by its environment
(:func:`worker_env`): a worker the runtime bound to chips asks for the
``tpu`` platform explicitly and sees exactly those chips; every other
process of the runtime is pinned to the CPU before it can import jax.
With ``JAX_PLATFORMS`` unset, a process that loses the race for a chip
logs at INFO and carries on on the CPU (the ``tpu`` backend factory is
registered ``fail_quietly``); asking for ``tpu`` by name turns that into
an error. :func:`check_devices_match_binding` closes the loop from inside
the worker once its backend is up.
"""

from __future__ import annotations

import os
from typing import Dict, Mapping, Optional, Sequence, Tuple

from ray_tpu.util.compile_cache import configure as _configure_compile_cache

VALID_TPU_CHIP_COUNTS = (1, 2, 4, 8)  # reference: tpu.py:141

# (chips in the process) -> TPU_CHIPS_PER_PROCESS_BOUNDS; a process given a
# strict subset of its host's chips must be told the shape of that subset,
# or libtpu waits for the host's full topology (reference: tpu.py:155-195)
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}

# what a previous owner of this environment may have said about chips; a
# worker's view is rebuilt from its binding alone
_TPU_VIEW_VARS = ("TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES",
                  "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")


class AcceleratorBindingError(RuntimeError):
    """The devices a worker's JAX backend reports are not the chips the
    runtime bound that worker to."""


def detect_num_tpu_chips(dev_root: str = "/dev",
                         environ: Mapping[str, str] = os.environ) -> int:
    """Count this host's TPU chips without initialising jax.

    Chips appear as ``/dev/accel<n>`` (v2-v4 and v5p hosts) or as
    ``/dev/vfio/<n>`` (v5e and v6e hosts); the reference's TPU manager
    counts the same two places. ``TPU_VISIBLE_CHIPS`` narrows the answer
    to what this process was allowed to see. What ``JAX_PLATFORMS`` asks
    for says nothing about what the host has, and is not consulted.
    """
    visible = environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    try:
        accel = [f for f in os.listdir(dev_root) if f.startswith("accel")]
    except OSError:
        accel = []
    if accel:
        return len(accel)
    try:
        return len([f for f in os.listdir(os.path.join(dev_root, "vfio"))
                    if f.isdigit()])
    except OSError:
        return 0


def runtime_process_env(base: Mapping[str, str]) -> Dict[str, str]:
    """Environment for a process of the runtime that runs no user device
    code (node daemon, launcher, CPU pool worker): JAX, if anything there
    imports it, is held to the CPU and can never take a chip."""
    env = dict(base)
    for var in _TPU_VIEW_VARS:
        env.pop(var, None)
    env["JAX_PLATFORMS"] = "cpu"
    _configure_compile_cache(env)
    return env


def worker_env(base: Mapping[str, str], chips: Optional[Sequence[int]],
               host_chips: int) -> Dict[str, str]:
    """Environment a worker process is spawned with.

    ``chips`` is the worker's TPU binding (chip indices on this host) or
    ``None`` for a worker of the CPU pool. ``base`` is whatever the parent
    exports; nothing it says about platforms or chip visibility survives.
    """
    env = runtime_process_env(base)
    if not chips:
        return env
    env["JAX_PLATFORMS"] = "tpu"
    env["TPU_VISIBLE_CHIPS"] = ",".join(str(int(c)) for c in chips)
    if len(chips) < host_chips:
        bounds = _PROCESS_BOUNDS.get(len(chips))
        if bounds is None:
            raise ValueError(
                f"cannot bind {len(chips)} of {host_chips} TPU chips to one "
                f"process: a subset must hold {sorted(_PROCESS_BOUNDS)} chips")
        # both spellings: a TPU VM image exports the older *_HOST_BOUNDS
        # names for the whole host, and they must not contradict the subset
        for var in ("TPU_CHIPS_PER_PROCESS_BOUNDS",
                    "TPU_CHIPS_PER_HOST_BOUNDS"):
            env[var] = bounds
        for var in ("TPU_PROCESS_BOUNDS", "TPU_HOST_BOUNDS"):
            env[var] = "1,1,1"
    return env


def check_devices_match_binding(chips: Optional[Sequence[int]],
                                devices: Sequence) -> None:
    """Raise unless ``devices`` (``jax.local_devices()`` of a worker whose
    backend is initialised) are exactly what its binding promised: one TPU
    device per bound chip, or no TPU device for a worker bound to none."""
    bound: Tuple[int, ...] = tuple(chips or ())
    tpus = [d for d in devices if d.platform == "tpu"]
    if (len(tpus) == len(devices) == len(bound)) if bound else not tpus:
        return
    raise AcceleratorBindingError(
        f"worker pid={os.getpid()} is bound to TPU chips {list(bound)} "
        f"({len(bound)} chip(s)) but its JAX backend reports "
        f"{[str(d) for d in devices]} "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}, "
        f"TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')!r})")


def tpu_pod_resources() -> Dict[str, float]:
    """Slice-head resources (e.g. TPU-v5e-8-head) for gang scheduling
    (reference: tpu.py advertises TPU-{type}-head on worker 0)."""
    out: Dict[str, float] = {}
    acc_type = os.environ.get("TPU_ACCELERATOR_TYPE")  # e.g. v5litepod-8
    worker_id = os.environ.get("TPU_WORKER_ID", "0")
    if acc_type and worker_id == "0":
        out[f"TPU-{acc_type}-head"] = 1.0
    return out


def detect_resources(
    num_cpus: Optional[int] = None,
    num_tpus: Optional[int] = None,
    num_gpus: Optional[int] = None,
    extra: Optional[Dict[str, float]] = None,
) -> Dict[str, float]:
    total: Dict[str, float] = {}
    total["CPU"] = float(num_cpus if num_cpus is not None else os.cpu_count() or 1)
    n_tpu = num_tpus if num_tpus is not None else detect_num_tpu_chips()
    if n_tpu:
        total["TPU"] = float(n_tpu)
        total.update(tpu_pod_resources())
    if num_gpus:
        total["GPU"] = float(num_gpus)
    total["memory"] = float(os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"))
    total["object_store_memory"] = 0.0
    if extra:
        total.update({k: float(v) for k, v in extra.items()})
    total = {k: v for k, v in total.items() if v}
    return total
