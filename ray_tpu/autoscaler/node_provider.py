"""Node providers: the cloud seam of the autoscaler.

Reference: python/ray/autoscaler/node_provider.py (NodeProvider interface:
create_node/terminate_node/non_terminated_nodes) and the per-cloud
implementations under python/ray/autoscaler/_private/. Here the
interface is minimal and synchronous; the reconciler serializes calls.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
import uuid
from typing import Callable, Dict, List, Optional


class NodeProvider:
    """Create/terminate worker nodes. Implementations must be idempotent
    on terminate and report only their own (non-head) nodes."""

    def create_node(self, node_config: dict) -> str:
        raise NotImplementedError

    def terminate_node(self, provider_id: str) -> None:
        raise NotImplementedError

    def non_terminated_nodes(self) -> List[str]:
        raise NotImplementedError

    def is_running(self, provider_id: str) -> bool:
        return provider_id in self.non_terminated_nodes()

    def shutdown(self) -> None:
        for pid in list(self.non_terminated_nodes()):
            self.terminate_node(pid)


class LocalNodeProvider(NodeProvider):
    """Worker nodes as local ``python -m ray_tpu start`` daemon processes
    joining the head over TCP — the autoscaler analog of the reference's
    'local' provider, and the test double for cloud providers (every
    launched node is a REAL separate-process node daemon)."""

    def __init__(self, head_address, cluster_key_hex: str):
        self._address = f"{head_address[0]}:{head_address[1]}"
        self._key = cluster_key_hex
        self._procs: Dict[str, subprocess.Popen] = {}
        self._lock = threading.Lock()

    def create_node(self, node_config: dict) -> str:
        import json

        provider_id = f"local-{uuid.uuid4().hex[:8]}"
        cmd = [sys.executable, "-m", "ray_tpu", "start",
               "--address", self._address, "--key", self._key,
               # the provider_id label is how the reconciler maps a
               # cluster node back to this instance for termination
               "--labels", json.dumps({"provider_id": provider_id}),
               # explicit counts — never auto-detect (a co-located node
               # already advertises the TPU chips)
               "--num-cpus", str(node_config.get("num_cpus", 1)),
               "--num-tpus", str(node_config.get("num_tpus", 0))]
        if node_config.get("resources"):
            cmd += ["--resources", json.dumps(node_config["resources"])]
        from ray_tpu.core.accelerators import runtime_process_env

        # the node daemon itself never owns a chip (its workers do)
        env = runtime_process_env(os.environ)
        repo = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(cmd, env=env, start_new_session=True,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        with self._lock:
            self._procs[provider_id] = proc
        return provider_id

    def terminate_node(self, provider_id: str) -> None:
        with self._lock:
            proc = self._procs.pop(provider_id, None)
        if proc is None:
            return
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            return
        deadline = time.time() + 3.0
        while time.time() < deadline and proc.poll() is None:
            time.sleep(0.05)
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass

    def non_terminated_nodes(self) -> List[str]:
        with self._lock:
            return [pid for pid, proc in self._procs.items()
                    if proc.poll() is None]


class TPUSliceProvider(NodeProvider):
    """TPU-slice provisioning seam (injected callables).

    Zero-egress environments can't call cloud APIs, so actual provisioning
    is delegated to operator-supplied callables — e.g. wrappers over
    ``gcloud compute tpus queued-resources create`` or a KubeRay-style CRD
    reconciler. The autoscaler treats slices as atomic nodes: one
    create_node call = one slice request (the TPU analog of the
    reference's per-VM cloud providers). For the full Queued-Resources
    shape see :class:`TPUQueuedResourceProvider`.
    """

    def __init__(self, launch_fn: Callable[[dict], str],
                 terminate_fn: Callable[[str], None],
                 list_fn: Callable[[], List[str]]):
        self._launch = launch_fn
        self._terminate = terminate_fn
        self._list = list_fn

    def create_node(self, node_config: dict) -> str:
        return self._launch(node_config)

    def terminate_node(self, provider_id: str) -> None:
        self._terminate(provider_id)

    def non_terminated_nodes(self) -> List[str]:
        return list(self._list())


# accelerator type -> (chips per host, total chips); topology label is the
# type's own chip grid (reference: accelerators/tpu.py pod shapes)
_TPU_SHAPES = {
    "v4-8": (4, 4), "v4-16": (4, 8), "v4-32": (4, 16),
    "v5litepod-4": (4, 4), "v5litepod-8": (8, 8), "v5litepod-16": (4, 16),
    "v5litepod-32": (4, 32), "v5litepod-64": (4, 64),
    "v5p-8": (4, 4), "v5p-16": (4, 8),
    "v6e-4": (4, 4), "v6e-8": (8, 8), "v6e-16": (4, 16),
    "v6e-64": (4, 64), "v6e-256": (4, 256),
}


class TPUQueuedResourceProvider(NodeProvider):
    """GCP Queued-Resources slice provider (reference: the cloud-provider
    role of python/ray/autoscaler/_private/gcp/ + the TPU pod semantics of
    accelerators/tpu.py:71).

    One ``create_node`` = one queued-resource request for a whole slice.
    Every host of a granted slice bootstraps (via the startup script this
    provider composes) as a node daemon carrying the slice topology as
    scheduler labels:

        ray-tpu-slice=<qr name>, ray-tpu-accelerator=<type>,
        ray-tpu-worker=<host index>

    plus the ``TPU-<type>-head`` resource on worker 0 — the label set
    gang-scheduling placement groups key on.

    ``runner`` executes the gcloud invocations and returns stdout; the
    default shells out, tests inject a fake (this box has zero egress).
    The QR lifecycle (WAITING_FOR_RESOURCES -> PROVISIONING -> ACTIVE |
    SUSPENDED/FAILED) is polled via ``list``; only non-terminal QRs count
    as non_terminated (the autoscaler keeps demand pending meanwhile).
    """

    def __init__(self, head_address, cluster_key_hex: str, *,
                 project: str, zone: str,
                 runtime_version: str = "v2-alpha-tpuv5-lite",
                 runner: Optional[Callable[[List[str]], str]] = None):
        self._address = f"{head_address[0]}:{head_address[1]}"
        self._key = cluster_key_hex
        self._project = project
        self._zone = zone
        self._runtime = runtime_version
        self._runner = runner or self._shell
        self._lock = threading.Lock()
        self._requested: Dict[str, dict] = {}  # qr name -> node_config

    @staticmethod
    def _shell(cmd: List[str]) -> str:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=300)
        if out.returncode != 0:
            raise RuntimeError(f"{' '.join(cmd)} failed: {out.stderr}")
        return out.stdout

    # ---- slice math ------------------------------------------------------

    @staticmethod
    def slice_shape(accelerator_type: str):
        """(chips_per_host, total_chips, num_hosts) for a type."""
        per_host, total = _TPU_SHAPES.get(accelerator_type, (4, 4))
        return per_host, total, max(1, total // per_host)

    def startup_script(self, qr_name: str, accelerator_type: str) -> str:
        """Per-host bootstrap: join the head with slice-topology labels.
        TPU_WORKER_ID is set by the TPU runtime on every pod host."""
        import json

        per_host, _total, _hosts = self.slice_shape(accelerator_type)
        labels = {
            "ray-tpu-slice": qr_name,
            "ray-tpu-accelerator": accelerator_type,
            "ray-tpu-worker": "${TPU_WORKER_ID}",
        }
        head_res = json.dumps({f"TPU-{accelerator_type}-head": 1})
        # The labels JSON must ride inside DOUBLE quotes so the shell
        # expands ${TPU_WORKER_ID} per host (single quotes would register
        # every host with the literal string '${TPU_WORKER_ID}').
        labels_sh = json.dumps(labels).replace('"', '\\"')
        return (
            "#!/bin/bash\n"
            f"RES='{{}}'\n"
            f"if [ \"${{TPU_WORKER_ID}}\" = \"0\" ]; then RES='{head_res}'; fi\n"
            f"python -m ray_tpu start --address {self._address} "
            f"--key {self._key} --num-tpus {per_host} "
            f"--resources \"$RES\" "
            f"--labels \"{labels_sh}\"\n"
        )

    # ---- provider interface ---------------------------------------------

    def create_node(self, node_config: dict) -> str:
        acc = node_config.get("accelerator_type", "v5litepod-4")
        qr_name = f"raytpu-qr-{uuid.uuid4().hex[:8]}"
        cmd = [
            "gcloud", "compute", "tpus", "queued-resources", "create",
            qr_name,
            f"--project={self._project}", f"--zone={self._zone}",
            f"--node-id={qr_name}-node",
            f"--accelerator-type={acc}",
            f"--runtime-version={self._runtime}",
            "--metadata-from-file",
            f"startup-script={self._write_script(qr_name, acc)}",
        ]
        if node_config.get("spot"):
            cmd.append("--spot")
        if node_config.get("reserved"):
            cmd.append("--reserved")
        self._runner(cmd)
        with self._lock:
            self._requested[qr_name] = dict(node_config)
        return qr_name

    def _write_script(self, qr_name: str, acc: str) -> str:
        import tempfile

        path = os.path.join(tempfile.gettempdir(),
                            f"raytpu_qr_{qr_name}.sh")
        with open(path, "w") as f:
            f.write(self.startup_script(qr_name, acc))
        return path

    # delete errors that mean the QR is already gone / already going:
    # retrying is pointless and raising would abort the reconciler's
    # whole scale-down pass (other victims never terminate)
    _GONE_MARKERS = ("not_found", "notfound", "404", "409", "conflict",
                     "already", "deleting", "does not exist")

    def terminate_node(self, provider_id: str) -> None:
        try:
            self._runner([
                "gcloud", "compute", "tpus", "queued-resources", "delete",
                provider_id, f"--project={self._project}",
                f"--zone={self._zone}", "--quiet", "--force"])
        except Exception as e:  # noqa: BLE001 — classify, don't mask
            msg = str(e).lower()
            if not any(m in msg for m in self._GONE_MARKERS):
                raise
            # already deleted / delete in progress: converge silently
        with self._lock:
            self._requested.pop(provider_id, None)

    def non_terminated_nodes(self) -> List[str]:
        import json

        try:
            out = self._runner([
                "gcloud", "compute", "tpus", "queued-resources", "list",
                f"--project={self._project}", f"--zone={self._zone}",
                "--format=json"])
        except Exception:
            # transient list/describe failure (gcloud timeouts are the
            # common QR-devops papercut): serve the last good view so one
            # blip doesn't zero the provider count and double-launch.
            # Never-succeeded listing still raises (misconfig, fail fast).
            cached = getattr(self, "_last_alive", None)
            if cached is None:
                raise
            return list(cached)
        alive = []
        for qr in json.loads(out or "[]"):
            name = qr.get("name", "").rsplit("/", 1)[-1]
            state = (qr.get("state") or {}).get("state", "")
            if state not in ("SUSPENDED", "FAILED", "DELETING"):
                alive.append(name)
        self._last_alive = list(alive)
        return alive
