"""In-process multi-node test cluster.

Analog of ``python/ray/cluster_utils.py`` (:135 Cluster, add_node :201,
remove_node :279) in the reference — the workhorse for distributed tests:
several Node objects (each with its own worker processes, shm arena, and
resource view) share one head/GCS in the driver process. ``remove_node``
simulates node death, driving the same failover paths real node loss would
(actor restart, task retry, lineage reconstruction).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ray_tpu.core import api, object_ref as object_ref_mod, runtime as runtime_mod
from ray_tpu.core.node import Node
from ray_tpu.core.runtime import DriverRuntime, Head


class Cluster:
    def __init__(self, initialize_head: bool = True,
                 head_node_args: Optional[Dict] = None, connect: bool = True):
        self.head: Optional[Head] = None
        self._connected = False
        self._procs: List[subprocess.Popen] = []
        if initialize_head:
            args = dict(head_node_args or {})
            resources = args.pop("resources", {})
            resources.setdefault("CPU", args.pop("num_cpus", 4))
            if "num_tpus" in args:
                resources["TPU"] = args.pop("num_tpus")
            self.head = Head(resources, labels=args.pop("labels", None),
                             storage=args.pop("storage", None))
            api._head = self.head
            if connect:
                self.connect()

    def connect(self):
        rt = DriverRuntime(self.head)
        runtime_mod.set_current_runtime(rt)
        object_ref_mod.set_runtime(rt)
        self._connected = True
        return rt

    def add_node(self, num_cpus: int = 4, num_tpus: int = 0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 separate_process: bool = False,
                 register_timeout: float = 30.0,
                 node_ip: Optional[str] = None):
        """Add a node: in-process by default (several raylets, one OS
        process — the reference Cluster fixture), or as a REAL separate OS
        process joining over TCP (``separate_process=True``), exercising the
        full multi-host path: daemon registration, remote dispatch, direct
        chunked node-to-node object transfer."""
        total = dict(resources or {})
        total.setdefault("CPU", num_cpus)
        if num_tpus:
            total["TPU"] = num_tpus
        if not separate_process:
            return self.head.add_node(total, labels=labels, node_ip=node_ip)
        host, port = self.head.start_node_server()
        before = set(self.head.nodes)
        from ray_tpu.core.accelerators import runtime_process_env

        # a node daemon runs no device code: held to the CPU, it can never
        # take a chip from the workers it spawns (each of which gets its
        # own platform from its binding, core/node.py)
        env = runtime_process_env(os.environ)
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.node_daemon",
             "--address", f"{host}:{port}",
             "--key", self.head.cluster_key_hex,
             # explicit counts: never let the daemon auto-detect the TPU
             # chips a co-located node already advertises
             "--num-cpus", str(total.get("CPU", num_cpus)),
             "--num-tpus", str(total.get("TPU", 0)),
             "--resources", json.dumps(
                 {k: v for k, v in total.items() if k not in ("CPU", "TPU")}),
             "--labels", json.dumps(labels or {})]
            + (["--node-ip", node_ip] if node_ip else []),
            env=env,
        )
        self._procs.append(proc)
        deadline = time.monotonic() + register_timeout
        while time.monotonic() < deadline:
            new = set(self.head.nodes) - before
            if new:
                return self.head.nodes[new.pop()]
            if proc.poll() is not None:
                raise RuntimeError(
                    f"node daemon exited rc={proc.returncode} before joining")
            time.sleep(0.05)
        raise TimeoutError("node daemon did not register in time")

    def remove_node(self, node) -> None:
        self.head.remove_node(node.hex)

    def shutdown(self):
        if self._connected:
            runtime_mod.set_current_runtime(None)
            object_ref_mod.set_runtime(None)
        if self.head is not None:
            self.head.shutdown()
            self.head = None
        for p in self._procs:
            try:
                p.terminate()
                p.wait(timeout=5)
            except Exception:
                try:
                    p.kill()
                except Exception:
                    pass
        self._procs.clear()
        api._head = None
