"""Test config: force a virtual 8-device CPU mesh for all jax-using tests.

Mirrors the reference's test strategy (SURVEY.md §4): scheduler/Train logic is
tested against fake multi-device topology — here JAX's
``xla_force_host_platform_device_count`` gives 8 virtual CPU devices, so
multi-chip sharding paths compile and run without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch a chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# persistent XLA compile cache, shared by the pytest process AND every
# worker subprocess it spawns (env set before any jax import), at the one
# place the program's rule puts it (ray_tpu/util/compile_cache.py). The
# suite compiles the same train-step/collective programs over and over
# across processes; on a small box this is most of the wall clock
# (test_llama: 39s cold -> 8s warm).  Keyed by HLO hash, so stale
# entries are impossible.
from ray_tpu.util.compile_cache import configure as _configure_compile_cache

_configure_compile_cache()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

import pytest


@pytest.fixture(autouse=True)
def _dump_stacks_on_hang():
    """Per-test hang telemetry: if any single test exceeds 10 minutes,
    dump every thread's stack to stderr (the suite has shown rare
    whole-run wedges with idle workers — stacks are the only way to
    find the blocked wait on a box with no gdb/py-spy)."""
    import faulthandler

    window = float(os.environ.get("RAY_TPU_TEST_HANG_DUMP_S", "600"))
    faulthandler.dump_traceback_later(window, exit=False)
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def ray_start_regular():
    """Single-node cluster fixture (reference: conftest.py ray_start_regular)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-node in-process cluster (reference: conftest.py ray_start_cluster)."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()
