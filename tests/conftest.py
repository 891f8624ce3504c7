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
# across processes; on the 8-CPU box the driver's whole run takes 347-377 s
# from an empty cache and 269-276 s with the cache a run before it left (PR
# 34: test_spmd_train.py 140 s -> 58-71 s of its worker).  What no cache
# serves is tracing and lowering, in Python.  Keyed by HLO hash, so stale
# entries are impossible.
from ray_tpu.util.compile_cache import configure as _configure_compile_cache

_configure_compile_cache()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

import faulthandler
import signal

import pytest

# One deadline for every test's whole protocol (fixture set-up of any scope,
# the call, tear-down). A rare wedge (a wait nobody ends) once cost the
# driver its whole run and named no test; now it costs one failure, by name.
DEADLINE_S = 180.0  # the longest test is under 60 s on the loaded 8-CPU box
_real_stderr = None  # not the captured one: what is written here is seen


def pytest_configure(config):
    global _real_stderr
    config.addinivalue_line(
        "markers", "deadline(seconds): this test's own deadline, in place "
        f"of conftest.DEADLINE_S ({DEADLINE_S:g} s)")
    # capture is suspended here, so descriptor 2 is the run's own stderr (an
    # xdist worker inherits the controller's)
    _real_stderr = os.fdopen(os.dup(2), "w")


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    """At the deadline every thread's stack goes to the real stderr and the
    test FAILS with a ``TimeoutError`` naming it; its fixtures are torn
    down and the file's next test runs. The alarm interrupts a wait in
    Python (``Event.wait``, ``Queue.get``, ``socket.recv``,
    ``subprocess.wait``) and comes again every half deadline, for a
    tear-down that waits on the same thing. A wait no signal breaks (inside a
    C call) ends at twice the deadline: the stacks again and the worker exits,
    which xdist reports as this test's failure before it starts another."""
    marker = item.get_closest_marker("deadline")
    seconds = float(marker.args[0]) if marker else DEADLINE_S

    def passed_deadline(signum, frame):
        what = f"{item.nodeid} passed its deadline of {seconds:g} s"
        _real_stderr.write(f"\n{what}\n")
        _real_stderr.flush()
        faulthandler.dump_traceback(file=_real_stderr, all_threads=True)
        raise TimeoutError(what)

    before = signal.signal(signal.SIGALRM, passed_deadline)
    signal.setitimer(signal.ITIMER_REAL, seconds, seconds / 2)
    faulthandler.dump_traceback_later(2 * seconds, exit=True,
                                      file=_real_stderr)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, before)


def pytest_testnodedown(node, error):
    """xdist 3.8 under ``--dist loadfile`` puts a lost worker's FINISHED
    files back on the queue beside the one it died in. A worker that takes
    a finished file with nothing else left is sent no test, so it never
    asks again and is never shut down: the run stands until the driver's
    clock. Forget the finished files before xdist requeues the rest."""
    sched = getattr(node.config.pluginmanager.getplugin("dsession"), "sched",
                    None)
    work = getattr(sched, "assigned_work", {}).get(node, {})
    for file in [f for f, tests in work.items() if all(tests.values())]:
        del work[file]


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
