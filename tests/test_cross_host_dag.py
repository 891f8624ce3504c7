"""Cross-host compiled graphs: net-ring edges resolved from actor
placement. Daemons here are separate OS processes joined over TCP — the
full multi-host path; an edge between the driver and a daemon-hosted
actor (or between actors on different daemons) must ride a NetRing,
while co-located edges stay /dev/shm, transparently to the caller."""

from __future__ import annotations

import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.core.exceptions import ActorDiedError
from ray_tpu.core.net_ring import NetRingReader, NetRingWriter
from ray_tpu.dag import InputNode
from ray_tpu.experimental.channel import ShmChannel


def wait_for(cond, timeout=60.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture()
def two_daemons():
    cluster = Cluster(head_node_args={"num_cpus": 1})
    n1 = cluster.add_node(num_cpus=2, resources={"d1": 4},
                          separate_process=True)
    n2 = cluster.add_node(num_cpus=2, resources={"d2": 4},
                          separate_process=True)
    yield cluster, n1, n2
    cluster.shutdown()


@ray_tpu.remote(resources={"d1": 1})
class OnD1:
    def inc(self, x):
        return x + 1

    def inc_late(self, x):
        time.sleep(1.0)  # a kill sent right after execute() lands mid-flight
        return x + 1

    def pid(self):
        return os.getpid()

    def matmul(self, x):
        import jax.numpy as jnp

        return jnp.asarray(x) @ jnp.asarray(x).T

    def chan_stats(self):
        from ray_tpu.experimental.channel import STATS

        return dict(STATS)


@ray_tpu.remote(resources={"d2": 1})
class OnD2:
    def double(self, x):
        return x * 2

    def rowsum(self, m):
        import jax.numpy as jnp

        return jnp.asarray(m).sum(axis=1)

    def chan_stats(self):
        from ray_tpu.experimental.channel import STATS

        return dict(STATS)


def test_cross_daemon_edges_are_net_rings(two_daemons):
    """driver->d1->d2->driver: every edge crosses a process on a
    different node, so the compile must lay NetRings end to end — and
    the DAG must behave exactly like a shm one (ordering, overlap,
    backpressure)."""
    a, b = OnD1.remote(), OnD2.remote()
    with InputNode() as inp:
        out = b.double.bind(a.inc.bind(inp))
    dag = out.experimental_compile(max_inflight=4)
    try:
        # topology proof: the driver's endpoints are net, not shm
        assert all(isinstance(ch, NetRingWriter)
                   for ch in dag._input_chans), dag._input_chans
        assert isinstance(dag._out, NetRingReader)
        assert not any(isinstance(ch, ShmChannel) for ch in dag._channels)
        for i in range(6):
            assert dag.execute(i).get(timeout=60) == (i + 1) * 2
        # pipelined: max_inflight rounds overlap in flight
        refs = [dag.execute(i) for i in range(4)]
        assert [r.get(timeout=60) for r in refs] == \
            [(i + 1) * 2 for i in range(4)]
    finally:
        dag.teardown()


def test_mixed_topology_shm_and_net(two_daemons):
    """An actor on the HEAD node keeps /dev/shm edges to the driver
    while the daemon-hosted stage gets net rings — per-edge resolution,
    one graph."""

    @ray_tpu.remote  # no resource constraint: lands on the head node
    class Local:
        def triple(self, x):
            return x * 3

    loc, far = Local.remote(), OnD1.remote()
    with InputNode() as inp:
        out = far.inc.bind(loc.triple.bind(inp))
    dag = out.experimental_compile(max_inflight=2)
    try:
        # driver->local edge is shm; local->far and far->driver are net
        assert any(isinstance(ch, ShmChannel) for ch in dag._input_chans)
        assert isinstance(dag._out, NetRingReader)
        for i in range(5):
            assert dag.execute(i).get(timeout=60) == i * 3 + 1
    finally:
        dag.teardown()


def test_tensor_path_crosses_daemons_without_serializer(two_daemons):
    """device_channels=True across daemons: activations ride the
    TAG_TENSOR payload format over the net session — the serializer
    stays at zero bytes on every stage."""
    a, b = OnD1.remote(), OnD2.remote()
    with InputNode() as inp:
        out = b.rowsum.bind(a.matmul.bind(inp))
    dag = out.experimental_compile(buffer_size_bytes=8 << 20,
                                   device_channels=True, max_inflight=2)
    try:
        x = np.arange(64 * 32, dtype=np.float32).reshape(64, 32)
        got = dag.execute(x).get(timeout=120)
        np.testing.assert_allclose(np.asarray(got), (x @ x.T).sum(axis=1),
                                   rtol=1e-4)
        sa = ray_tpu.get(a.chan_stats.remote())
        sb = ray_tpu.get(b.chan_stats.remote())
        assert sa["tensor_bytes"] >= 64 * 64 * 4
        assert sa["serialized_bytes"] == 0, sa
        assert sb["serialized_bytes"] == 0, sb
    finally:
        dag.teardown()


def test_executor_death_cross_daemon_fails_attributed(two_daemons):
    """Killing a daemon-hosted executor worker mid-flight must surface
    as an attributed ActorDiedError on the driver — parked net reads
    unwedge via the poison broadcast, never a bare timeout."""
    a = OnD1.remote()
    pid = ray_tpu.get(a.pid.remote(), timeout=60)
    with InputNode() as inp:
        out = a.inc_late.bind(inp)  # else a reply can get out before the kill
    dag = out.experimental_compile(max_inflight=2)
    assert dag.execute(1).get(timeout=60) == 2
    ref = dag.execute(2)
    os.kill(pid, signal.SIGKILL)
    with pytest.raises(ActorDiedError):
        ref.get(timeout=60)
    dag.teardown()  # bounded, no wedge


def test_rebind_rebuilds_net_edges_to_actors_new_node(two_daemons):
    """THE PR-12 gap this PR closes: after an executor restart the
    rebind must re-resolve placement and rebuild net-ring edges to the
    actor's NEW node — not just re-uid the old shm paths. Kill the
    daemon hosting the actor; failover restarts it on the OTHER daemon;
    the next execute() must dial rings there and produce correct
    results."""
    cluster, n1, n2 = two_daemons

    @ray_tpu.remote(resources={"pool": 1}, max_restarts=2)
    class Movable:
        def inc(self, x):
            return x + 1

    # two daemons share the "pool" resource so failover has a target
    cluster.add_node(num_cpus=1, resources={"pool": 1},
                     separate_process=True)
    cluster.add_node(num_cpus=1, resources={"pool": 1},
                     separate_process=True)
    s = Movable.remote()
    assert ray_tpu.get(s.inc.remote(0), timeout=60) == 1
    from ray_tpu.core.runtime import get_current_runtime

    head = get_current_runtime().head
    loc0 = head.actor_location(s._actor_id)["node_hex"]
    with InputNode() as inp:
        out = s.inc.bind(inp)
    dag = out.experimental_compile(max_inflight=2)
    assert dag.execute(1).get(timeout=60) == 2
    assert isinstance(dag._out, NetRingReader)
    # kill the HOSTING DAEMON (not just the worker): the restart must
    # land on the other pool node
    victim = head.nodes[loc0]
    os.kill(victim.pid, signal.SIGKILL)
    wait_for(lambda: (head.actor_location(s._actor_id) or {})
             .get("node_hex") not in (None, loc0),
             timeout=90, msg="actor failover to the surviving node")
    wait_for(lambda: (head.actor_location(s._actor_id) or {})
             .get("state") == "ALIVE",
             timeout=90, msg="restarted actor alive")
    loc1 = head.actor_location(s._actor_id)["node_hex"]
    assert loc1 != loc0
    # drive the DAG until the rebind lands on the new incarnation
    deadline = time.monotonic() + 90
    value = None
    while time.monotonic() < deadline:
        try:
            value = dag.execute(9, timeout=20).get(timeout=30)
            break
        except Exception:
            time.sleep(0.3)
    assert value == 10, f"rebind to the new node never served: {value!r}"
    # and the rebuilt output edge is a fresh net ring (new uid)
    assert isinstance(dag._out, NetRingReader)
    assert dag._uid in dag._out.ring_id
    dag.teardown()
