"""Deterministic chaos suite: kill each role mid-pipeline, assert
recovery or clean, attributed failure.

Reference coverage modeled: the reference's chaos/fault-tolerance drills
— GCS restart with raylets live (gcs FT), actor restart with
max_restarts/max_task_retries replay (gcs_actor_manager), owner-side
recovery of in-flight state. Every failure here is injected
DETERMINISTICALLY: either through a seeded fault spec
(core/fault_injection.py — named points with exact hit counts) or by
killing a specific pid / bouncing the head at a specific point in the
workload. No sleeps for correctness — every assertion waits on
observable state with a deadline.
"""

import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu.core import fault_injection
from ray_tpu.core.config import global_config
from ray_tpu.core.exceptions import ActorDiedError, format_death_cause


def wait_for(cond, timeout=30.0, msg="condition"):
    """Deadline on observable state (ADVICE: never sleep-and-hope)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


def _head_rpcs() -> float:
    from ray_tpu.util.metrics import registry

    m = registry().snapshot().get("ray_tpu_head_rpcs_total")
    return sum(m["values"].values()) if m else 0.0


# --------------------------------------------------------------------------
# fault-spec unit tests (no cluster)
# --------------------------------------------------------------------------


class TestFaultSpec:
    def teardown_method(self):
        fault_injection.reset()
        global_config().test_fault_spec = ""

    def test_parse_actions_and_hits(self):
        rules = fault_injection.parse_spec(
            "a.b=crash@3;c=drop;d=delay:250@2+;e.f=fail@1")
        assert rules["a.b"][0].action == "crash"
        assert rules["a.b"][0].start == 3 and not rules["a.b"][0].open_ended
        assert rules["c"][0].start == 1 and rules["c"][0].open_ended
        assert rules["d"][0].action == "delay"
        assert rules["d"][0].arg == pytest.approx(0.25)
        assert rules["d"][0].open_ended

    @pytest.mark.parametrize("bad", ["x", "p=explode", "p=crash@0",
                                     "p=crash@x"])
    def test_malformed_specs_raise(self, bad):
        with pytest.raises(ValueError):
            fault_injection.parse_spec(bad)

    def test_exact_hit_counting_is_deterministic(self):
        fault_injection.configure("p=drop@2")
        global_config().test_fault_spec = "p=drop@2"
        assert fault_injection.fire("p") is None          # hit 1
        assert fault_injection.fire("p") == "drop"        # hit 2
        assert fault_injection.fire("p") is None          # hit 3
        assert fault_injection.hits("p") == 3

    def test_open_ended_and_detail_match(self):
        spec = "wire.send.sync=drop@2+"
        fault_injection.configure(spec)
        global_config().test_fault_spec = spec
        assert fault_injection.fire("wire.send", "sync") is None
        assert fault_injection.fire("wire.send", "sync") == "drop"
        assert fault_injection.fire("wire.send", "sync") == "drop"
        # other tags never match the detail-qualified rule
        assert fault_injection.fire("wire.send", "pong") is None

    def test_raise_action(self):
        spec = "pt=raise@1"
        fault_injection.configure(spec)
        global_config().test_fault_spec = spec
        with pytest.raises(fault_injection.FaultInjected):
            fault_injection.fire("pt")

    def test_config_resync_rearms(self):
        global_config().test_fault_spec = "q=drop@1"
        assert fault_injection.fire("q") == "drop"
        global_config().test_fault_spec = ""  # disarm via config
        assert fault_injection.fire("q") is None


class TestDeathCauseFormatting:
    def test_format_death_cause(self):
        s = format_death_cause("worker died", "abcdef0123456789", 4242)
        assert s == "worker died (node abcdef01, worker pid 4242)"
        assert format_death_cause("x") == "x"

    def test_actor_died_error_fields_survive_pickle(self):
        import pickle

        from ray_tpu.core.ids import ActorID

        aid = ActorID.from_random()
        e = ActorDiedError(aid, "boom (node ab, worker pid 1)",
                           restarting=True)
        e2 = pickle.loads(pickle.dumps(e))
        assert e2.actor_id == aid
        assert e2.restarting is True
        assert "boom" in str(e2) and "restarting" in str(e2)

    def test_restart_backoff_schedule(self):
        from ray_tpu.core.runtime import Head

        cfg = global_config()
        old = (cfg.actor_restart_delay_ms, cfg.actor_restart_max_delay_ms)
        try:
            cfg.actor_restart_delay_ms = 100
            cfg.actor_restart_max_delay_ms = 450
            assert Head._restart_backoff_s(1) == pytest.approx(0.1)
            assert Head._restart_backoff_s(2) == pytest.approx(0.2)
            assert Head._restart_backoff_s(3) == pytest.approx(0.4)
            assert Head._restart_backoff_s(4) == pytest.approx(0.45)  # cap
            cfg.actor_restart_delay_ms = 0
            assert Head._restart_backoff_s(5) == 0.0
        finally:
            cfg.actor_restart_delay_ms, cfg.actor_restart_max_delay_ms = old


# --------------------------------------------------------------------------
# actor restart: kill mid-call via fault point, replay completes
# --------------------------------------------------------------------------


class TestActorCrashMidCall:
    def test_crash_point_kills_second_call_and_replay_completes(self):
        """The chaos point "worker.exec.bump=crash@2" hard-kills the actor
        worker at the exact moment it begins executing the SECOND bump()
        — deterministically, same op every run. max_restarts=1 restarts
        the actor, max_task_retries=1 replays the killed call onto the
        fresh incarnation (whose per-process hit counter is back at 0),
        and the caller sees nothing but a slower answer."""
        cfg = global_config()
        cfg.test_fault_spec = "worker.exec.bump=crash@2"
        try:
            ray_tpu.init(num_cpus=2, num_tpus=0)

            @ray_tpu.remote(max_restarts=1, max_task_retries=1)
            class Counter:
                def __init__(self):
                    self.pid = os.getpid()

                def bump(self, x):
                    return (x + 1, os.getpid())

            c = Counter.remote()
            v1, pid1 = ray_tpu.get(c.bump.remote(1), timeout=60)
            assert v1 == 2
            # second call: the worker dies mid-call, the runtime restarts
            # the actor and REPLAYS the call — it must still complete
            v2, pid2 = ray_tpu.get(c.bump.remote(2), timeout=120)
            assert v2 == 3
            assert pid2 != pid1, "call must have replayed on a fresh " \
                                 "incarnation (the old worker was killed)"
        finally:
            cfg.test_fault_spec = ""
            fault_injection.reset()
            ray_tpu.shutdown()

    def test_exhausted_restarts_fail_attributed_never_bare_timeout(self):
        ray_tpu.init(num_cpus=2, num_tpus=0)
        try:

            @ray_tpu.remote  # max_restarts=0
            class Frail:
                def pid(self):
                    return os.getpid()

                def work(self):
                    return "ok"

            a = Frail.remote()
            pid = ray_tpu.get(a.pid.remote(), timeout=60)
            os.kill(pid, signal.SIGKILL)
            with pytest.raises(ActorDiedError) as ei:
                ray_tpu.get(a.work.remote(), timeout=60)
            # cause attribution: node hex + worker pid, never a bare
            # timeout (the shared exceptions.format_death_cause contract)
            msg = str(ei.value)
            assert "node " in msg and "pid" in msg, msg
        finally:
            ray_tpu.shutdown()


# --------------------------------------------------------------------------
# compiled DAG: killed executor never wedges — attributed fail or rebind
# --------------------------------------------------------------------------


class TestCompiledDagExecutorDeath:
    def test_permanent_death_fails_every_outstanding_ref_attributed(self):
        ray_tpu.init(num_cpus=3, num_tpus=0)
        try:

            @ray_tpu.remote
            class S:
                def pid(self):
                    return os.getpid()

                def inc(self, x):
                    if x != 1:  # every round after the warm-up is still
                        time.sleep(30)  # in the executor when the kill lands
                    return x + 1

            s = S.remote()
            pid = ray_tpu.get(s.pid.remote(), timeout=60)
            from ray_tpu.dag import InputNode

            with InputNode() as inp:
                out = s.inc.bind(inp)
            dag = out.experimental_compile(max_inflight=4)
            assert dag.execute(1).get(timeout=60) == 2
            r1, r2 = dag.execute(2), dag.execute(3)
            os.kill(pid, signal.SIGKILL)
            for r in (r1, r2):
                with pytest.raises(ActorDiedError) as ei:
                    r.get(timeout=30)
                assert "executor died" in str(ei.value)
                assert ei.value.restarting is False
            # ...and get() is idempotent on the failure
            with pytest.raises(ActorDiedError):
                r1.get(timeout=5)
            # future executes fail fast with the same attribution: the
            # DAG is broken, not wedged
            with pytest.raises(ActorDiedError):
                dag.execute(4)
            dag.teardown()  # clean, bounded
        finally:
            ray_tpu.shutdown()

    def test_restarted_executor_rebinds_fresh_rings(self):
        ray_tpu.init(num_cpus=3, num_tpus=0)
        try:

            @ray_tpu.remote(max_restarts=1)
            class S:
                def pid(self):
                    return os.getpid()

                def inc(self, x):
                    if x == 7:  # the round the kill must find in flight
                        time.sleep(30)
                    return x + 1

            @ray_tpu.remote
            class T:
                def dbl(self, x):
                    return x * 2

            s, t = S.remote(), T.remote()
            pid = ray_tpu.get(s.pid.remote(), timeout=60)
            from ray_tpu.dag import InputNode

            with InputNode() as inp:
                out = t.dbl.bind(s.inc.bind(inp))
            dag = out.experimental_compile(max_inflight=2)
            assert dag.execute(5).get(timeout=60) == 12
            ref = dag.execute(7)
            os.kill(pid, signal.SIGKILL)
            # the in-flight round died inside the graph: attributed, with
            # the restarting flag up (the actor has restart budget)
            with pytest.raises(ActorDiedError) as ei:
                ref.get(timeout=30)
            assert ei.value.restarting is True
            # once the incarnation is back, execute() rebinds fresh ring
            # channels transparently and the graph serves again
            deadline = time.monotonic() + 60
            value = None
            while time.monotonic() < deadline:
                try:
                    value = dag.execute(9, timeout=20).get(timeout=30)
                    break
                except ActorDiedError:
                    time.sleep(0.3)  # still restarting: retry the submit
            assert value == 20
            dag.teardown()
        finally:
            ray_tpu.shutdown()


# --------------------------------------------------------------------------
# net rings: wire.send.* drops on the cross-host data plane
# --------------------------------------------------------------------------


class TestNetRingWireFaults:
    """The ``wire.send.<tag>`` chaos point extends to the net-ring
    session messages (nrd/nra/nrrq/nrbase) — drive exactly the loss
    cases the ring-protocol-net model checker proved recoverable,
    through the REAL TCP transport."""

    def teardown_method(self):
        fault_injection.reset()
        global_config().test_fault_spec = ""

    def test_dropped_final_ack_does_not_wedge_send_window(self):
        """THE wedge the model checker's goal-reachability pass caught
        in the spec's first draft: n_slots=1, the single message is
        consumed, its ack — the FINAL ack, with no later traffic to
        piggyback on — is lost. Without the Go-Back-N re-ack rule the
        writer's window stays pinned shut forever while its
        retransmissions are silently dropped as stale. With it, the
        retransmitted stale seq draws a cumulative re-ack and the
        window reopens: the next write must succeed."""
        from ray_tpu.core import net_ring
        from ray_tpu.experimental.channel import TAG_BYTES

        reader = net_ring.create_reader("chaos_ack_ring", 1, 1 << 16)
        host = net_ring.ensure_host()
        w = net_ring.NetRingWriter.connect(
            host.address, host.authkey, "chaos_ack_ring", 1, 1 << 16)
        try:
            global_config().test_fault_spec = "wire.send.nra=drop@1"
            w.write(b"only", tag=TAG_BYTES, timeout=10)
            # consumed, but the ack for it is the drop@1 victim
            assert reader.read(timeout=10) == (TAG_BYTES, b"only")
            assert fault_injection.hits("wire.send.nra") >= 1
            wait_for(lambda: not w.writable() or w.acked == 1,
                     timeout=2, msg="ack state settled")
            # recovery is retransmit(stale seq) -> re-ack: the window
            # must reopen and the next write must go through end to end
            w.write(b"after", tag=TAG_BYTES, timeout=15)
            assert reader.read(timeout=15) == (TAG_BYTES, b"after")
            wait_for(lambda: w.acked == 2, timeout=10,
                     msg="window fully re-acked")
        finally:
            fault_injection.reset()
            w.close()
            reader.close()

    def test_dropped_data_messages_recover_in_cross_daemon_dag(self):
        """A cross-daemon compiled DAG keeps producing correct results
        while the chaos point drops driver-side net-ring data messages
        (every loss re-covered by retransmission)."""
        from ray_tpu.cluster_utils import Cluster

        c = Cluster(head_node_args={"num_cpus": 1})
        try:
            c.add_node(num_cpus=2, resources={"far": 2},
                       separate_process=True)

            @ray_tpu.remote(resources={"far": 1})
            class S:
                def inc(self, x):
                    return x + 1

            s = S.remote()
            from ray_tpu.dag import InputNode

            with InputNode() as inp:
                out = s.inc.bind(inp)
            dag = out.experimental_compile(max_inflight=4)
            assert dag.execute(0).get(timeout=60) == 1
            # drop every 3rd data message the DRIVER's net writer sends
            global_config().test_fault_spec = "wire.send.nrd=drop@3"
            for i in range(6):
                assert dag.execute(i).get(timeout=60) == i + 1
            assert fault_injection.hits("wire.send.nrd") >= 3
            dag.teardown()
        finally:
            fault_injection.reset()
            c.shutdown()


# --------------------------------------------------------------------------
# lineage reconstruction: store-resident result's sealing node dies
# --------------------------------------------------------------------------


class TestLineageReconstruction:
    def test_result_rederived_after_sealing_node_death(self,
                                                       ray_start_cluster):
        c = ray_start_cluster
        n2 = c.add_node(num_cpus=2, resources={"side": 2})
        import numpy as np

        @ray_tpu.remote(resources={"side": 1})
        def produce(tag):
            return np.full(300_000, tag, dtype=np.uint8)

        ref = produce.remote(7)
        ray_tpu.wait([ref], timeout=60, fetch_local=False)
        locs = ray_tpu.get_object_locations([ref])[ref]
        assert locs == [n2.hex], "result must live on the doomed node"
        c.remove_node(n2)
        # the node (and the only copy) is gone: the get re-derives the
        # result by resubmitting the creating task from lineage — but the
        # task NEEDS the side resource, so give it a new home first
        c.add_node(num_cpus=2, resources={"side": 2})
        v = ray_tpu.get(ref, timeout=120)
        assert v.shape == (300_000,) and int(v[0]) == 7


# --------------------------------------------------------------------------
# head bounce: the PR-7 owner tables replay (satellite: 2-daemon cluster,
# streams + pins in flight, zero lost objects, zero steady-state RPC delta)
# --------------------------------------------------------------------------


@pytest.fixture
def bounced_cluster(tmp_path):
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_node_args={"num_cpus": 1,
                                "storage": str(tmp_path / "gcs")})
    daemons = [
        c.add_node(num_cpus=1, resources={"d1": 10}, separate_process=True),
        c.add_node(num_cpus=1, resources={"d2": 10}, separate_process=True),
    ]
    yield c, daemons
    c.shutdown()


class TestHeadBounce:
    def test_owner_tables_replay_across_bounce(self, bounced_cluster):
        c, (n1, n2) = bounced_cluster
        head = c.head
        hexes = {n1.hex, n2.hex}

        @ray_tpu.remote(resources={"d1": 1}, max_restarts=0)
        class Gen:
            def stream(self, n):
                for i in range(n):
                    time.sleep(0.1)
                    yield i

            def echo(self, x):
                return x

        g = Gen.remote()
        assert ray_tpu.get(g.echo.remote("warm"), timeout=90) == "warm"

        # pre-bounce state the bounce must not lose:
        # (a) a large object sealed on each daemon
        import numpy as np

        @ray_tpu.remote(resources={"d2": 1})
        def big(tag):
            return np.full(300_000, tag, dtype=np.uint8)

        obj_refs = [big.remote(3)]
        ray_tpu.wait(obj_refs, timeout=90, fetch_local=False)
        # (b) a stream mid-flight (items keep arriving through the bounce
        # over the owner reply chain — the head is not on that path)
        gen = g.stream.options(num_returns="streaming").remote(30)

        # consume a few items, then bounce the head under the traffic
        it = iter(gen)
        first = ray_tpu.get(next(it), timeout=90)
        assert first == 0
        head.bounce()

        # daemons detect the bounce and re-register under the SAME hexes
        wait_for(lambda: hexes <= set(head.nodes), 60,
                 "daemons to re-register after bounce")
        assert {h for h in head.nodes if h in hexes} == hexes

        # zero lost stream items: the rest of the stream drains in order
        got = [first] + [ray_tpu.get(r, timeout=90) for r in it]
        assert got == list(range(30))

        # zero lost objects: the pre-bounce object is still resolvable
        # (directory replayed from the daemon's store manifest)
        v = ray_tpu.get(obj_refs[0], timeout=90)
        assert int(v[0]) == 3 and v.shape == (300_000,)

        # the actor plane converged: calls still flow (same incarnation)
        assert ray_tpu.get(g.echo.remote("post"), timeout=90) == "post"

        # steady state after convergence is head-free again: actor calls
        # + stream consumption move the head-RPC counter by ZERO
        before = _head_rpcs()
        for i in range(5):
            assert ray_tpu.get(g.echo.remote(i), timeout=90) == i
        assert _head_rpcs() - before == 0

    def test_deferred_delete_survives_bounce_exactly_once(
            self, bounced_cluster):
        """An in-flight pinned arg defers its cluster-wide delete; the
        bounce must neither lose the delete (leak) nor double/early-apply
        it (the executing task would lose its arg)."""
        c, (n1, _n2) = bounced_cluster
        head = c.head
        import numpy as np

        payload = ray_tpu.put(np.ones(300_000, dtype=np.uint8))
        oid = payload.id

        @ray_tpu.remote
        def slow_consume(arr, delay):  # plain CPU: direct (owner) path
            time.sleep(delay)
            return int(arr.sum())

        res = slow_consume.remote(payload, 4.0)
        # dropping the driver ref now defers the delete behind the
        # owner-side in-flight arg pin (PR-7 table)
        del payload
        wait_for(lambda: oid in head._deferred_deletes, 30,
                 "deferred delete parked behind the in-flight pin")
        head.bounce()
        # the deferred delete survived the bounce (durable meta)
        assert oid in head._deferred_deletes
        # the task completes with its arg intact — the delete did NOT
        # apply early...
        assert ray_tpu.get(res, timeout=120) == 300_000
        # ...and once the lease releases, the delete applies for good
        wait_for(lambda: oid not in head._deferred_deletes, 60,
                 "deferred delete applied after settle")
        wait_for(lambda: not head.gcs.get_object_locations(oid), 60,
                 "object bytes released cluster-wide")


# --------------------------------------------------------------------------
# kill matrix (slow tier): each role killed mid-pipeline
# --------------------------------------------------------------------------


@pytest.mark.slow
class TestKillMatrix:
    def test_daemon_killed_mid_stream_fails_attributed(self, tmp_path):
        """Killing the daemon HOSTING a stream's executor mid-flight must
        surface an attributed error (or a clean end), never a hang."""
        from ray_tpu.cluster_utils import Cluster

        c = Cluster(head_node_args={"num_cpus": 1})
        try:
            c.add_node(num_cpus=1, resources={"d1": 10},
                       separate_process=True)
            proxy = next(n for n in c.head.nodes.values()
                         if getattr(n, "pid", None) is not None
                         and not hasattr(n, "store"))

            @ray_tpu.remote(resources={"d1": 1})
            class G:
                def stream(self, n):
                    for i in range(n):
                        time.sleep(0.2)
                        yield i

            g = G.remote()
            gen = g.stream.options(num_returns="streaming").remote(50)
            it = iter(gen)
            assert ray_tpu.get(next(it), timeout=90) == 0
            os.kill(proxy.pid, signal.SIGKILL)
            with pytest.raises(Exception) as ei:
                # remaining items: the owner learns the executor died
                for r in it:
                    ray_tpu.get(r, timeout=90)
            assert not isinstance(ei.value, TimeoutError), \
                "death must be reported, not timed out"
        finally:
            c.shutdown()

    def test_worker_crash_spec_is_reproducible(self):
        """The same fault spec against the same workload kills the same
        operation run after run (the determinism contract)."""
        cfg = global_config()
        for _round in range(2):
            cfg.test_fault_spec = "worker.exec.boom=raise@2"
            try:
                ray_tpu.init(num_cpus=1, num_tpus=0)

                @ray_tpu.remote(max_restarts=1, max_task_retries=1)
                class B:
                    def boom(self, i):
                        return i

                b = B.remote()
                # hit 1 fine; hit 2 raises FaultInjected inside the task
                assert ray_tpu.get(b.boom.remote(1), timeout=60) == 1
                with pytest.raises(Exception) as ei:
                    ray_tpu.get(b.boom.remote(2), timeout=60)
                assert "fault injected" in str(ei.value)
            finally:
                cfg.test_fault_spec = ""
                fault_injection.reset()
                ray_tpu.shutdown()


# --------------------------------------------------------------------------
# serve compiled dispatch plane: replica death mid-RPS-ramp
# --------------------------------------------------------------------------


class TestServeCompiledChaos:
    """The serve-plane chaos drill (ROADMAP "chaos-drill the SERVE
    plane"): a replica hard-killed mid-traffic via the deterministic
    fault spec must surface as an attributed ActorDiedError (never a
    wedge, never a bare timeout), and the compiled lane must serve the
    restarted incarnation again."""

    def _planes(self, serve, name):
        from ray_tpu.serve import observability as obs

        obs.drain_deferred()
        return serve.status().get(name, {}).get("dispatch_planes", {})

    def test_replica_crash_surfaces_attributed_then_recovers(self):
        cfg = global_config()
        # the 6th compiled batch on any one incarnation dies mid-dispatch
        cfg.test_fault_spec = "dag.exec.handle_request_compiled_batch=crash@6"
        try:
            ray_tpu.init(num_cpus=4, num_tpus=0)
            from ray_tpu import serve

            serve.start(serve.HTTPOptions(port=18572))

            @serve.deployment(max_inflight=4, retry_on_replica_failure=False,
                              ray_actor_options={"max_restarts": 3})
            class M:
                def work(self, x):
                    return (x, os.getpid())

            h = serve.run(M.bind(), route_prefix=None)
            _, pid1 = h.work.remote(0).result(timeout=60)

            def engaged():
                h.work.remote(0).result(timeout=30)
                return self._planes(serve, "M").get("compiled", 0) >= 1

            wait_for(engaged, timeout=60, msg="compiled plane engaged")
            # closed-loop ramp: every request gets a bounded reply — ok
            # or an ATTRIBUTED error; a wedge would blow the per-request
            # timeout (surfacing as TimeoutError = test failure)
            died = 0
            recovered_pid = None
            deadline = time.monotonic() + 120
            i = 0
            while time.monotonic() < deadline and recovered_pid is None:
                i += 1
                try:
                    _, pid = h.work.remote(i).result(timeout=30)
                    if pid != pid1:
                        recovered_pid = pid
                except ActorDiedError as e:
                    died += 1
                    msg = str(e)
                    assert "executor" in msg or "actor" in msg, msg
                    assert "timed out" not in msg.lower()
            assert died >= 1, "the crash never surfaced as ActorDiedError"
            assert recovered_pid is not None, \
                "the restarted replica never served"
            # the recovered replica serves on the COMPILED plane again
            # (the lane rebound to the new incarnation)
            base = self._planes(serve, "M").get("compiled", 0)

            def compiled_grows():
                try:
                    h.work.remote(999).result(timeout=30)
                except ActorDiedError:
                    pass  # racing a second scheduled crash: keep waiting
                return self._planes(serve, "M").get("compiled", 0) > base

            wait_for(compiled_grows, timeout=60,
                     msg="compiled plane serving after restart")
            serve.shutdown()
        finally:
            cfg.test_fault_spec = ""
            fault_injection.reset()
            ray_tpu.shutdown()

    def test_retrying_deployment_loses_no_request(self):
        """With replica-failure retry on (the default), the crash is
        invisible to callers: every in-flight request either completed
        or was redispatched — zero lost, zero errors."""
        cfg = global_config()
        cfg.test_fault_spec = "dag.exec.handle_request_compiled_batch=crash@5"
        try:
            ray_tpu.init(num_cpus=4, num_tpus=0)
            from ray_tpu import serve

            serve.start(serve.HTTPOptions(port=18573))

            @serve.deployment(max_inflight=4,
                              ray_actor_options={"max_restarts": 3})
            class R:
                def work(self, x):
                    return (x, os.getpid())

            h = serve.run(R.bind(), route_prefix=None)
            pids = set()
            for i in range(12):
                v, pid = h.work.remote(i).result(timeout=120)
                assert v == i
                pids.add(pid)
            assert len(pids) >= 2, \
                "the fault spec should have crashed one incarnation"
            serve.shutdown()
        finally:
            cfg.test_fault_spec = ""
            fault_injection.reset()
            ray_tpu.shutdown()
