"""TPU/JAX device telemetry: memory_stats gauges + jax.monitoring
listeners feeding the metrics registry."""

import pytest

import ray_tpu
from ray_tpu.util import device_telemetry
from ray_tpu.util.metrics import registry


class _FakeDevice:
    platform = "tpu"

    def __init__(self, device_id, in_use, peak):
        self.id = device_id
        self._stats = {"bytes_in_use": in_use,
                       "peak_bytes_in_use": peak}

    def memory_stats(self):
        return self._stats


class _StatlessDevice:
    platform = "cpu"
    id = 0

    def memory_stats(self):
        return None  # CPU backends typically report nothing


class _BrokenDevice:
    platform = "cpu"
    id = 1

    def memory_stats(self):
        raise NotImplementedError


def test_collect_device_stats_publishes_tagged_gauges():
    n = device_telemetry.collect_device_stats(
        [_FakeDevice(0, 1024, 4096), _FakeDevice(1, 2048, 8192),
         _StatlessDevice(), _BrokenDevice()],
        node_hex="abcdef0123456789")
    assert n == 2  # only devices that actually report stats
    snap = registry().snapshot()
    in_use = snap["ray_tpu_device_bytes_in_use"]["values"]
    key0 = (("device", "tpu:0"), ("node", "abcdef01"))
    key1 = (("device", "tpu:1"), ("node", "abcdef01"))
    assert in_use[key0] == 1024.0
    assert in_use[key1] == 2048.0
    peak = snap["ray_tpu_device_peak_bytes_in_use"]["values"]
    assert peak[key0] == 4096.0
    assert snap["ray_tpu_device_bytes_in_use"]["type"] == "gauge"


def test_collect_once_with_real_jax_is_safe():
    # collecting must never raise, whatever the backend reports
    import jax

    jax.devices()  # user code has brought the (CPU) backend up
    n = device_telemetry.collect_once(node_hex="deadbeef")
    assert n >= 0


def test_jax_monitoring_listeners_count_events():
    from jax._src import monitoring

    assert device_telemetry.install_jax_listeners()
    monitoring.record_event("/raytpu/test/event")
    monitoring.record_event("/raytpu/test/event")
    snap = registry().snapshot()
    vals = snap["ray_tpu_jax_events_total"]["values"]
    # key shape is ("event", ...) plus a ("node", ...) tag once any
    # runtime has stamped this process's node hex
    assert sum(v for k, v in vals.items()
               if ("event", "/raytpu/test/event") in k) == 2.0
    monitoring.record_event_duration_secs("/raytpu/test/duration", 0.5)
    snap = registry().snapshot()
    hv = snap["ray_tpu_jax_event_duration_seconds"]["values"]
    entry = next(v for k, v in hv.items()
                 if ("event", "/raytpu/test/duration") in k)
    assert entry["count"] == 1 and entry["sum"] == 0.5


def test_jit_compilation_is_counted_via_monitoring():
    """A real jax.jit compile fires monitoring events the listener
    counts (the 'is my run recompiling?' signal)."""
    import jax
    import jax.numpy as jnp

    assert device_telemetry.install_jax_listeners()
    before = _total_jax_events()

    @jax.jit
    def f(x):
        return x * 2 + 1

    f(jnp.arange(7)).block_until_ready()
    assert _total_jax_events() > before


_FOUR = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
}


def _ring_spans():
    from ray_tpu.util import flight_recorder as fr

    payload = fr.snapshot_payload()
    names = {int(sid): d["name"] for sid, d in payload["names"].items()}
    return [(names[int(sid)], t0, dur)
            for _seq, sid, kind, t0, dur, _tags in payload["events"]
            if kind == fr.KIND_SPAN]


@pytest.fixture()
def recorder_on():
    from ray_tpu.util import flight_recorder as fr

    was = fr._on[0]
    fr.configure(enabled=True)
    fr.reset_for_tests()
    yield fr
    fr.reset_for_tests()
    fr._on[0] = was


@pytest.mark.parametrize("event,span", sorted(_FOUR.items()))
def test_duration_listener_records_a_span_that_ends_at_the_call(
        recorder_on, event, span):
    """jax tells the listener a duration once the work has ended: the span
    ends at the listener's call and began ``duration`` before it. The
    histogram and ``compile_s`` keep reading what they read."""
    import time

    import jax

    def histogram():  # over every series of the event (a node tag or none)
        hv = registry().snapshot().get(
            "ray_tpu_jax_event_duration_seconds", {"values": {}})["values"]
        found = [v for k, v in hv.items() if ("event", event) in k]
        return (sum(v["count"] for v in found), sum(v["sum"] for v in found))

    jax.devices()  # process_device_report() is for a process with a backend
    before = device_telemetry.process_device_report()["compile_s"]
    count0, sum0 = histogram()
    lo = time.monotonic()
    device_telemetry._on_jax_event_duration(event, 1.25, fun_name="f")
    hi = time.monotonic()
    (name, t0, dur), = _ring_spans()
    assert name == span and dur == 1.25
    assert lo <= t0 + dur <= hi
    count1, sum1 = histogram()
    assert count1 - count0 == 1 and sum1 - sum0 == pytest.approx(1.25)
    after = device_telemetry.process_device_report()["compile_s"]
    moved = 1.25 if span == "jax.backend_compile" else 0.0
    assert after - before == pytest.approx(moved, abs=2e-3)


def test_duration_listener_records_no_span_for_another_event(recorder_on):
    device_telemetry._on_jax_event_duration(
        "/jax/compilation_cache/compile_time_saved_sec", 3.0)
    device_telemetry._on_jax_event_duration("/raytpu/test/duration", 3.0)
    assert _ring_spans() == []
    recorder_on.configure(enabled=False)
    try:  # recorder off: the histogram still counts, the ring stays empty
        device_telemetry._on_jax_event_duration(
            "/jax/core/compile/backend_compile_duration", 0.5)
        assert recorder_on.snapshot_payload()["events"] == []
    finally:
        recorder_on.configure(enabled=True)


def test_a_real_compile_nests_its_jax_spans(recorder_on):
    """A jit's first call: trace, lower and backend compile as spans in
    that order, each ending before the next one ends, from the listeners
    the import hook installs."""
    import jax
    import jax.numpy as jnp

    assert device_telemetry.install_jax_listeners()
    recorder_on.configure(min_span_us=0)
    try:
        jax.jit(lambda x: jnp.tanh(x) @ x.T + 3)(
            jnp.ones((5, 7))).block_until_ready()
    finally:
        recorder_on.configure(
            min_span_us=ray_tpu.core.config.global_config()
            .flight_recorder_min_span_us)
    ends = {}
    for name, t0, dur in _ring_spans():
        ends.setdefault(name, t0 + dur)
    assert {"jax.trace", "jax.lower", "jax.backend_compile"} <= set(ends)
    assert ends["jax.trace"] <= ends["jax.lower"] <= \
        ends["jax.backend_compile"]


def test_backend_devices_spans_only_the_first_touch(recorder_on):
    import jax

    jax.devices()  # the backend is up: this call brings nothing up
    assert device_telemetry.backend_devices() == jax.devices()
    assert _ring_spans() == []


def _total_jax_events() -> float:
    snap = registry().snapshot()
    m = snap.get("ray_tpu_jax_events_total")
    if m is None:
        return 0.0
    return sum(m["values"].values())


def test_two_daemon_compile_telemetry_reaches_head_history():
    """2-daemon e2e: worker jit compiles fire jax.monitoring events
    (listeners armed at process start via the import-observation hook)
    and HBM gauges; both ride the existing metrics channel and land in
    the head's /api/metrics/history rings with per-node tags."""
    import json
    import os
    import time
    import urllib.request

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.util.metrics import aggregate_series

    def wait_for(cond, timeout=90.0, msg="condition"):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.2)
        raise TimeoutError(f"timed out waiting for {msg}")

    os.environ["RAY_TPU_METRICS_REPORT_INTERVAL_MS"] = "200"
    c = Cluster(head_node_args={"num_cpus": 1})
    dash = None
    try:
        c.add_node(num_cpus=1, resources={"gdt1": 1},
                   separate_process=True)
        c.add_node(num_cpus=1, resources={"gdt2": 1},
                   separate_process=True)
        head = c.head

        # defined in-test so it cloudpickles BY VALUE (daemon workers
        # cannot import the test module)
        @ray_tpu.remote
        def compile_and_report():
            """Worker-side: a real jit compile (monitoring listeners
            were armed at runtime start by observe_jax_import, BEFORE
            jax loaded) plus one fake-HBM gauge stamped with this
            worker's real node hex."""
            import jax
            import jax.numpy as jnp

            jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)) \
                .block_until_ready()

            from ray_tpu.core.runtime import get_current_runtime
            from ray_tpu.util import device_telemetry as dt

            node = get_current_runtime().node_hex

            class Dev:  # CPU devices report no memory_stats; fake one
                platform = "tpu"
                id = 0

                def memory_stats(self):
                    return {"bytes_in_use": 12345.0,
                            "peak_bytes_in_use": 23456.0}

            dt.collect_device_stats([Dev()], node_hex=node)
            return node[:8]

        hex1 = ray_tpu.get(
            compile_and_report.options(resources={"gdt1": 1}).remote(),
            timeout=120)
        hex2 = ray_tpu.get(
            compile_and_report.options(resources={"gdt2": 1}).remote(),
            timeout=120)
        assert hex1 and hex2 and hex1 != hex2

        def compile_nodes():
            flat = aggregate_series(registry())
            nodes = set()
            for tags, v in flat.get("ray_tpu_jax_events_total", ()):
                d = dict(tags)
                if v > 0 and d.get("node") and "compil" in d.get(
                        "event", ""):
                    nodes.add(d["node"])
            return nodes

        def hbm_nodes():
            flat = aggregate_series(registry())
            return {dict(t).get("node")
                    for t, v in flat.get("ray_tpu_device_bytes_in_use", ())
                    if v == 12345.0}

        wait_for(lambda: {hex1, hex2} <= compile_nodes(),
                 msg="per-node compile events reported to head")
        wait_for(lambda: {hex1, hex2} <= hbm_nodes(),
                 msg="per-node HBM gauges reported to head")

        head.sample_metrics_history()
        dash = start_dashboard(port=0, with_jobs=False)
        base = f"http://127.0.0.1:{dash.address[1]}"

        def hist(name):
            url = f"{base}/api/metrics/history?name={name}"
            with urllib.request.urlopen(url, timeout=10) as r:
                assert r.status == 200
                return json.loads(r.read().decode())

        ev = hist("ray_tpu_jax_events_total")
        ev_nodes = {s["tags"].get("node") for s in ev["series"]}
        assert {hex1, hex2} <= ev_nodes
        hbm = hist("ray_tpu_device_bytes_in_use")
        hbm_by_node = {s["tags"].get("node"): s for s in hbm["series"]
                       if s["tags"].get("device") == "tpu:0"}
        assert {hex1, hex2} <= set(hbm_by_node)
        assert hbm_by_node[hex1]["points"][-1][1] == 12345.0
    finally:
        if dash is not None:
            dash.stop()
        os.environ.pop("RAY_TPU_METRICS_REPORT_INTERVAL_MS", None)
        c.shutdown()


def test_worker_device_telemetry_reaches_head(ray_start_regular):
    """A worker's device gauges ride the existing metrics channel; verify
    the collector runs worker-side without breaking task execution."""
    @ray_tpu.remote
    def collect_in_worker():
        from ray_tpu.util import device_telemetry as dt
        from ray_tpu.util.metrics import registry as reg

        n = dt.collect_once(node_hex="feedface")
        import jax  # force jax so collect_once has devices to look at

        del jax
        n2 = dt.collect_once(node_hex="feedface")
        snap = reg().snapshot()
        return n, n2, "ray_tpu_jax_events_total" in snap or n2 >= 0

    n, n2, ok = ray_tpu.get(collect_in_worker.remote())
    assert ok and n >= 0 and n2 >= 0
