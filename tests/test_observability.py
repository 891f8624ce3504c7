"""State API, metrics pipeline, log tail-to-driver.

Reference: python/ray/util/state/api.py, ray.util.metrics +
metrics_agent.py Prometheus re-export, log_monitor.py:581.
"""

import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.core import api
from ray_tpu.util import state
from ray_tpu.util.metrics import Counter, Gauge, Histogram, registry, \
    render_prometheus


def test_state_api_lists(ray_start_regular):
    @ray_tpu.remote
    def f():
        return 1

    @ray_tpu.remote
    class A:
        def m(self):
            return 2

    a = A.options(name="obs_actor").remote()
    ray_tpu.get([f.remote(), f.remote(), a.m.remote()])

    tasks = state.list_tasks()
    assert any(t["name"] == "f" and t["state"] == "FINISHED" for t in tasks)
    actors = state.list_actors()
    assert any(x["state"] == "ALIVE" and x["name"] == "obs_actor"
               for x in actors)
    nodes = state.list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]
    summary = state.summarize_tasks()
    assert summary["f"]["FINISHED"] == 2
    # worker-side query goes through the RPC passthrough
    @ray_tpu.remote
    def from_worker():
        from ray_tpu.util import state as s

        return len(s.list_nodes())

    assert ray_tpu.get(from_worker.remote()) == 1


def test_metrics_prometheus_endpoint(ray_start_regular):
    head = api._get_head()
    host, port = head.start_metrics_server()
    Counter("test_counter_total", "a counter").inc(2.0, tags={"k": "v"})
    Gauge("test_gauge", "a gauge").set(7.5)
    Histogram("test_hist", "a histogram", boundaries=[1, 10]).observe(3.0)
    body = urllib.request.urlopen(
        f"http://{host}:{port}/metrics").read().decode()
    assert 'test_counter_total{k="v"} 2.0' in body
    assert "test_gauge 7.5" in body
    assert "test_hist_count 1" in body
    assert 'test_hist_bucket' in body
    # runtime task metrics recorded by the head
    assert "ray_tpu_tasks_total" in body


def test_worker_metrics_merge():
    """Worker snapshots merge under a source key; counters sum."""
    reg = registry()
    reg.merge("w1", {"m_total": {"type": "counter", "help": "h",
                                 "buckets": None,
                                 "values": {(): 3.0}}})
    reg.merge("w2", {"m_total": {"type": "counter", "help": "h",
                                 "buckets": None,
                                 "values": {(): 4.0}}})
    text = render_prometheus(reg)
    assert "m_total 7.0" in text


def test_log_to_driver(ray_start_regular, capfd):
    @ray_tpu.remote
    def shout():
        print("LOUD_MARKER_123")
        return 1

    ray_tpu.get(shout.remote())
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        err = capfd.readouterr().err
        if "LOUD_MARKER_123" in err:
            assert "pid=" in err
            return
        time.sleep(0.2)
    pytest.fail("worker stdout was not tailed to the driver")


def test_render_prometheus_escapes_label_values():
    """Exposition format: label values escape backslash, quote, newline —
    a raw quote used to produce an unparseable scrape."""
    from ray_tpu.util.metrics import _Registry

    reg = _Registry()
    evil = 'he said "hi"\\path\nnextline'
    reg.record("esc_total", "counter", "a counter", (("k", evil),), 1.0,
               mode="add")
    text = render_prometheus(reg)
    assert 'k="he said \\"hi\\"\\\\path\\nnextline"' in text
    from prom_parser import parse_exposition

    samples = parse_exposition(text)
    (name, labels, value), = samples
    assert name == "esc_total" and value == 1.0
    assert labels["k"] == evil  # round-trips through escape + parse


def test_render_prometheus_escapes_help_text():
    from ray_tpu.util.metrics import _Registry

    reg = _Registry()
    reg.record("help_esc", "gauge", "line1\nline2", (), 1.0)
    text = render_prometheus(reg)
    assert "# HELP help_esc line1\\nline2" in text
    assert all(not ln or ln.startswith(("#", "help_esc"))
               for ln in text.split("\n"))


@pytest.mark.slow  # >5s on the 1-core box: full-tier only (tier-1 wall budget)
def test_metrics_endpoint_scrape_parses_end_to_end(ray_start_regular):
    """Scrape the head /metrics endpoint and validate EVERY line against
    the exposition grammar (guards the escaping fix and any future
    metric additions)."""
    from prom_parser import parse_exposition

    head = api._get_head()
    host, port = head.start_metrics_server()
    Counter("scrape_total", "desc with \"quotes\" and \\slashes").inc(
        1.0, tags={"path": 'a"b\\c', "multi": "x\ny"})
    Gauge("scrape_gauge", "g").set(2.5, tags={"node": "n-1"})
    Histogram("scrape_hist", "h", boundaries=[0.1, 1]).observe(0.5)

    @ray_tpu.remote
    def worker_metric():
        Counter("scrape_worker_total", "from a worker").inc(
            3.0, tags={"who": 'w"orker'})
        return 1

    ray_tpu.get(worker_metric.remote())
    # worker metrics flush on an interval; force one more local change and
    # poll the scrape until the worker counter lands (or accept head-only)
    deadline = time.monotonic() + 8
    body = ""
    while time.monotonic() < deadline:
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics").read().decode()
        if "scrape_worker_total" in body:
            break
        time.sleep(0.25)

    samples = parse_exposition(body)  # raises on ANY malformed line
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert any(lbl == {"path": 'a"b\\c', "multi": "x\ny"}
               for lbl, _v in by_name["scrape_total"])
    assert ("scrape_hist_bucket" in by_name
            and "scrape_hist_count" in by_name)
    assert any(lbl.get("le") == "+Inf"
               for lbl, _ in by_name["scrape_hist_bucket"])


def test_report_thread_survives_send_failures():
    """A transient send_fn failure must not kill the worker's metrics
    report thread; it logs once and retries next interval."""
    from ray_tpu.util.metrics import start_report_thread

    Counter("retry_probe_total", "x").inc()
    calls = []
    delivered = []

    def flaky_send(snap):
        calls.append(1)
        if len(calls) <= 2:
            raise ConnectionError("channel blip")
        delivered.append(snap)

    stop = start_report_thread(flaky_send, interval_s=0.05)
    try:
        deadline = time.monotonic() + 10
        while not delivered and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(calls) >= 3  # kept retrying past the failures
        assert delivered and "retry_probe_total" in delivered[0]
    finally:
        stop.set()


class TestRegistrySourceLifecycle:
    """retire()/merge(): worker death folds counters/histograms into the
    _retired accumulator monotonically and drops stale gauges."""

    def _merge_worker(self, reg, src, counter=5.0, gauge=1.0):
        reg.merge(src, {
            "w_total": {"type": "counter", "help": "h", "buckets": None,
                        "values": {(("k", "v"),): counter}},
            "w_gauge": {"type": "gauge", "help": "h", "buckets": None,
                        "values": {(): gauge}},
            "w_hist": {"type": "histogram", "help": "h", "buckets": [1.0],
                       "values": {(): {"sum": 0.5, "count": 2,
                                       "le": {1.0: 2}}}},
        })

    def test_retire_folds_counters_and_histograms_drops_gauges(self):
        from ray_tpu.util.metrics import _Registry

        reg = _Registry()
        self._merge_worker(reg, "n1:100")
        text = render_prometheus(reg)
        assert 'w_total{k="v"} 5.0' in text
        assert "w_gauge" in text and "source=" in text

        reg.retire("n1:100")
        retired = reg.metrics["w_total"]["sources"]["_retired"]
        assert retired[(("k", "v"),)] == 5.0
        hist_retired = reg.metrics["w_hist"]["sources"]["_retired"]
        assert hist_retired[()]["count"] == 2
        assert hist_retired[()]["sum"] == 0.5
        assert hist_retired[()]["le"][1.0] == 2
        # gauges: dropped, not folded
        assert "n1:100" not in reg.metrics["w_gauge"]["sources"]
        assert "_retired" not in reg.metrics["w_gauge"]["sources"]
        text = render_prometheus(reg)
        assert 'w_total{k="v"} 5.0' in text  # sum survives the death
        assert 'w_gauge' not in text.split("# TYPE w_gauge gauge")[-1] \
            .split("#")[0].strip()

    def test_retire_is_monotonic_across_source_reuse(self):
        """node:pid reuse after a death must never make sums go down."""
        from ray_tpu.util.metrics import _Registry

        reg = _Registry()
        self._merge_worker(reg, "n1:100", counter=5.0)
        reg.retire("n1:100")
        # same source id reappears (pid reuse), reports fresh values
        self._merge_worker(reg, "n1:100", counter=2.0)
        text = render_prometheus(reg)
        assert 'w_total{k="v"} 7.0' in text  # retired 5 + live 2
        reg.retire("n1:100")
        retired = reg.metrics["w_total"]["sources"]["_retired"]
        assert retired[(("k", "v"),)] == 7.0  # accumulates, never resets
        hist = reg.metrics["w_hist"]["sources"]["_retired"]
        assert hist[()]["count"] == 4 and hist[()]["le"][1.0] == 4

    def test_retire_unknown_source_is_noop(self):
        from ray_tpu.util.metrics import _Registry

        reg = _Registry()
        self._merge_worker(reg, "n1:100")
        reg.retire("n9:999")
        assert reg.metrics["w_total"]["sources"]["n1:100"] \
            [(("k", "v"),)] == 5.0

    def test_worker_death_retires_metrics_end_to_end(self, monkeypatch):
        """An actor's counter keeps contributing to the merged sum after
        the actor (its worker) dies; its gauge disappears."""
        from ray_tpu.core.config import global_config

        # short report interval so the worker's snapshot lands fast (the
        # config snapshot ships to workers at init)
        monkeypatch.setattr(global_config(),
                            "metrics_report_interval_ms", 300)
        ray_tpu.init(num_cpus=2, num_tpus=0)
        try:
            @ray_tpu.remote
            class Emitter:
                def bump(self):
                    Counter("life_total", "c").inc(4.0)
                    Gauge("life_gauge", "g").set(1.0)
                    return 1

            a = Emitter.remote()
            assert ray_tpu.get(a.bump.remote()) == 1
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if "life_total" in render_prometheus(registry()):
                    break
                time.sleep(0.1)
            assert "life_total" in render_prometheus(registry())
            def gauge_samples():
                # sample lines only (HELP/TYPE comments legitimately stay)
                return [ln for ln in
                        render_prometheus(registry()).splitlines()
                        if ln.startswith("life_gauge")]

            ray_tpu.kill(a)
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if "life_total 4.0" in render_prometheus(registry()) \
                        and not gauge_samples():
                    break
                time.sleep(0.1)
            text = render_prometheus(registry())
            assert "life_total 4.0" in text  # folded into _retired
            assert not gauge_samples()  # stale gauge samples dropped
        finally:
            ray_tpu.shutdown()


class TestHistogramPercentiles:
    """Histogram.percentile()/summary() over merged bucket counts (the
    serve.status() aggregation helper)."""

    def test_percentile_interpolates_within_bucket(self):
        from ray_tpu.util.metrics import percentile_from_buckets

        # 10 observations uniform in (0, 1]: buckets 0.5 -> 5, 1.0 -> 10
        le = {0.5: 5, 1.0: 10}
        assert percentile_from_buckets(le, 10, 0.5) == pytest.approx(0.5)
        # p90 -> rank 9, inside the (0.5, 1.0] bucket: 0.5 + 0.5 * 4/5
        assert percentile_from_buckets(le, 10, 0.9) == pytest.approx(0.9)
        # rank in the +Inf bucket returns the highest finite bound
        assert percentile_from_buckets({0.5: 5, 1.0: 8}, 10, 0.99) == 1.0
        assert percentile_from_buckets({}, 0, 0.5) is None

    def test_histogram_percentile_merges_sources(self):
        from ray_tpu.util.metrics import (Histogram, _Registry,
                                          histogram_summary)

        reg = _Registry()
        reg.record("lat_s", "histogram", "h", (("d", "x"),), 0.05,
                   mode="observe", buckets=[0.1, 1.0])
        # a worker's snapshot of the same series merges in
        reg.merge("w1", {"lat_s": {
            "type": "histogram", "help": "h", "buckets": [0.1, 1.0],
            "values": {(("d", "x"),): {"sum": 1.5, "count": 3,
                                       "le": {0.1: 0, 1.0: 3}}}}})
        h = Histogram("lat_s", boundaries=[0.1, 1.0])
        # 4 total: 1 in (0, 0.1], 3 in (0.1, 1.0]
        p = h.percentile(0.5, tags={"d": "x"}, reg=reg)
        assert 0.1 < p <= 1.0
        assert h.percentile(0.1, tags={"d": "x"}, reg=reg) \
            == pytest.approx(0.04)
        assert h.percentile(0.5, tags={"d": "zzz"}, reg=reg) is None
        summ = histogram_summary("lat_s", reg=reg)[(("d", "x"),)]
        assert summ["count"] == 4
        assert summ["avg"] == pytest.approx((0.05 + 1.5) / 4)
        assert set(summ) >= {"p50", "p95", "p99"}


class TestStrictHistogramParsing:
    """prom_parser.parse_histograms: conformant families parse; the real
    renderer failure modes raise."""

    GOOD = (
        "# HELP h desc\n# TYPE h histogram\n"
        'h_bucket{le="0.1"} 1\nh_bucket{le="1"} 3\n'
        'h_bucket{le="+Inf"} 4\nh_sum 2.5\nh_count 4\n')

    def test_rendered_histograms_conform(self):
        from prom_parser import parse_histograms

        from ray_tpu.util.metrics import _Registry

        reg = _Registry()
        reg.record("rt_h", "histogram", "h", (("k", "v"),), 0.05,
                   mode="observe", buckets=[0.1, 1.0])
        reg.record("rt_h", "histogram", "h", (("k", "v"),), 7.0,
                   mode="observe", buckets=[0.1, 1.0])
        fams = parse_histograms(render_prometheus(reg))
        (series,), = [fams["rt_h"]]
        assert series["labels"] == {"k": "v"}
        assert series["count"] == 2 and series["buckets"]["+Inf"] == 2

    def test_good_family_parses(self):
        from prom_parser import parse_histograms

        fams = parse_histograms(self.GOOD)
        assert fams["h"][0]["buckets"] == {"0.1": 1, "1": 3, "+Inf": 4}

    @pytest.mark.parametrize("mutation, why", [
        (lambda t: t.replace('h_bucket{le="+Inf"} 4\n', ""), "no +Inf"),
        (lambda t: t.replace("h_count 4", "h_count 5"),
         "+Inf != count"),
        (lambda t: t.replace('h_bucket{le="1"} 3', 'h_bucket{le="1"} 0'),
         "decreasing cumulative counts"),
        (lambda t: t.replace("h_sum 2.5\n", ""), "missing _sum"),
        (lambda t: t.replace('le="0.1"', 'le="abc"'), "bad le value"),
    ])
    def test_violations_raise(self, mutation, why):
        from prom_parser import PromParseError, parse_histograms

        with pytest.raises(PromParseError):
            parse_histograms(mutation(self.GOOD))
        assert why  # parametrize label


def test_sampling_profiler_collapsed_stack_format(tmp_path):
    """Dumps are collapsed-stack: root-first, ';'-separated frames, one
    'stack count' line each, full counts (no top-N cut)."""
    import re
    import threading

    from ray_tpu.util import sampling_profiler

    stop_busy = threading.Event()

    def _obs_busy_leaf():
        x = 0
        while not stop_busy.is_set():
            x += 1
        return x

    t = threading.Thread(target=_obs_busy_leaf, name="busy")
    t.start()
    path = tmp_path / "prof.out"
    dump = sampling_profiler.start(str(path), interval_s=0.001, depth=16)
    time.sleep(0.3)
    stop_busy.set()
    dump()
    t.join(timeout=2)
    lines = [ln for ln in path.read_text().splitlines() if ln]
    assert lines
    pat = re.compile(r"^\S+ \d+$")
    assert all(pat.match(ln) for ln in lines)
    busy_lines = [ln for ln in lines if "_obs_busy_leaf" in ln]
    assert busy_lines
    stack = busy_lines[0].rsplit(" ", 1)[0].split(";")
    assert len(stack) > 1  # multi-frame, ';'-separated
    # root-first: the thread bootstrap sits before the busy function
    # (leaf-most frames last; the true leaf may be e.g. Event.is_set)
    busy_idx = max(i for i, fr in enumerate(stack)
                   if "_obs_busy_leaf" in fr)
    boot_idx = min(i for i, fr in enumerate(stack)
                   if "threading.py" in fr or "run" in fr)
    assert boot_idx < busy_idx
    assert "_obs_busy_leaf" not in stack[0]


def test_dashboard_serve_and_pubsub_endpoints():
    """Round-4 dashboard modules: /api/serve (deployment summary) and
    /api/pubsub (HTTP channel polling) — reference: dashboard/modules/
    serve + the pubsub surface."""
    import json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.util import pubsub

    ray_tpu.init(num_cpus=2)
    dash = None
    try:
        dash = start_dashboard(port=0)
        base = f"http://127.0.0.1:{dash.address[1]}"

        # no serve instance yet -> {}
        with urllib.request.urlopen(base + "/api/serve", timeout=10) as r:
            assert json.loads(r.read()) == {}

        @serve.deployment
        def hello(x):
            return "hi"

        # not the default port 8000: another xdist worker may hold it
        serve.start(serve.HTTPOptions(port=0))
        serve.run(hello.bind(), route_prefix=None)
        with urllib.request.urlopen(base + "/api/serve", timeout=30) as r:
            summary = json.loads(r.read())
        assert "hello" in summary
        assert summary["hello"]["num_replicas"] >= 1

        pubsub.publish("dash-chan", {"k": 1})
        pubsub.publish("dash-chan", {"k": 2})
        url = base + "/api/pubsub?channel=dash-chan&cursor=0&timeout=2"
        with urllib.request.urlopen(url, timeout=20) as r:
            body = json.loads(r.read())
        assert body["messages"] == [{"k": 1}, {"k": 2}]
        assert body["cursor"] == 2
    finally:
        if dash is not None:
            dash.stop()
        serve.shutdown()
        ray_tpu.shutdown()
