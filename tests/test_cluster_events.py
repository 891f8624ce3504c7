"""Structured cluster event log: emit -> buffer -> GCS ring -> state API /
dashboard / JSONL. Reference: the GCS cluster-event table behind
``ray list cluster-events`` + the export-event pipeline.
"""

import json
import os
import time
import urllib.request

import ray_tpu
from ray_tpu.util import events, state


# --------------------------------------------------------------- unit


class TestEventBuffer:
    def test_emit_without_sink_parks_bounded(self):
        buf = events._EventBuffer(maxlen=3)
        for i in range(5):
            buf.emit(events.ClusterEvent(
                ts=float(i), severity="INFO", source="T", entity_id="",
                message=f"m{i}"))
        assert len(buf._buf) == 3  # bounded pre-sink
        got = []
        buf.set_sink(got.extend)
        assert [e["message"] for e in got] == ["m2", "m3", "m4"]
        buf.clear_sink()

    def test_sink_failure_reparks_and_retries(self):
        buf = events._EventBuffer()
        calls = []

        def flaky(batch):
            calls.append(list(batch))
            if len(calls) == 1:
                raise ConnectionError("link down")

        buf.set_sink(flaky, flush_interval_s=0.05)
        buf.emit(events.ClusterEvent(ts=0.0, severity="INFO", source="T",
                                     entity_id="", message="x"))
        deadline = time.monotonic() + 5
        while len(calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(calls) >= 2
        assert calls[1][0]["message"] == "x"  # re-delivered after failure
        buf.clear_sink()

    def test_clear_sink_requires_match(self):
        buf = events._EventBuffer()
        sink = lambda b: None  # noqa: E731
        buf.set_sink(sink)
        buf.clear_sink(lambda b: None)  # different sink: no-op
        assert buf._sink is not None
        buf.clear_sink(sink)
        assert buf._sink is None

    def test_event_log_writer_rotates_at_size_cap(self, tmp_path):
        w = events.EventLogWriter(str(tmp_path), max_bytes=400)
        for i in range(20):
            w.write([{"ts": float(i), "severity": "INFO", "source": "T",
                      "entity_id": "", "message": "x" * 40, "attrs": {}}])
        w.close()
        main = tmp_path / "logs" / "events" / "events.jsonl"
        rotated = tmp_path / "logs" / "events" / "events.jsonl.1"
        assert rotated.exists()  # rotated at the cap
        assert main.stat().st_size < 500  # current file stays bounded
        # rotated + current together never exceed ~2x the cap
        assert main.stat().st_size + rotated.stat().st_size < 1200

    def test_filter_events(self):
        rows = [
            {"severity": "INFO", "source": "NODE", "message": "a"},
            {"severity": "WARNING", "source": "SCHEDULER", "message": "b"},
            {"severity": "ERROR", "source": "NODE", "message": "c"},
        ]
        assert [r["message"] for r in
                events.filter_events(rows, severity="warning")] == ["b"]
        assert [r["message"] for r in
                events.filter_events(rows, min_severity="WARNING")] == \
            ["b", "c"]
        assert [r["message"] for r in
                events.filter_events(rows, source="node")] == ["a", "c"]
        assert [r["message"] for r in events.filter_events(
            rows, source="NODE", min_severity="ERROR")] == ["c"]


# --------------------------------------------------------------- e2e


class _FakeProvider:
    """Records create/terminate calls without launching real daemons."""

    def __init__(self):
        self.nodes = []
        self.created = 0

    def create_node(self, node_config):
        pid = f"fake-{self.created}"
        self.created += 1
        self.nodes.append(pid)
        return pid

    def terminate_node(self, pid):
        if pid in self.nodes:
            self.nodes.remove(pid)

    def non_terminated_nodes(self):
        return list(self.nodes)

    def shutdown(self):
        self.nodes.clear()


def _wait_for(predicate, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = predicate()
        if got:
            return got
        time.sleep(0.25)
    return predicate()


def test_cluster_events_end_to_end():
    """Events from >= 5 distinct subsystems (node lifecycle, scheduler,
    autoscaler, serve, tune) land in one severity-filterable log, are
    served over /api/events, and persist as JSONL."""
    from ray_tpu import serve, tune
    from ray_tpu.autoscaler import Autoscaler, AutoscalerConfig
    from ray_tpu.core import api
    from ray_tpu.dashboard import start_dashboard

    ray_tpu.init(num_cpus=4, num_tpus=0)
    dash = None
    scaler = None
    try:
        head = api._get_head()

        # NODE: init already emitted node-alive; add/remove one for dead
        extra = head.add_node({"CPU": 1})
        head.remove_node(extra.hex)

        # SCHEDULER: an ask no node shape can ever fit
        @ray_tpu.remote(num_cpus=64)
        def impossible():
            return 1

        impossible.remote()  # never completes; infeasible event instead
        assert _wait_for(lambda: state.list_cluster_events(
            source="SCHEDULER", severity="WARNING"))

        # AUTOSCALER: the pending infeasible ask is feasible on the
        # provider's (bigger) node shape -> a launch decision
        provider = _FakeProvider()
        scaler = Autoscaler(head, provider, AutoscalerConfig(
            min_workers=0, max_workers=2, interval_s=9999,
            node_config={"num_cpus": 128}))
        scaler.update()
        assert provider.created >= 1
        assert state.list_cluster_events(source="AUTOSCALER")

        # SERVE: deploy -> controller (a worker actor) emits over the
        # worker channel
        @serve.deployment
        def hello(x):
            return "hi"

        # not the default port 8000: another xdist worker may hold it
        serve.start(serve.HTTPOptions(port=0))
        serve.run(hello.bind(), route_prefix=None)
        assert _wait_for(lambda: state.list_cluster_events(source="SERVE"))

        # TUNE: one tiny trial -> RUNNING + TERMINATED transitions
        def train_fn(config):
            tune.report({"score": config["x"]})

        tune.run(train_fn, config={"x": 1}, metric="score", mode="max",
                 storage_path=os.path.join(head.session_dir, "tune"))
        tune_events = _wait_for(
            lambda: state.list_cluster_events(source="TUNE"))
        assert any(e["attrs"].get("state") == "RUNNING"
                   for e in tune_events)
        assert any(e["attrs"].get("state") == "TERMINATED"
                   for e in tune_events)

        rows = state.list_cluster_events()
        sources = {e["source"] for e in rows}
        assert {"NODE", "SCHEDULER", "AUTOSCALER", "SERVE",
                "TUNE"} <= sources
        # severity filtering
        warnings = state.list_cluster_events(severity="WARNING")
        assert warnings and all(e["severity"] == "WARNING"
                                for e in warnings)
        assert any(e["source"] == "NODE" and "dead" in e["message"]
                   for e in warnings)
        floor = state.list_cluster_events(min_severity="WARNING")
        assert all(e["severity"] in ("WARNING", "ERROR") for e in floor)
        assert len(floor) >= len(warnings)

        # dashboard endpoint with filters
        dash = start_dashboard(port=0, with_jobs=False)
        base = f"http://127.0.0.1:{dash.address[1]}"
        with urllib.request.urlopen(
                base + "/api/events?source=NODE", timeout=10) as r:
            via_http = json.loads(r.read())
        assert via_http and all(e["source"] == "NODE" for e in via_http)
        with urllib.request.urlopen(
                base + "/api/events?min_severity=WARNING&limit=5",
                timeout=10) as r:
            capped = json.loads(r.read())
        assert len(capped) <= 5
        assert all(e["severity"] in ("WARNING", "ERROR") for e in capped)

        # JSONL persistence under session_dir/logs/events/
        events.flush()
        path = os.path.join(head.session_dir, "logs", "events",
                            "events.jsonl")
        assert os.path.isfile(path)
        with open(path) as f:
            persisted = [json.loads(line) for line in f]
        assert {"NODE", "SCHEDULER", "AUTOSCALER"} <= \
            {e["source"] for e in persisted}
        assert all({"ts", "severity", "source", "entity_id", "message",
                    "attrs"} <= set(e) for e in persisted)
    finally:
        if dash is not None:
            dash.stop()
        serve.shutdown()
        if scaler is not None:
            scaler.stop(terminate_nodes=True)
        ray_tpu.shutdown()


def test_event_log_disabled(monkeypatch):
    from ray_tpu.core.config import global_config

    monkeypatch.setattr(global_config(), "event_log_enabled", False)
    before = len(events._buffer._buf)
    events.emit("INFO", "TEST", "should be dropped")
    assert len(events._buffer._buf) == before


def test_worker_emitted_events_reach_head(ray_start_regular):
    """emit() inside a task rides the worker channel to the head ring."""
    @ray_tpu.remote
    def noisy():
        from ray_tpu.util import events as ev

        ev.emit("WARNING", "USERCODE", "worker-side event",
                entity_id="w1", detail=42)
        return 1

    assert ray_tpu.get(noisy.remote()) == 1
    got = _wait_for(lambda: state.list_cluster_events(source="USERCODE"))
    assert got and got[-1]["message"] == "worker-side event"
    assert got[-1]["attrs"]["detail"] == 42
    assert got[-1]["severity"] == "WARNING"
