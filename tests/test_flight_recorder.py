"""Flight recorder: ring mechanics, the min-RTT clock-offset
estimator, and the cross-host trace merge behind
``python -m ray_tpu timeline``.

Three layers of coverage. (1) Pure ring semantics — record/snapshot/
drain, the duration floor, capacity wrap with the torn-slot guard.
(2) Clock math on synthetic data — a skewed remote clock must be
recovered within the rtt/2 error bound, and two payloads whose anchors
disagree must land on one wall timeline after the per-node offset is
applied. (3) The real plumbing — a compiled DAG across two
separate-process daemons produces ONE merged trace containing span
events from every node, and a 2-stage MPMD pipeline's trace-derived
bubble fraction matches ``pipeline_stats()``.
"""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.config import global_config
from ray_tpu.util import flight_recorder as fr


def wait_for(cond, timeout=60.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture()
def clean_ring():
    """Fresh, enabled, floorless recorder; restores shared module state
    (other suites run against the defaults)."""
    saved_on, saved_min = fr._on[0], fr._min_dur[0]
    fr.reset_for_tests()
    fr.configure(enabled=True, min_span_us=0.0)
    yield
    fr.reset_for_tests()
    fr._on[0] = saved_on
    fr._min_dur[0] = saved_min


# --------------------------------------------------------------------------- #
# Ring semantics
# --------------------------------------------------------------------------- #


SP_A = fr.register_span("test.fr_a", tag_keys=("k",))
SP_B = fr.register_span("test.fr_b")
SP_EXEMPT = fr.register_span("test.fr_exempt", tag_keys=("k",),
                             floor_exempt=True)


def _names_of(payload):
    names = {int(k): v["name"] for k, v in payload["names"].items()}
    return [names[rec[1]] for rec in payload["events"]]


def test_record_snapshot_drain(clean_ring):
    t0 = fr.now()
    assert t0 > 0.0
    SP_A.end(t0, "v1")
    SP_B.end_at(fr.now(), 0.002)
    SP_B.instant("ignored-extra")

    snap = fr.snapshot_payload()
    assert sorted(_names_of(snap)) == ["test.fr_a", "test.fr_b",
                                       "test.fr_b"]
    # tags ride the record; the names table carries the tag keys
    a = [r for r in snap["events"] if r[1] == SP_A.sid][0]
    assert a[5] == ("v1",)
    assert snap["names"][SP_A.sid]["tag_keys"] == ["k"]
    assert snap["pid"] and snap["anchor_wall"] > 0

    # drain consumes; a second drain with nothing new returns None
    batch = fr.drain()
    assert batch is not None and len(batch["events"]) == 3
    assert fr.drain() is None
    # snapshot is non-consuming: records are still visible
    assert len(fr.snapshot_payload()["events"]) == 3


def test_duration_floor_filters_short_spans(clean_ring):
    fr.configure(min_span_us=1000.0)
    SP_B.end_at(fr.now(), 0.0002)          # 200 us: below the floor
    assert fr.snapshot_payload()["events"] == []
    SP_B.end_at(fr.now(), 0.002)           # 2 ms: above
    t0 = fr.now()
    time.sleep(0.003)
    SP_B.end(t0)                           # closed-now path, above
    SP_B.instant()                         # instants are exempt
    assert len(fr.snapshot_payload()["events"]) == 3
    # floor==0 records everything again
    fr.configure(min_span_us=0.0)
    SP_B.end_at(fr.now(), 1e-7)
    assert len(fr.snapshot_payload()["events"]) == 4


@pytest.mark.parametrize("how", ["end", "end_at"])
def test_floor_exempt_span_is_recorded_however_short(clean_ring, how):
    """A per-request span (a queue wait) is recorded at 1 us under the
    default floor, where an ordinary span is dropped: its median must be
    over every request, not over the ones that waited."""
    fr.configure(min_span_us=500.0)
    for sp in (SP_A, SP_EXEMPT):
        if how == "end":
            sp.end(fr.now(), "v")          # closed at once: ~1 us
        else:
            sp.end_at(fr.now(), 1e-6, "v")
    snap = fr.snapshot_payload()
    assert _names_of(snap) == ["test.fr_exempt"]
    assert snap["events"][0][4] < 500e-6
    with pytest.raises(ValueError):        # the flag is part of the name
        fr.register_span("test.fr_exempt", tag_keys=("k",))


def test_disabled_recorder_records_nothing(clean_ring):
    fr.configure(enabled=False)
    assert fr.now() == 0.0                 # begin side: one flag test
    SP_B.end(fr.now())
    SP_B.end_at(time.monotonic(), 0.5)
    SP_EXEMPT.end_at(time.monotonic(), 0.5, "v")
    SP_EXEMPT.end(time.monotonic(), "v")
    SP_B.instant()
    fr.configure(enabled=True)
    assert fr.snapshot_payload()["events"] == []


def test_capacity_wrap_keeps_latest(clean_ring):
    fr.configure(capacity=1024)
    try:
        n = 2500
        for i in range(n):
            SP_A.end_at(fr.now(), 0.001, i)
        snap = fr.snapshot_payload()
        assert len(snap["events"]) <= 1024
        # survivors are exactly the most recent seqs (torn-slot guard:
        # every collected record's stamped seq matches its slot)
        seqs = [r[0] for r in snap["events"]]
        assert min(seqs) >= n - 1024
        assert max(seqs) == n - 1
        assert seqs == sorted(seqs)
    finally:
        fr.configure(capacity=fr._DEFAULT_CAPACITY)


def test_register_span_idempotent_and_conflicts():
    sp = fr.register_span("test.fr_a", tag_keys=("k",))
    assert sp is SP_A                      # identical re-registration
    with pytest.raises(ValueError, match="already registered"):
        fr.register_span("test.fr_a", tag_keys=("k", "extra"))
    # sids derive from the NAME (crc32): registration order can differ
    # across processes (cloudpickle-by-value) without colliding tables
    import zlib

    assert SP_A.sid == zlib.crc32(b"test.fr_a")


def test_crash_dump_writes_window(clean_ring, tmp_path):
    saved_dir = fr._dump_dir[0]
    try:
        fr.set_dump_dir(str(tmp_path))
        SP_B.end_at(fr.now(), 0.002)
        path = fr.dump("test-reason")
        assert path is not None
        with open(path) as f:
            payload = json.load(f)
        assert payload["reason"] == "test-reason"
        assert len(payload["events"]) == 1
    finally:
        fr._dump_dir[0] = saved_dir


# --------------------------------------------------------------------------- #
# Clock-offset estimation
# --------------------------------------------------------------------------- #


def test_clock_offset_recovered_within_rtt_bound():
    """Remote clock 3.7 s ahead, asymmetric per-round path delays: the
    min-RTT midpoint estimate must sit within rtt_min/2 of truth."""
    true_offset = 3.7
    est = fr.ClockOffsetEstimator()
    rounds = [(0.040, 0.008), (0.002, 0.001), (0.015, 0.030),
              (0.009, 0.009), (0.120, 0.004)]
    t = 100.0
    for d_out, d_back in rounds:
        send = t
        remote = t + d_out + true_offset
        recv = t + d_out + d_back
        est.add_ping(send, recv, remote)
        t += 1.0
    rtt_min = min(a + b for a, b in rounds)
    assert est.rtt() == pytest.approx(rtt_min)
    assert est.error_bound() == pytest.approx(rtt_min / 2.0)
    assert abs(est.offset() - true_offset) <= est.error_bound() + 1e-9


def test_clock_offset_window_ages_out_steps():
    """A stepped remote clock must win once the old samples age out of
    the sliding window — the estimate tracks the CURRENT clock."""
    est = fr.ClockOffsetEstimator(window=4)
    for _ in range(4):
        est.add(10.0, 0.001)               # old regime, tight rtt
    assert est.offset() == pytest.approx(10.0)
    for _ in range(4):
        est.add(20.0, 0.050)               # clock stepped; worse rtt
    assert est.offset() == pytest.approx(20.0)


def test_empty_estimator_is_neutral():
    est = fr.ClockOffsetEstimator()
    assert est.offset() == 0.0
    assert est.rtt() is None and est.error_bound() is None


# --------------------------------------------------------------------------- #
# Merge math + attribution on synthetic payloads
# --------------------------------------------------------------------------- #


def _payload(anchor_mono, anchor_wall, events, **extra):
    p = {"pid": 1, "proc": "p", "anchor_mono": anchor_mono,
         "anchor_wall": anchor_wall,
         "names": {SP_A.sid: {"name": "test.fr_a", "tag_keys": ["k"]},
                   SP_B.sid: {"name": "test.fr_b", "tag_keys": []}},
         "events": events}
    p.update(extra)
    return p


def test_merge_aligns_skewed_clocks_onto_one_timeline():
    """The same true instant recorded on two nodes — node B's wall
    clock 5 s ahead, which the estimator reported as offset_s=5 — must
    map to the SAME merged timestamp."""
    # node A (reference): instant at wall 1001.0 == mono 101.0
    pa = _payload(100.0, 1000.0,
                  [[0, SP_B.sid, fr.KIND_SPAN, 101.0, 0.25, []]],
                  source="a", node_hex="aaaa", offset_s=0.0)
    # node B: same instant reads wall 1006.0 there == mono 50.0
    pb = _payload(50.0, 1001.0 + 5.0,
                  [[0, SP_B.sid, fr.KIND_SPAN, 50.0, 0.25, []]],
                  source="b", node_hex="bbbb", offset_s=5.0)
    ev_a, ev_b = fr.build_span_events([pa, pb])
    assert ev_a["ts"] == pytest.approx(ev_b["ts"])
    assert ev_a["ts"] == pytest.approx(1001.0 * 1e6)
    assert ev_a["pid"] != ev_b["pid"]      # one track group per node
    assert ev_a["dur"] == pytest.approx(0.25e6)
    # without the offset, B would sit 5 s in the future
    pb["offset_s"] = 0.0
    _, ev_b_raw = fr.build_span_events([pa, pb])
    assert ev_b_raw["ts"] - ev_a["ts"] == pytest.approx(5e6)


def test_build_span_events_tags_tracks_and_instants():
    recs = [[0, SP_A.sid, fr.KIND_SPAN, 1.0, 0.5, ["ch0"]],
            [1, SP_A.sid, fr.KIND_SPAN, 2.0, 0.5, ["ch1"]],
            [2, SP_B.sid, fr.KIND_INSTANT, 3.0, 0.0, []],
            [3, 999999999, fr.KIND_SPAN, 4.0, 0.1, []]]  # unknown sid
    events = fr.build_span_events(
        [_payload(0.0, 0.0, recs, source="s", offset_s=0.0)])
    assert len(events) == 3                # unknown sid dropped
    # a "channel"-keyed tag (here key "k" is not channel) -> per-name
    # track; swap the names table to prove per-channel lanes
    p = _payload(0.0, 0.0, recs[:2], source="s", offset_s=0.0)
    p["names"][SP_A.sid] = {"name": "ring.wait_read",
                            "tag_keys": ["channel"]}
    lanes = {e["tid"] for e in fr.build_span_events([p])}
    assert len(lanes) == 2                 # one lane per channel value
    inst = [e for e in events if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "test.fr_b"
    spans = [e for e in events if e["ph"] == "X"]
    assert all(e["cat"] == "span" for e in spans)
    assert spans[0]["args"]["k"] == "ch0"


def test_attribute_trace_folds_step_budget():
    """Synthetic 2-stage trace: 1 s of stepped wall, each stage 0.4 s
    busy -> efficiency 0.8/(2*1.0) = 0.4, bubble 0.6; warmup spans
    before the first step are clipped, ring waits are accounted."""

    def ev(name, ts_s, dur_s, **args):
        return {"ph": "X", "cat": "span", "name": name,
                "ts": ts_s * 1e6, "dur": dur_s * 1e6, "pid": "n",
                "tid": name, "args": args}

    events = [
        ev("pipe.fwd", 0.2, 0.5, stage=0),     # warmup: before step 0
        ev("pipe.step", 10.0, 1.0),
        ev("pipe.fwd", 10.0, 0.25, stage=0),
        ev("pipe.bwd", 10.3, 0.15, stage=0),
        ev("pipe.fwd", 10.2, 0.2, stage=1),
        ev("pipe.loss_bwd", 10.5, 0.2, stage=1),
        ev("ring.wait_read", 10.4, 0.05, channel="c", role="r"),
        ev("spmd.ingest_wait", 11.0, 0.1),
    ]
    rep = fr.attribute_trace(events)
    assert rep["steps"] == 1
    assert rep["num_stages"] == 2
    assert rep["step_wall_s"] == pytest.approx(1.0)
    assert rep["pipeline_busy_s"] == pytest.approx(0.8)
    assert rep["pipeline_efficiency"] == pytest.approx(0.4)
    assert rep["bubble_fraction"] == pytest.approx(0.6)
    assert rep["per_stage_busy_s"] == {"0": 0.4, "1": 0.4}
    assert rep["ring_stall_s"] == pytest.approx(0.05)
    assert rep["ingest_wait_s"] == pytest.approx(0.1)
    # the human rendering mentions the headline numbers
    text = fr.format_attribution(rep)
    assert "bubble fraction" in text and "0.6000" in text


def test_attribute_trace_serving_section():
    """The spans on a request's way to its first token fold into one
    section per decode deployment: median and p95 of each stop, the
    engine's and the stream loop's spans (which name no deployment)
    going to the deployment that recorded in the same process, and
    tokens per decode step from the span's own count."""

    def ev(name, dur_ms, source="worker:1", **args):
        return {"ph": "X", "cat": "span", "name": name, "ts": 0.0,
                "dur": dur_ms * 1e3, "pid": "n", "tid": name,
                "args": dict(args, source=source)}

    events = []
    for corr, wait in enumerate([0.0, 1.0, 200.0]):
        events += [
            ev("dag.stream_ingress", wait, method="m", corr=corr),
            ev("serve.sched_wait", 2 * wait, deployment="LM", corr=corr),
            ev("serve.prefill", 300.0, deployment="LM", corr=corr),
            ev("engine.prefill_program", 25.0, pages=4),
            ev("engine.prefill_kv", 175.0, pages=4),
            ev("engine.prefill_logits", 100.0, pages=4),
            ev("serve.first_token_hold", 90.0, deployment="LM", corr=corr),
        ]
    events += [ev("serve.decode_step", 100.0, deployment="LM", tokens=2),
               ev("serve.decode_step", 50.0, deployment="LM", tokens=1),
               # another process's stream lane, serving no decode
               ev("dag.stream_ingress", 7.0, source="worker:2",
                  method="other", corr=0)]
    rep = fr.attribute_trace(events)
    lm = rep["serving"]["LM"]
    assert list(rep["serving"]) == ["LM"]
    assert lm["ingress"] == {"n": 3, "p50_ms": 1.0, "p95_ms": 200.0}
    assert lm["sched_wait"]["p50_ms"] == 2.0
    assert lm["sched_wait"]["p95_ms"] == 400.0
    assert lm["prefill"]["p50_ms"] == 300.0
    assert lm["prefill_program"]["p50_ms"] == 25.0
    assert lm["prefill_kv"]["p50_ms"] == 175.0
    assert lm["prefill_logits"]["p50_ms"] == 100.0
    assert lm["first_token_hold"]["n"] == 3
    assert (lm["decode_steps"], lm["decode_tokens"]) == (2, 3)
    text = fr.format_attribution(rep)
    assert "where did the time to first token go: LM" in text
    assert "scheduler wait" in text and "first token held" in text
    assert "1.50 tokens a step" in text
    # a trace with no decode replica has no such section
    assert "serving" not in fr.attribute_trace(
        [ev("spmd.compute", 10.0)])


def _spmd_steps(slow_step, how, source="worker:1", at=0.0):
    """Six 100 ms steps as the SPMD loop records them, one step ahead of
    its reports (times in ms; the first step is the compile, settled
    alone), one of them slow: ``how`` = "device" (its program took 400 ms
    longer) or "host" (the report two steps before took 400 ms). A routed
    model: each step reports its ``moe.held_share``, 0.01 a step number."""
    events = []

    def ev(name, ts, dur, step, **args):
        events.append({"ph": "X", "cat": "span", "name": name,
                       "ts": (at + ts) * 1e3, "dur": dur * 1e3, "pid": "n",
                       "tid": name, "args": dict(args, source=source,
                                                 step=step)})

    host = 0.0   # the loop's thread
    issued, ready = {}, {0: 0.0}  # ready: when the HOST saw a step ready

    def settle(k, done):
        nonlocal host
        waited_from, host = host, max(host, done)
        lo = max(issued[k], ready[k - 1])
        if k == 1:
            ev("spmd.compile", lo, host - lo, k)
        else:
            ev("spmd.ready_wait", waited_from, host - waited_from, k)
            ev("spmd.compute", lo, host - lo, k)
        ready[k] = host
        ev("spmd.fetch", host, 0.1, k)
        events.append({"ph": "i", "s": "t", "cat": "span",
                       "name": "moe.held_share", "pid": "n", "tid": "moe",
                       "ts": (at + host + 0.1) * 1e3,
                       "args": {"value": 0.01 * k, "step": k,
                                "source": source}})
        rep = 400.0 if how == "host" and k == slow_step - 2 else 1.0
        ev("spmd.report", host + 0.1, rep, k)
        host += 0.1 + rep

    device, before = 0.0, None   # when the device comes free
    for step in range(1, 7):
        ev("spmd.ingest_wait", host, 0.2, step)
        issued[step] = host = host + 0.2
        if step > 1:
            ev("spmd.dispatch", host, 0.5, step)
            host += 0.5
        device = max(issued[step], device) + 100.0 + (
            400.0 if how == "device" and step == slow_step else 0.0)
        if before is not None:
            settle(*before)
        before = (step, device)
        if step == 1:
            settle(*before)
            before = None
    settle(*before)
    return events


def test_attribute_trace_spmd_phases_and_slowest_step():
    """The SPMD loop's phases fold into seconds beside ``spmd_compute_s``,
    and the slowest step by wall comes with what is tagged with its step: a
    step the device took long over shows in its wait for the device, a
    report that stalled the loop in the step that made it."""
    rep = fr.attribute_trace(_spmd_steps(4, "device"))
    assert rep["spmd_dispatch_s"] == pytest.approx(5 * 0.5e-3)
    assert rep["spmd_fetch_s"] == pytest.approx(6 * 0.1e-3)
    assert rep["spmd_report_s"] == pytest.approx(6 * 1e-3)
    assert rep["spmd_ready_wait_s"] > 0.5
    slow = rep["slowest_step"]
    assert slow["step"] == 4 and slow["source"] == "worker:1"
    assert slow["phases_ms"]["ready_wait"] > 400.0
    assert slow["phases_ms"]["dispatch"] == pytest.approx(0.5)
    assert slow["phases_ms"]["report"] == pytest.approx(1.0)
    assert slow["wall_ms"] == pytest.approx(sum(slow["phases_ms"].values()))
    assert 495.0 < slow["wall_ms"] < 505.0
    assert slow["router"] == {"moe.held_share": pytest.approx(0.04)}
    text = fr.format_attribution(rep)
    assert f"slowest step       : 4, {slow['wall_ms']:.3f} ms wall" in text
    assert "device wait 4" in text and "moe.held_share 0.04" in text
    assert "  dispatch         : 0.0025s" in text
    # the same loop run twice in one process repeats its step numbers: the
    # slow step of the second run keeps its own spans and scalars
    twice = _spmd_steps(0, "device") + _spmd_steps(4, "device", at=5000.0)
    assert fr.attribute_trace(twice)["slowest_step"] == slow

    # a report of 400 ms in step 3: the step after it was ready before the
    # host came to wait for it
    late = fr.attribute_trace(_spmd_steps(5, "host"))["slowest_step"]
    assert late["step"] == 3
    assert late["phases_ms"]["report"] == pytest.approx(400.0)
    assert late["phases_ms"]["ready_wait"] < 100.0
    # a trace from before the spans carried ``step`` has no such line
    assert "slowest_step" not in fr.attribute_trace(
        [{"ph": "X", "cat": "span", "name": "spmd.dispatch", "ts": 0.0,
          "dur": 1e5, "args": {}}])


@pytest.mark.parametrize("metric,span", [
    ("train.dispatch_ms", "spmd.dispatch"),
    ("train.fetch_ms", "spmd.fetch"),
    ("train.report_ms", "spmd.report"),
])
def test_train_loop_metric_files_read_their_spans(metric, span):
    """Each per-layer metric of the train loop's phases, through the
    benchmark's own loader and reader: the median of its span in ms, and
    nothing (not an error) from a program that records no such span."""
    import json
    import os

    from benchmarks.lib import reducers, spec

    file = spec.load_layer_metric(metric)
    assert file["args"] == {"span": span} and file["layer"] == "train loop"
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == metric]
    assert (entry["unit"], entry["source"], entry["moves"]) == (
        file["unit"], file["source"], file["moves"])
    assert entry["workloads"] == [
        "train-mistral7b-1chip", "train-mistral7b-4chip",
        "train-olmoe-1chip", "train-nemotron3nano-1chip",
        "train-glm47flash-1chip"]
    spans = {span: [[0.0, 0.001], [1.0, 0.003], [2.0, 2.5]]}
    assert reducers.read_metric(file, {"spans": spans}) == pytest.approx(3.0)
    assert reducers.wanted_spans([file]) == {span}
    assert reducers.read_metric(file, {"spans": {}}) is None


# --------------------------------------------------------------------------- #
# Set-up taken apart: a process's way to its first productive span
# --------------------------------------------------------------------------- #


def _setup_trace():
    """A driver and a train worker (seconds): nested ``jax.*`` inside
    ``xla.compile`` inside ``spmd.compile``, a load inside a compile, three
    gaps nobody spans, a second loop's build after the first compute."""

    def ev(source, name, t0, t1, **args):
        return {"ph": "X", "cat": "span", "name": name, "ts": t0 * 1e6,
                "dur": (t1 - t0) * 1e6, "pid": "n", "tid": name,
                "args": dict(args, source=source)}

    d, w = "head:driver", "ab12cd:77"
    return d, w, [
        ev(d, "runtime.init", 0.0, 0.1), ev(d, "trainer.place", 0.1, 2.1),
        ev(w, "worker.boot", 0.5, 1.5), ev(w, "jax.import", 2.0, 3.0),
        ev(w, "jax.backend_init", 3.5, 6.5), ev(w, "spmd.build", 6.5, 6.6),
        ev(w, "spmd.init_state", 6.6, 7.6), ev(w, "jax.trace", 6.6, 6.7),
        ev(w, "jax.backend_compile", 6.8, 7.5),
        ev(w, "jax.cache_load", 6.9, 7.4),
        ev(w, "spmd.compile", 8.0, 12.0, step=1),
        ev(w, "xla.compile", 8.0, 11.0, program="spmd.train_step"),
        ev(w, "jax.trace", 8.0, 8.5), ev(w, "jax.lower", 8.5, 9.0),
        ev(w, "jax.trace", 8.1, 8.3),  # a jit traced under a jit: once
        ev(w, "jax.backend_compile", 9.0, 11.0),
        ev(w, "jax.cache_load", 9.1, 10.9),
        ev(w, "ring.wait_read", 2.0, 11.0, channel="c", role="r"),
        ev(w, "spmd.compute", 12.0, 12.5, step=2),
        ev(w, "spmd.build", 20.0, 20.1), ev(w, "spmd.compute", 21.0, 21.5),
    ]


def test_setup_block_cuts_the_stretch_by_innermost_span():
    d, w, events = _setup_trace()
    rep = fr.attribute_trace(events)["setup"]
    assert set(rep) == {d, w}
    # the driver has no productive span: to its last set-up span's end
    assert rep[d]["until"] is None and rep[d]["stretch_s"] == 2.1
    assert rep[d]["parts_s"] == {"runtime.init": 0.1, "trainer.place": 2.0}
    got = rep[w]
    assert got["until"] == "spmd.compute" and got["stretch_s"] == 11.5
    want = {"worker.boot": 1.0, "jax.import": 1.0, "jax.backend_init": 3.0,
            "spmd.build": 0.1, "spmd.init_state": 0.2, "jax.trace": 0.6,
            "jax.lower": 0.5, "jax.cache_load": 2.3,
            # innermost wins: a compile less the load nested in it, the
            # first step less everything inside it, xla.compile all given away
            "jax.backend_compile": 0.4, "spmd.compile": 1.0,
            "xla.compile": 0.0,
            # 1.5-2.0, 3.0-3.5, 7.6-8.0: nobody's (a ring wait is no part)
            "unattributed": 1.4}
    assert {k: v for k, v in got["parts_s"].items() if v} == pytest.approx(
        {k: v for k, v in want.items() if v})
    assert sum(got["parts_s"].values()) == pytest.approx(got["stretch_s"],
                                                         abs=1e-3)
    assert got["programs"] == {
        "other": {"programs": 1, "first_run_s": 0.0, "trace_s": 0.1,
                  "lower_s": 0.0, "load_s": 0.5, "compiled_s": 0.2},
        "spmd.train_step": {"programs": 1, "first_run_s": 1.0,
                            "trace_s": 0.5, "lower_s": 0.5, "load_s": 1.8,
                            "compiled_s": 0.2}}


def test_setup_block_is_printed_a_line_a_part_largest_first():
    d, w, events = _setup_trace()
    text = fr.format_attribution(fr.attribute_trace(events))
    block = text[text.index(f"set-up : 11.500 s on {w}"):]
    block = block[:block.index("\n\n")] if "\n\n" in block else block
    lines = block.splitlines()
    assert "(to its first spmd.compute)" in lines[0]
    parts = [ln for ln in lines[2:] if not ln.startswith("  ")]
    seconds = [float(ln.split(":")[1].split()[0]) for ln in parts]
    assert seconds == sorted(seconds, reverse=True)
    assert sum(seconds) == pytest.approx(11.5, abs=1e-3)
    assert parts[0].startswith("jax.backend_init") and seconds[0] == 3.0
    assert any(ln.startswith("unattributed") for ln in parts)
    assert any("spmd.train_step" in ln and "load 1.800" in ln
               and "first run 1.000" in ln for ln in lines)
    assert f"set-up : 2.100 s on {d}" in text
    # the step budget's own compile row is still there, once
    assert text.count("compile (1st step)") == 1


def test_setup_block_of_a_replica_and_none_without_setup_spans():
    """A replica: the weights and the stores inside engine.build, a warm-up
    call's first run around its xla.compile, the stretch to the first
    request's prefill. A trace with no set-up span prints no block."""

    def ev(name, t0, t1, **args):
        return {"ph": "X", "cat": "span", "name": name, "ts": t0 * 1e6,
                "dur": (t1 - t0) * 1e6, "pid": "n", "tid": name,
                "args": dict(args, source="r:1")}

    events = [
        ev("worker.boot", 0.0, 1.0), ev("engine.build", 1.0, 9.0),
        ev("jax.backend_init", 1.0, 4.0), ev("engine.weights", 4.0, 7.0),
        ev("engine.stores", 7.0, 7.5, kind="b"),
        ev("engine.stores", 7.5, 8.5, kind="W"),
        ev("engine.prefill_program", 9.0, 12.0, pages=2),
        ev("xla.compile", 9.0, 11.0, program="llama.prefill"),
        ev("jax.backend_compile", 9.5, 11.0),
        ev("engine.prefill_program", 12.0, 12.2, pages=2),  # compiled nothing
        ev("dag.stream_ingress", 12.5, 12.6, method="m", corr=1),
        ev("serve.prefill", 13.0, 13.5, deployment="LM", corr=1),
    ]
    rep = fr.attribute_trace(events)["setup"]["r:1"]
    assert rep["until"] == "serve.prefill" and rep["stretch_s"] == 13.0
    assert rep["parts_s"] == pytest.approx({
        "worker.boot": 1.0, "jax.backend_init": 3.0, "engine.weights": 3.0,
        "engine.stores": 1.5, "engine.build": 0.5, "xla.compile": 0.5,
        "jax.backend_compile": 1.5, "engine.prefill_program": 1.2,
        "unattributed": 0.8})
    assert rep["programs"]["llama.prefill"] == {
        "programs": 1, "first_run_s": 1.0, "trace_s": 0.0, "lower_s": 0.0,
        "load_s": 0.0, "compiled_s": 1.5}
    plain = [e for e in events if e["name"] in (
        "serve.prefill", "dag.stream_ingress", "engine.prefill_program")]
    assert "setup" not in fr.attribute_trace(plain)
    assert "set-up" not in fr.format_attribution(fr.attribute_trace(plain))


def test_cut_innermost_sums_to_the_interval():
    cut = fr.cut_innermost(
        [("a", 0, 10), ("b", 2, 5), ("c", 3, 4), ("d", 12, 20),
         ("a", 2, 2.5), ("e", -5, -1)], 1, 15)
    assert cut == {"a": 6.5, "b": 1.5, "c": 1.0, "unattributed": 2.0,
                   "d": 3.0}
    assert sum(cut.values()) == 14
    assert fr.cut_innermost([], 0, 3) == {"unattributed": 3}


@pytest.mark.parametrize("metric,counter", [
    ("compile.cache_misses", "cache_misses"),
    ("compile.cache_hits", "cache_hits"),
])
def test_compile_cache_metric_files_read_their_counters(metric, counter):
    """The two counters of the compile plane that every cell's evidence
    carried and no metric file read, through the benchmark's own loader and
    reader; spelt as ``compile_s`` is (every cell: no ``workloads``)."""
    import os

    from benchmarks.lib import reducers, spec

    file = spec.load_layer_metric(metric)
    assert file["reader"] == "counter_value"
    assert file["args"] == {"counter": counter}
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    entry, = [m for m in per_layer if m["name"] == metric]
    like, = [m for m in per_layer if m["name"] == "compile_s"]
    assert set(entry) == set(like) and "workloads" not in entry
    assert (entry["unit"], entry["source"], entry["moves"], entry["layer"],
            entry["better"]) == (file["unit"], file["source"], file["moves"],
                                 file["layer"], "lower") == (
        "programs", "program_counter", "setup_s", "compile plane", "lower")
    counters = {"compile_s": 6.5, "cache_hits": 26, "cache_misses": 3}
    assert reducers.read_metric(file, {"counters": counters}) == float(
        counters[counter])
    assert reducers.read_metric(file, {"counters": {}}) is None
    assert reducers.wanted_spans([file]) == set()


@pytest.mark.deadline(170)
@pytest.mark.parametrize("cell,must_name", [
    ("train-mistral7b-1chip",
     ("runtime.init", "trainer.place", "worker.boot", "jax.import",
      "spmd.compile", "jax.backend_compile", "harness.reference_check",
      "harness.driver_imports")),
    # the serving cell's run binds the serve plane's port, so it queues
    # behind the ONE rehearsal lock, where three cells' rehearsals already
    # run to within seconds of their deadlines (keye's 161 of 170 s in a
    # whole run of PR 50): out of tier-1, as chip_smoke's rehearsal is
    pytest.param(
        "serve-internlm2-prefill-open",
        ("runtime.init", "serve.deploy", "jax.import", "jax.backend_compile",
         "harness.prepare.build", "harness.prepare.warm",
         "harness.prepare.check"), marks=pytest.mark.slow),
])
def test_setup_parts_rehearsal_sums_to_setup_s(cell, must_name):
    """``sweep/setup_parts.py --rehearsal`` on the smallest train and serve
    cell: exits 0, its parts with ``unattributed_s`` sum to its ``setup_s``,
    and both the program's spans and the harness's stamps are among them."""
    import rehearse

    out = rehearse.run_cell(cell, 5000000050, seconds=1,
                            script="benchmarks/sweep/setup_parts.py",
                            extra=(), serves=cell.startswith("serve-"))
    assert out["device"]["platform"] == "cpu"
    assert sum(out["parts_s"].values()) + out["unattributed_s"] == \
        pytest.approx(out["setup_s"], abs=1e-3)
    named = {name.split("@")[0] for name in out["inclusive_s"]}
    assert set(must_name) <= named, named
    assert out["unattributed_s"] < 0.1 * out["setup_s"]
    worker, = out["programs"].values()  # the one process that compiled
    assert all(row["programs"] >= 1 for row in worker.values())


# --------------------------------------------------------------------------- #
# Cluster plumbing: 2 separate-process daemons -> one merged trace
# --------------------------------------------------------------------------- #


def _span_names_in(head):
    names = set()
    for chunks in head.flight_spans.values():
        for p in chunks:
            tbl = {int(k): v["name"] for k, v in p["names"].items()}
            for rec in p["events"]:
                n = tbl.get(rec[1])
                if n:
                    names.add(n)
    return names


@pytest.fixture()
def traced_two_daemons():
    """Two separate-process daemons with fast span/ping cadence and no
    duration floor (sub-ms test workloads must record)."""
    cfg = global_config()
    saved = (cfg.flight_recorder_min_span_us,
             cfg.flight_recorder_report_interval_ms,
             cfg.health_check_period_ms)
    cfg.flight_recorder_min_span_us = 0.0
    cfg.flight_recorder_report_interval_ms = 300
    cfg.health_check_period_ms = 300
    saved_min = fr._min_dur[0]
    fr.configure(min_span_us=0.0)
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 1})
    n1 = cluster.add_node(num_cpus=2, resources={"fr1": 2},
                          separate_process=True)
    n2 = cluster.add_node(num_cpus=2, resources={"fr2": 2},
                          separate_process=True)
    yield cluster, n1, n2
    cluster.shutdown()
    (cfg.flight_recorder_min_span_us,
     cfg.flight_recorder_report_interval_ms,
     cfg.health_check_period_ms) = saved
    fr.configure(min_span_us=saved_min)


@ray_tpu.remote(resources={"fr1": 1})
class FrStage1:
    def inc(self, x):
        time.sleep(0.002)
        return x + 1


@ray_tpu.remote(resources={"fr2": 1})
class FrStage2:
    def double(self, x):
        time.sleep(0.002)
        return x * 2


def test_two_daemon_dag_merges_into_one_trace(traced_two_daemons):
    """driver->d1->d2->driver compiled DAG: executor spans from BOTH
    daemons' workers arrive at the head (stamped with their node hex),
    every daemon proxy grows a ping-fed clock estimator, and
    cluster_trace() emits one JSON-serializable Chrome trace whose span
    events cover all three nodes with per-track monotone executors."""
    from ray_tpu.core.runtime import get_current_runtime
    from ray_tpu.dag import InputNode

    a, b = FrStage1.remote(), FrStage2.remote()
    with InputNode() as inp:
        out = b.double.bind(a.inc.bind(inp))
    dag = out.experimental_compile(max_inflight=2)
    wall_lo = time.time() - 30.0
    try:
        for i in range(12):
            assert dag.execute(i).get(timeout=60) == (i + 1) * 2
    finally:
        dag.teardown()
    wall_hi = time.time() + 30.0

    head = get_current_runtime().head
    # worker executor spans from two distinct daemons reach the head
    wait_for(lambda: "dag.exec" in _span_names_in(head),
             timeout=30, msg="executor spans reported to head")

    def exec_hexes():
        hexes = set()
        for chunks in head.flight_spans.values():
            for p in chunks:
                tbl = {int(k): v["name"] for k, v in p["names"].items()}
                if any(tbl.get(r[1]) == "dag.exec" for r in p["events"]):
                    hexes.add(p.get("node_hex"))
        return hexes

    wait_for(lambda: len(exec_hexes()) >= 2, timeout=30,
             msg="dag.exec spans from both daemons")
    assert None not in exec_hexes()

    # pings fed each daemon's clock estimator; same host, so the
    # estimated offset is small and its error bound is finite
    daemon_proxies = [p for p in head.nodes.values()
                      if p.hex != head.head_node.hex]
    assert len(daemon_proxies) >= 2
    wait_for(lambda: all(p.clock_est is not None
                         and p.clock_est.rtt() is not None
                         for p in daemon_proxies),
             timeout=30, msg="clock estimators fed by pongs")
    for p in daemon_proxies:
        assert abs(p.clock_est.offset()) <= 1.0
        assert p.clock_est.error_bound() < 1.0

    # head-side payload stamping: local snapshot at offset 0, worker
    # payloads keyed by node hex
    payloads = fr.cluster_span_payloads(head)
    assert payloads[0]["source"].startswith("head:")
    assert payloads[0]["offset_s"] == 0.0
    assert any(p.get("node_hex") in exec_hexes() for p in payloads[1:])

    # ONE merged Chrome trace: driver dispatch spans + both daemons'
    # executor spans, all on the head's wall timeline
    events = fr.cluster_trace(head)
    json.dumps(events)                     # exporter contract
    spans = [e for e in events if e.get("cat") == "span"
             and e.get("ph") == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name.get("dag.execute", [])) >= 12   # driver side
    exec_pids = {e["pid"] for e in by_name.get("dag.exec", [])}
    assert len(exec_pids) >= 2                         # both daemons
    all_pids = {e["pid"] for e in spans}
    assert len(all_pids) >= 3                          # + the head
    # merged clocks: every span lands inside the test's wall window
    for e in spans:
        assert wall_lo <= e["ts"] / 1e6 <= wall_hi, e
    # executor loops are serial: per-track spans must not overlap
    tracks = {}
    for e in by_name.get("dag.exec", []):
        tracks.setdefault((e["pid"], e["tid"]), []).append(e)
    for evs in tracks.values():
        evs.sort(key=lambda e: e["ts"])
        for prev, cur in zip(evs, evs[1:]):
            assert prev["ts"] + prev["dur"] <= cur["ts"] + 1e3, \
                (prev, cur)


# --------------------------------------------------------------------------- #
# End to end: trace-derived bubble matches pipeline_stats()
# --------------------------------------------------------------------------- #


def test_trace_attribution_matches_pipeline_stats():
    """The acceptance bar: fold the merged trace of a 2-stage MPMD run
    into the per-step budget and the bubble fraction must agree with
    the trainer's own measured ``pipeline_stats()`` within 0.05 — the
    trace is the *explained* version of the same accounting."""
    from ray_tpu.core.runtime import get_current_runtime
    from ray_tpu.train.pipeline import MPMDPipelineTrainer

    cfg = global_config()
    saved = (cfg.flight_recorder_min_span_us,
             cfg.flight_recorder_report_interval_ms)
    cfg.flight_recorder_min_span_us = 0.0
    cfg.flight_recorder_report_interval_ms = 300
    saved_min = fr._min_dur[0]
    fr.configure(min_span_us=0.0)
    layers = [16, 64, 64, 8]
    rng = np.random.RandomState(7)
    x = rng.randn(32, layers[0]).astype(np.float32)
    y = rng.randn(32, layers[-1]).astype(np.float32)
    steps, mb = 5, 4
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        fr.reset_for_tests()               # driver ring: this run only
        trainer = MPMDPipelineTrainer(layers, num_stages=2, lr=0.05,
                                      seed=3)
        try:
            trainer.fit(x, y, steps=steps, num_microbatches=mb)
            stats = trainer.pipeline_stats()
            head = get_current_runtime().head

            def busy_events():
                n = 0
                for chunks in head.flight_spans.values():
                    for p in chunks:
                        tbl = {int(k): v["name"]
                               for k, v in p["names"].items()}
                        n += sum(1 for r in p["events"]
                                 if tbl.get(r[1], "").startswith("pipe."))
                return n

            # each microbatch yields 3 stage-side spans (stage-0 fwd +
            # bwd, last stage's fused loss_bwd): wait for the full run
            # to ride the 300 ms report cadence in
            want = 3 * steps * mb
            wait_for(lambda: busy_events() >= want, timeout=30,
                     msg=f"{want} pipeline spans reported")

            report = fr.attribute_trace(
                fr.cluster_trace(head, include_tasks=False))
            assert report["steps"] == steps
            assert report["num_stages"] == 2
            assert report["bubble_fraction"] is not None
            assert abs(report["bubble_fraction"]
                       - stats["bubble_fraction"]) <= 0.05, (report,
                                                             stats)
            assert report["pipeline_busy_s"] > 0
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
        (cfg.flight_recorder_min_span_us,
         cfg.flight_recorder_report_interval_ms) = saved
        fr.configure(min_span_us=saved_min)


def test_timeline_cli_accepts_both_trace_shapes(tmp_path, clean_ring):
    """`timeline --input` takes a bare event list OR the
    {"traceEvents": [...]} object form a --perfetto re-export writes."""
    from ray_tpu.__main__ import main as cli_main

    ev = {"name": "dag.exec", "cat": "span", "ph": "X", "pid": "p",
          "tid": "t", "ts": 1000.0, "dur": 2000.0, "args": {}}
    flat = tmp_path / "flat.json"
    flat.write_text(json.dumps([ev]))
    wrapped = tmp_path / "wrapped.json"
    wrapped.write_text(json.dumps({"traceEvents": [ev]}))

    for src in (flat, wrapped):
        out = tmp_path / (src.stem + "_out.json")
        rc = cli_main(["timeline", "--input", str(src),
                       "--perfetto", str(out), "--attribute"])
        assert rc == 0
        assert len(json.loads(out.read_text())) == 1
