"""XLA compile observatory: the ObservedFunction wrapper, recompile /
shape-churn accounting, the head-side fold (xla_report / format_xla /
/api/xla), the recompile-storm detector, and the goodput + timeline
compile joins.

Metric counters are process-global and cumulative, so every test uses
unique program names; ``reset_for_tests`` clears only the in-process
program registry, not the metrics plane.
"""

import pytest

import ray_tpu
from ray_tpu.core.config import global_config
from ray_tpu.util import flight_recorder as fr
from ray_tpu.util import xla_observatory as xo
from ray_tpu.util.metrics import aggregate_series, registry


@pytest.fixture(autouse=True)
def _fresh_registry():
    xo.reset_for_tests()
    yield
    xo.reset_for_tests()


def _by_program(metric):
    flat = aggregate_series(registry())
    return {dict(tags).get("program"): v
            for tags, v in flat.get(metric, ())}


# --------------------------------------------------------------------------- #
# ObservedFunction
# --------------------------------------------------------------------------- #


def test_observe_records_compile_and_analyses():
    import jax
    import jax.numpy as jnp

    fn = xo.observe_compiled(jax.jit(lambda m: m @ m), "obs.t1")
    x = jnp.ones((16, 16), jnp.float32)
    out = fn(x)
    assert out.shape == (16, 16) and float(out[0, 0]) == 16.0

    rec = xo.get_program("obs.t1")
    assert rec["compiles"] == 1 and rec["recompiles"] == 0
    assert rec["variants"] == 1
    assert rec["avals"] == "f32[16,16]"
    assert rec["compile_seconds"] > 0
    # CPU cost_analysis reports flops and bytes accessed for a matmul
    assert rec["flops"] > 0
    assert rec["bytes_accessed"] > 0
    assert rec["memory"]["argument"] > 0
    assert "peak_bytes" in rec

    # steady state: same fingerprint, no second compile
    fn(x)
    assert xo.get_program("obs.t1")["compiles"] == 1
    assert "obs.t1" in xo.program_names()


def test_recompiles_and_churn_counted():
    import jax
    import jax.numpy as jnp

    fn = xo.observe_compiled(jax.jit(lambda x: x + 1), "obs.t2")
    for n in (4, 5, 6):
        fn(jnp.zeros((n,), jnp.float32))

    rec = xo.get_program("obs.t2")
    assert rec["compiles"] == 3 and rec["recompiles"] == 2
    assert rec["variants"] == 3
    assert rec["churn"][-1] == pytest.approx(
        {"from": "f32[5]", "to": "f32[6]",
         "compile_s": rec["churn"][-1]["compile_s"]})

    # the metrics plane carries the same counts, tagged {program}
    assert _by_program("ray_tpu_xla_recompiles_total")["obs.t2"] == 2.0
    assert _by_program("ray_tpu_xla_compiles_total")["obs.t2"] == 3.0
    assert _by_program("ray_tpu_xla_program_variants")["obs.t2"] == 3.0
    flat = aggregate_series(registry())
    churn = [dict(t) for t, _ in flat.get("ray_tpu_xla_shape_churn", ())
             if dict(t).get("program") == "obs.t2"]
    assert {"program": "obs.t2", "from": "f32[4]", "to": "f32[5]"} in churn


def test_scalar_args_do_not_fake_recompiles():
    import jax
    import jax.numpy as jnp

    fn = xo.observe_compiled(jax.jit(lambda x, s: x * s), "obs.t3")
    a = fn(jnp.ones((3,), jnp.float32), 2.0)
    b = fn(jnp.ones((3,), jnp.float32), 3.0)
    # one compile covers both values — and values stay correct
    assert float(a[0]) == 2.0 and float(b[0]) == 3.0
    rec = xo.get_program("obs.t3")
    assert rec["compiles"] == 1 and rec["recompiles"] == 0


def test_disabled_config_is_passthrough():
    import jax
    import jax.numpy as jnp

    cfg = global_config()
    jitted = jax.jit(lambda x: x - 1)
    try:
        cfg.xla_observatory_enabled = False
        assert xo.observe_compiled(jitted, "obs.t4") is jitted

        # a wrapper built while enabled routes straight through (and
        # records nothing) once the knob is off
        cfg.xla_observatory_enabled = True
        wrapped = xo.observe_compiled(jax.jit(lambda x: x - 2), "obs.t4b")
        cfg.xla_observatory_enabled = False
        out = wrapped(jnp.zeros((2,), jnp.float32))
        assert float(out[0]) == -2.0
        assert xo.get_program("obs.t4b") is None
    finally:
        cfg.xla_observatory_enabled = True


class _FakeJit:
    """Stands in for a jitted callable: counts plain calls, and fails at
    the stage the test names."""

    def __init__(self, lower_error=None, compile_error=None, run_error=None):
        self.lower_error, self.compile_error = lower_error, compile_error
        self.run_error = run_error
        self.plain_calls = 0

    def __call__(self, *a, **k):
        self.plain_calls += 1
        return "ran through jit"

    def lower(self, *a, **k):
        if self.lower_error is not None:
            raise self.lower_error
        return self

    def compile(self):
        if self.compile_error is not None:
            raise self.compile_error

        def executable(*a, **k):
            if self.run_error is not None:
                raise self.run_error
            return "ran compiled"

        return executable


def test_compile_error_reaches_the_caller_as_it_is():
    """A failed lower()/compile() is XLA's answer: it propagates, and the
    program is NOT compiled a second time through plain jit."""
    import jax.numpy as jnp

    for stage in ("lower_error", "compile_error"):
        err = RuntimeError(f"RESOURCE_EXHAUSTED at {stage}")
        fake = _FakeJit(**{stage: err})
        f = xo.ObservedFunction(fake, f"obs.t5.{stage}")
        with pytest.raises(RuntimeError) as ei:
            f(jnp.zeros((1,)))
        assert ei.value is err
        assert fake.plain_calls == 0
        assert xo.get_program(f"obs.t5.{stage}") is None


def test_runtime_error_reaches_the_caller_without_a_retry():
    """A failed execution is not retried on buffers the first attempt
    may already have donated: the caller sees XLA's error, not "array
    has been deleted" from a second run."""
    import jax.numpy as jnp

    err = RuntimeError("RESOURCE_EXHAUSTED: out of memory on the chip")
    fake = _FakeJit(run_error=err)
    f = xo.ObservedFunction(fake, "obs.t5.run")
    for _ in range(2):  # and the failure does not switch observation off
        with pytest.raises(RuntimeError) as ei:
            f(jnp.zeros((1,)))
        assert ei.value is err
    assert fake.plain_calls == 0


def test_argument_rejection_is_an_observation_failure_said_once(caplog):
    """The AOT executable refusing the call's arguments (a sharding the
    fingerprint cannot see) happens before anything runs; jit would have
    re-specialised, so the program goes on through jit, unobserved, and
    the log says so once, with the exception."""
    import logging

    import jax.numpy as jnp

    fake = _FakeJit(run_error=ValueError("input sharding does not match"))
    f = xo.ObservedFunction(fake, "obs.t5.args")
    with caplog.at_level(logging.WARNING, logger=xo.logger.name):
        assert f(jnp.zeros((1,))) == "ran through jit"
        assert f(jnp.zeros((1,))) == "ran through jit"
    said = [r for r in caplog.records if "obs.t5.args" in r.getMessage()]
    assert len(said) == 1
    assert "input sharding does not match" in said[0].getMessage()
    assert fake.plain_calls == 2


def test_lowered_input_compiles_and_records():
    import jax
    import jax.numpy as jnp

    lowered = jax.jit(lambda m: m @ m).lower(jnp.ones((8, 8), jnp.float32))
    compiled = xo.observe_compiled(lowered, "obs.t6")
    out = compiled(jnp.ones((8, 8), jnp.float32))
    assert float(out[0, 0]) == 8.0
    rec = xo.get_program("obs.t6")
    assert rec["compiles"] == 1
    assert rec["flops"] > 0


# --------------------------------------------------------------------------- #
# The head-side fold: roofline / MFU join
# --------------------------------------------------------------------------- #


def test_xla_report_joins_measured_spans_and_rooflines():
    import jax
    import jax.numpy as jnp

    from ray_tpu.train.spmd import _sp_compute

    prev_min = fr._min_dur[0] * 1e6
    fr.configure(enabled=True, min_span_us=0.0)
    fr.reset_for_tests()
    try:
        # "spmd.train_step" is measured by the spmd.compute span family
        fn = xo.observe_compiled(jax.jit(lambda m: m @ m), "spmd.train_step")
        x = jnp.ones((64, 64), jnp.float32)
        for _ in range(3):
            t0 = fr.now()
            fn(x).block_until_ready()
            _sp_compute.end(t0)

        # a CPU is not in the peak table: the fold reports what was
        # counted and measured, with no MFU and no verdict, and says why
        bare = xo.xla_report(None)
        cfg = global_config()
        cfg.xla_peak_flops, cfg.xla_peak_hbm_bytes = 1e12, 100e9
        try:
            report = xo.xla_report(None)
        finally:
            cfg.xla_peak_flops = cfg.xla_peak_hbm_bytes = 0.0
    finally:
        fr.configure(min_span_us=prev_min)
    assert bare["platform"] == "cpu"
    assert bare["peak_flops_per_chip"] is None
    assert "no peak-table entry" in bare["peaks_unknown"]
    bare_row = bare["programs"]["spmd.train_step"]
    assert bare_row["achieved_flops_per_s"] > 0
    assert "mfu" not in bare_row and "verdict" not in bare_row
    assert "peaks: unknown" in xo.format_xla(bare)

    # with peaks given (the overrides), the join rooflines
    assert report["peaks_unknown"] is None
    assert report["peak_flops_per_chip"] == 1e12
    assert report["ridge_intensity"] > 0

    row = report["programs"]["spmd.train_step"]
    assert row["measured_span"] == "spmd.compute"
    assert row["measured_steps"] >= 3
    assert row["mean_step_s"] > 0
    assert row["achieved_flops_per_s"] > 0
    assert 0 < row["mfu"] < 1
    assert row["arithmetic_intensity"] > 0
    assert row["verdict"] in ("compute-bound", "memory-bound")

    # ONE fold: the CLI rendering and the registry gauges agree with it
    text = xo.format_xla(report)
    assert "spmd.train_step" in text
    assert "measured: " in text
    flat = aggregate_series(registry())
    programs_gauge = dict(flat["ray_tpu_xla_programs"])[()]
    assert programs_gauge == float(len(report["programs"]))


def test_peak_table_overrides_and_kind_aliases():
    cfg = global_config()
    try:
        cfg.xla_peak_flops = 123e12
        cfg.xla_peak_hbm_bytes = 456e9
        assert xo.peak_flops_per_chip() == 123e12
        assert xo.peak_hbm_bytes_per_sec() == 456e9
    finally:
        cfg.xla_peak_flops = 0.0
        cfg.xla_peak_hbm_bytes = 0.0
    # device-kind strings as the runtime spells them (bare "v5" is a v5p;
    # a v5e chip prints "TPU v5 lite")
    flops = xo._TPU_PEAK_FLOPS
    assert xo._tpu_table_lookup(flops, "tpu", "TPU v5e") == 197e12
    assert xo._tpu_table_lookup(flops, "tpu", "TPU v5 lite") == 197e12
    assert xo._tpu_table_lookup(flops, "tpu", "TPU v5") == 459e12
    assert xo._tpu_table_lookup(flops, "tpu", "TPU v4") == 275e12


def test_a_device_that_is_not_in_the_peak_table_is_an_error():
    """No default for an unknown TPU kind, no nominal number for another
    platform: a roofline against a guessed peak is a wrong number."""
    with pytest.raises(xo.UnknownDeviceError, match="TPU v9"):
        xo._tpu_table_lookup(xo._TPU_PEAK_FLOPS, "tpu", "TPU v9")
    with pytest.raises(xo.UnknownDeviceError, match="platform='cpu'"):
        xo._tpu_table_lookup(xo._TPU_PEAK_HBM, "cpu", "cpu")
    with pytest.raises(xo.UnknownDeviceError):  # this process: CPU backend
        xo.peak_flops_per_chip()
    with pytest.raises(xo.UnknownDeviceError):
        xo.peak_hbm_bytes_per_sec()


# --------------------------------------------------------------------------- #
# Recompile-storm detector (unit: hand-built flat registries)
# --------------------------------------------------------------------------- #


def _flat(recompiles, compile_s, churn=()):
    flat = {
        "ray_tpu_xla_recompiles_total": [
            ((("program", p),), v) for p, v in recompiles.items()],
        "ray_tpu_xla_compile_seconds_total": [
            ((("program", p),), v) for p, v in compile_s.items()],
    }
    if churn:
        flat["ray_tpu_xla_shape_churn"] = [
            ((("program", p), ("from", a), ("to", b)), 1.0)
            for p, a, b in churn]
    return flat


def test_storm_detector_trigger_hysteresis_clear():
    from ray_tpu.train.health import RecompileStormDetector

    det = RecompileStormDetector()  # defaults: trigger 3, clear after 2
    assert det.trigger == 3 and det.clear_ticks == 2

    # tick 0: baseline — 4 pre-existing recompiles count as the first
    # delta and trigger immediately (a storm already in progress)
    ch = det.update(_flat({"p": 4.0}, {"p": 1.5},
                          churn=[("p", "f32[4]", "f32[5]")]))
    assert ch == [{"key": "p", "state": "triggered", "recompiles": 4}]
    assert det.active == {"p": 4.0}

    # still churning: stays active, no duplicate trigger event
    assert det.update(_flat({"p": 9.0}, {"p": 3.0})) == []
    assert det.active["p"] == 5.0

    # one quiet tick: hysteresis holds it active
    assert det.update(_flat({"p": 9.0}, {"p": 3.0})) == []
    assert "p" in det.active
    # second quiet tick: cleared
    ch = det.update(_flat({"p": 9.0}, {"p": 3.0}))
    assert ch == [{"key": "p", "state": "cleared"}]
    assert det.active == {}

    # sub-trigger churn never alarms
    assert det.update(_flat({"p": 11.0}, {"p": 3.5})) == []
    assert det.active == {}


def test_storm_detector_quiet_interruption_resets_hysteresis():
    from ray_tpu.train.health import RecompileStormDetector

    det = RecompileStormDetector()
    det.update(_flat({"q": 3.0}, {"q": 1.0}))
    assert "q" in det.active
    det.update(_flat({"q": 3.0}, {"q": 1.0}))       # quiet 1/2
    det.update(_flat({"q": 4.0}, {"q": 1.2}))       # churned again: reset
    det.update(_flat({"q": 4.0}, {"q": 1.2}))       # quiet 1/2
    assert "q" in det.active                        # not yet cleared
    ch = det.update(_flat({"q": 4.0}, {"q": 1.2}))  # quiet 2/2
    assert ch == [{"key": "q", "state": "cleared"}]


# --------------------------------------------------------------------------- #
# Goodput compile column + timeline attribution joins
# --------------------------------------------------------------------------- #


def _span(name, src, ts_s, dur_s, **extra):
    return {"ph": "X", "cat": "span", "name": name,
            "ts": ts_s * 1e6, "dur": dur_s * 1e6,
            "args": {"source": src, **extra}}


def test_goodput_compile_column_backfills_from_xla_spans():
    from ray_tpu.util.goodput import classify_badput

    events = [
        _span("spmd.compute", "A", 0.0, 1.0),
        _span("spmd.compile", "A", 1.0, 2.0),
        # same wall time seen program-by-program on A: must NOT add
        _span("xla.compile", "A", 1.0, 1.5, program="spmd.train_step"),
        # a source that never hits the spmd seam (serve decode): the
        # observatory span is its only compile signal — back-filled
        _span("xla.compile", "B", 1.0, 0.5, program="llama.decode"),
    ]
    ledger = classify_badput(events)
    assert ledger["window"]["wall_s"] == pytest.approx(3.0)
    assert ledger["badput_s"]["compile"] == pytest.approx(1.25)  # mean(2, .5)
    assert ledger["goodput_s"] == pytest.approx(1.0)

    # xla.compile never defines the window (a serve-only cluster must
    # not grow a fake train window out of compile spans alone) ...
    widened = classify_badput(
        events + [_span("xla.compile", "B", 10.0, 5.0, program="x")])
    assert widened["window"]["wall_s"] == pytest.approx(3.0)
    # ... and alone it produces an empty ledger
    only = classify_badput(
        [_span("xla.compile", "B", 0.0, 5.0, program="x")])
    assert only["window"]["wall_s"] == 0.0 and only["steps"] == 0


def test_attribute_trace_has_per_program_compile_rows():
    from ray_tpu.util.flight_recorder import (attribute_trace,
                                              format_attribution)

    events = [
        _span("spmd.compute", "A", 0.0, 1.0),
        _span("xla.compile", "A", 1.0, 0.25, program="spmd.train_step"),
        _span("xla.compile", "A", 2.0, 0.35, program="spmd.train_step"),
        _span("xla.compile", "B", 1.0, 0.10, program="llama.decode"),
    ]
    report = attribute_trace(events)
    rows = report["xla_compile_s"]
    assert rows["spmd.train_step"] == {"compiles": 2,
                                       "compile_s": pytest.approx(0.6)}
    assert rows["llama.decode"] == {"compiles": 1,
                                    "compile_s": pytest.approx(0.1)}
    text = format_attribution(report)
    assert "xla spmd.train_step" in text
    assert "(2 compile(s))" in text


# --------------------------------------------------------------------------- #
# E2E (the ISSUE acceptance drill): a shape-churning jit raises a storm
# WARNING visible via cluster events AND GET /api/xla
# --------------------------------------------------------------------------- #


def test_shape_churn_storm_visible_in_events_and_api():
    import itertools
    import json
    import time
    import urllib.request

    import jax
    import jax.numpy as jnp

    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.util import state

    ray_tpu.init(num_cpus=1, num_tpus=0)
    dash = None
    try:
        from ray_tpu.core.runtime import get_current_runtime

        head = get_current_runtime().head
        # the monitor loop builds the HealthMonitor shortly after init
        deadline = time.monotonic() + 30
        while head.health_monitor is None:
            assert time.monotonic() < deadline, "health monitor never started"
            time.sleep(0.05)
        monitor = head.health_monitor

        fn = xo.observe_compiled(jax.jit(lambda x: x * 2), "e2e.churny")
        sizes = itertools.count(4)
        # churn in rounds: each round is >= trigger recompiles, so the
        # storm fires whether our tick or the background 5s tick reads
        # the delta first
        for _ in range(6):
            for _ in range(4):
                fn(jnp.zeros((next(sizes),), jnp.float32))
            monitor.tick()
            if "e2e.churny" in monitor.recompile.active:
                break
        assert "e2e.churny" in monitor.recompile.active

        rows = state.list_cluster_events(severity="WARNING")
        storm = next(r for r in rows
                     if "recompile storm" in r["message"]
                     and r.get("entity_id") == "e2e.churny")
        # the WARNING names the program, the shape churn and the burn
        assert "e2e.churny recompiled" in storm["message"]
        assert "f32[" in storm["message"] and " -> " in storm["message"]
        assert "s compiling" in storm["message"]
        assert storm["attrs"]["recompiles"] >= 3
        assert storm["attrs"]["churn_from"].startswith("f32[")

        dash = start_dashboard(port=0, with_jobs=False)
        base = f"http://127.0.0.1:{dash.address[1]}"
        with urllib.request.urlopen(f"{base}/api/xla", timeout=30) as resp:
            assert resp.status == 200
            api = json.loads(resp.read().decode())
        row = api["programs"]["e2e.churny"]
        assert row["recompiles"] >= 3
        assert row["compiles"] >= 4
        assert row["compile_seconds"] > 0
        assert row["churn"]          # shape transitions shipped too
        assert "e2e.churny" in api["storms"]

        # the CLI renders the same fold, including the storm banner
        import argparse

        from ray_tpu.__main__ import _cmd_xla

        assert _cmd_xla(argparse.Namespace(
            address=base, json=False, program="e2e.churny")) == 0
        assert _cmd_xla(argparse.Namespace(
            address=base, json=False, program="no.such.program")) == 1
        text = xo.format_xla(xo.xla_report(head))
        assert "ACTIVE RECOMPILE STORMS" in text
        assert "e2e.churny" in text
    finally:
        if dash is not None:
            dash.stop()
        ray_tpu.shutdown()
