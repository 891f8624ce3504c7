"""``setup_s`` taken apart: a cell brought up as ``run.py`` brings it up, stopped
at the window's opening, and every second from this script's start to there put
down to the program's own set-up spans or to a part of the harness.

    python3 benchmarks/sweep/setup_parts.py --workload train-mistral7b-1chip \
        --seed 707 --out chiprun_out/setup_parts/train-mistral7b-1chip.json

The program's parts are its flight-recorder spans (``runtime.init``,
``trainer.place`` / ``serve.deploy``, ``worker.boot``, ``jax.import``,
``jax.backend_init``, ``spmd.build`` / ``spmd.init_state`` / ``spmd.compile``,
``engine.build`` / ``.weights`` / ``.stores``, ``dag.lane_build``, ``xla.compile``
and the four ``jax.*`` of a compile: ``flight_recorder.SETUP_SPANS``), read the
way an operator gets them: every process reports its ring to the head and
``ray_tpu.util.timeline.timeline()`` merges them onto one wall clock. The
HARNESS's parts (``harness.*``) are wall-clock stamps this script takes around
the benchmark's own stretches: its imports, the float32 reference check, the
train cell's warm loop and the measured loop's first step, serving's ``build_s``
/ ``warm_s`` / ``check_s`` (``serve_cell._prepare`` returns them) and the
streams that bring the lane up. ``[T_START, window open]`` is then cut by
INNERMOST span, whatever its process: ``parts_s`` sums to ``setup_s`` with
``unattributed_s``. ``inclusive_s`` is every name's whole length (a program
span inside ``harness.reference_check`` is in both).

``train_cell`` / ``serve_cell`` are used unedited. To stamp inside them the
script wraps, in the process where they run, ``check_against_reference`` and
``spmd_train_loop`` (train) and ``BenchLM._prepare`` (serve); a train cell's
first backend touch is the harness's (``jax.local_device_count()``), made here
through the program's ``backend_devices`` so that it lies under
``jax.backend_init``. A train cell's window is ``--seconds`` long (default 1: two
steps), a serving cell's is not opened. A program without the reader (before
PR 50: no ``flight_recorder.cut_innermost``) is refused with exit 2 before
anything is brought up. Run it on the chip;
``--rehearsal`` walks it at ``rehearsal.json``'s sizes on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# steady-state spans that lie inside set-up too (the warm loop's steps, the
# probe streams' prefills): named, so that they are not "unattributed"
STEADY = ("spmd.compute", "serve.prefill", "engine.decode")


def stamped_train_loop(config):
    """In the trainer's worker: ``train_cell.bench_train_loop`` with the
    harness's stretches stamped on the wall clock and reported as a row."""
    import time as _time

    from benchmarks.lib import train_cell
    from ray_tpu.train import session, spmd
    from ray_tpu.util import device_telemetry

    stamps: list = []

    def stamped(name, fn):
        def inner(*a, **kw):
            t0 = _time.time()
            try:
                return fn(*a, **kw)
            finally:
                stamps.append([name(), t0, _time.time()])
        return inner

    t0 = _time.time()
    import jax  # noqa: F401 - what bench_train_loop imports first

    from ray_tpu.models import llama  # noqa: F401
    from ray_tpu.ops import flash_attention  # noqa: F401
    stamps.append(["harness.worker_imports", t0, _time.time()])
    t0 = _time.time()
    getattr(device_telemetry, "backend_devices", jax.devices)()
    stamps.append(["harness.backend_first_touch", t0, _time.time()])
    loops = iter(("harness.warm_loop", "harness.measured_loop"))
    real_check, real_loop = (train_cell.check_against_reference,
                             spmd.spmd_train_loop)
    train_cell.check_against_reference = stamped(
        lambda: "harness.reference_check", real_check)
    spmd.spmd_train_loop = stamped(lambda: next(loops), real_loop)
    try:
        return train_cell.bench_train_loop(config)
    finally:
        train_cell.check_against_reference = real_check
        spmd.spmd_train_loop = real_loop
        session.report({"harness_stamps": stamps, "step": -2})


def read_events() -> list:
    """Every process's spans, merged by the head onto its wall clock."""
    from ray_tpu.core.config import global_config
    from ray_tpu.util.timeline import timeline

    # a worker reports its ring every so often: wait out two rounds
    time.sleep(2 * global_config().flight_recorder_report_interval_ms / 1e3 + 0.5)
    return [e for e in timeline()
            if e.get("cat") == "span" and e.get("ph") == "X"]


def bring_up_train(bundle: dict, args) -> dict:
    """``train_cell.run``'s driver half, with the spans read before the
    runtime goes down."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmarks.lib import reducers, spec, traffic as traffic_mod

    layer_specs = spec.layer_specs(bundle)
    run_dir = tempfile.mkdtemp(prefix="bench_train_")
    bench = {"config": bundle["config"], "traffic": bundle["traffic"],
             "seconds": args.seconds, "trace": False,
             "seed": traffic_mod.fold_seed(args.seed),
             "wanted_spans": sorted(reducers.wanted_spans(layer_specs.values())),
             "kernel_patterns": reducers.kernel_patterns(layer_specs.values()),
             "keep_dir": None}
    stamps = [["harness.driver_imports", T_START, time.time()]]
    ray_tpu.init()
    try:
        t0 = time.time()
        result = JaxTrainer(
            stamped_train_loop, train_loop_config={"bench": bench},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=not args.rehearsal,
                chips_per_worker=bundle["cell"]["chips"]),
            run_config=RunConfig(name="bench", storage_path=run_dir),
        ).fit()
        stamps.append(["harness.fit", t0, time.time()])
        events = read_events()
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(run_dir, ignore_errors=True)
    if result.error is not None:
        raise RuntimeError(f"the trainer failed: {result.error}")
    rows = result.metrics_dataframe
    res = [r for r in rows if "bench_result" in r][-1]["bench_result"]
    stamps += [r for r in rows if "harness_stamps" in r][-1]["harness_stamps"]
    return {"open_wall": res["window_open_wall"], "events": events,
            "stamps": stamps, "device": res["device"],
            "harness": {"reference": res.get("reference"),
                        "steps": res["steps"]}}


def bring_up_serve(bundle: dict, args) -> dict:
    """``serve_cell.start`` and the snapshot a window opens with."""
    from benchmarks.lib import serve_cell

    class StampedLM(serve_cell.BenchLM):
        def _prepare(self) -> dict:
            t0 = time.time()
            out = super()._prepare()
            out["prepare_wall"] = t0
            return out

    stamps = [["harness.driver_imports", T_START, time.time()]]
    real = serve_cell.BenchLM
    serve_cell.BenchLM = StampedLM
    try:
        ctx = serve_cell.start(bundle, args)
    finally:
        serve_cell.BenchLM = real
    try:
        t_started = time.time()
        timeout = bundle["config"]["deployment"]["control_timeout_s"]
        snap = ctx["handle"].snapshot.remote().result(timeout=timeout)
        open_wall = time.time() + 0.05  # as serve_cell.open_loop takes wall0
        events = read_events()
    finally:
        serve_cell.stop()
    prepared = ctx["prepared"]
    t = prepared.pop("prepare_wall")
    for key in ("build_s", "warm_s", "check_s"):
        stamps.append([f"harness.prepare.{key[:-2]}", t, t + prepared[key]])
        t += prepared[key]
    stamps.append(["harness.lane_warm", t, t_started])
    stamps.append(["harness.snapshot", t_started, open_wall])
    return {"open_wall": open_wall, "events": events, "stamps": stamps,
            "device": snap["device"],
            "harness": {k: prepared[k] for k in (
                "build_s", "warm_s", "check_s", "warm_streams", "shapes",
                "compile")}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=707)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    args.trace, args.keep, args.fault = 0, None, None

    from ray_tpu.core.accelerators import detect_num_tpu_chips
    from ray_tpu.util import compile_cache, flight_recorder as fr

    from benchmarks.lib import spec

    bundle = spec.cell_bundle(args.workload, rehearsal=args.rehearsal)
    chips = bundle["cell"]["chips"]
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                f"platform_device_count={chips}").strip()
    elif detect_num_tpu_chips() < chips:
        print(f"{args.workload} needs {chips} TPU chip(s)", file=sys.stderr)
        return 3
    compile_cache.configure(os.environ)
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

    cut = getattr(fr, "cut_innermost", None)
    if cut is None:
        print("this program has no set-up reader (flight_recorder."
              "cut_innermost)", file=sys.stderr)
        return 2
    kind = bundle["traffic"]["kind"]
    up = (bring_up_train if kind == "train_steps" else bring_up_serve)(
        bundle, args)
    names = set(getattr(fr, "SETUP_SPANS", ())) | set(STEADY)
    lo, hi = T_START, up["open_wall"]
    # the processes on set-up's path: the driver and whoever brought a
    # backend, a loop or an engine up. A pooled worker that boots beside them
    # delays nobody: its boot is listed, and left out of the cut
    on_path = {e["args"].get("source") for e in up["events"]
               if e["name"] in names and e["name"] != "worker.boot"}
    spans, off_path = [], {}
    for e in up["events"]:
        if e["name"] not in names:
            continue
        source = e["args"].get("source", "?")
        name = f"{e['name']}@{source}"
        if source in on_path:
            spans.append((name, e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6))
        else:
            off_path[name] = off_path.get(name, 0.0) + e["dur"] / 1e6
    spans += [(name, t0, t1) for name, t0, t1 in up["stamps"]]
    inclusive: dict = {}
    for name, t0, t1 in spans:
        if t1 > lo and t0 < hi:
            inclusive[name] = inclusive.get(name, 0.0) + min(t1, hi) - max(t0, lo)
    parts = cut(spans, lo, hi)
    report = fr.attribute_trace(up["events"])
    out = {"workload": args.workload, "seed": args.seed,
           "device": {k: up["device"][k] for k in ("platform", "kind", "count")},
           "setup_s": hi - lo,
           "unattributed_s": parts.pop("unattributed", 0.0),
           "parts_s": dict(sorted(parts.items(), key=lambda kv: -kv[1])),
           "inclusive_s": dict(sorted(inclusive.items(), key=lambda kv: -kv[1])),
           "off_path_s": off_path,
           "programs": {source: rec["programs"] for source, rec in
                        (report.get("setup") or {}).items()
                        if rec["programs"]},
           "compile": {k: up["device"].get(k) for k in (
               "compile_s", "cache_hits", "cache_misses")},
           "harness": up["harness"]}
    print(fr.format_attribution(report))
    print(json.dumps(out, default=str), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(dict(out, setup=report.get("setup"),
                           stamps=up["stamps"]), f, indent=1, default=str)
        # the spans themselves, for ``python -m ray_tpu timeline --input
        # <file> --attribute`` (and for cutting them again offline)
        with open(os.path.splitext(args.out)[0] + ".trace.json", "w") as f:
            json.dump([e for e in up["events"] if e["ts"] / 1e6 < hi + 1], f)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
