"""One cell of the benchmark, one process, one last line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are files found
by the names in ``BENCHMARK.json`` (see ``benchmarks/README.md``). This
process never imports jax: every device touch happens in a worker that the
runtime bound to the chips. With no chip, or fewer than the cell asks for,
it exits non-zero and prints no result. ``--rehearsal`` walks the same code
at the tiny sizes of ``rehearsal.json`` on the CPU to debug the harness; its
line says ``"platform": "cpu"`` and is never a measurement.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

T_START = time.time()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def build_line(out: dict, bundle: dict, trace: bool, rehearsal: bool) -> dict:
    from benchmarks.lib import reducers

    device = out["device"]
    line_device = {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": device["memory_peak_bytes"]}
    why = list(out["why_not_correct"])
    metrics = {}
    if not trace:
        for m in bundle["end_to_end"]:
            value = out["e2e"].get(m["name"])
            if value is None or not math.isfinite(value):
                why.append(f"end-to-end metric {m['name']} was not measured")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bundle["per_layer"]:
            try:
                value = reducers.read_metric(out["layer_specs"][m["name"]],
                                             out["evidence"])
            except KeyError:
                if not rehearsal:  # on the CPU there are no peaks to read
                    raise
                value = None
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if rehearsal:  # a CPU number never stands under a device metric's name
        metrics = {f"rehearsal_only.{k}": v for k, v in metrics.items()}
    line = {"correct": not why, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": line_device}
    digest = out["evidence"].get("trace")
    if trace and digest:
        line_device["busy_s"] = digest["busy_s"]
        line_device["window_s"] = digest["window_s"]
        line["breakdown"] = {"device_ops": digest["device_ops"],
                             "idle_gaps": digest["idle_gaps"]}
    elif trace and device["platform"] == "tpu":
        why.append("the traced window holds no device operation")
        line["correct"] = False
    line["why_not_correct"] = why
    line["detail"] = out["detail"]
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU; debugs the harness only")
    ap.add_argument("--fault", default=None,
                    help="rehearsal only, e.g. decode:5: every 5th call of "
                         "the engine's decode raises")
    ap.add_argument("--keep", default=None,
                    help="directory to keep the trace's extract in")
    args = ap.parse_args()
    if args.fault and not args.rehearsal:
        print("--fault is for --rehearsal only", file=sys.stderr)
        return 2

    try:
        import ray_tpu  # noqa: F401
        from ray_tpu.core.accelerators import detect_num_tpu_chips
        from ray_tpu.util import compile_cache
    except ImportError as e:
        print(f"benchmarks/run.py runs from the root of a ray_tpu checkout "
              f"(cannot import the program: {e})", file=sys.stderr)
        return 2
    from benchmarks.lib import spec

    bundle = spec.cell_bundle(args.workload, rehearsal=args.rehearsal)
    if args.seconds is None:
        args.seconds = float(bundle["bench"]["run_seconds"])
    chips = bundle["cell"]["chips"]
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:  # virtual CPU devices stand in for the chips
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                f"platform_device_count={chips}").strip()
    else:
        found = detect_num_tpu_chips()
        if found < chips:
            print(f"{args.workload} needs {chips} TPU chip(s); this host has "
                  f"{found}. Nothing is measured on anything else.",
                  file=sys.stderr)
            return 3
    # the compile cache: where JAX_COMPILATION_CACHE_DIR says, else a fixed
    # directory inside this checkout; every worker inherits it
    compile_cache.configure(os.environ)
    # workers import the benchmark's modules by name
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get(
        "PYTHONPATH", "")

    kind = bundle["traffic"]["kind"]
    try:
        if kind == "train_steps":
            from benchmarks.lib import train_cell as runner
        elif kind == "open_loop":
            from benchmarks.lib import serve_cell as runner
        else:
            raise ValueError(f"unknown traffic kind {kind!r}")
        out = runner.run(bundle, args, T_START)
    except Exception:  # noqa: BLE001 - could not measure at all
        traceback.print_exc()
        print(f"{args.workload}: the run could not measure", file=sys.stderr)
        return 1
    assert "jax" not in sys.modules or args.rehearsal, \
        "the benchmark's driver imported jax"
    want = "cpu" if args.rehearsal else "tpu"
    dev = out["device"]
    if dev["platform"] != want or (not args.rehearsal
                                   and dev["count"] != chips):
        print(f"{args.workload} ran on {dev['count']} x {dev['platform']}, "
              f"not {chips} x {want}: no result", file=sys.stderr)
        return 1
    line = build_line(out, bundle, bool(args.trace), args.rehearsal)
    # from the window's close to here: reading evidence, and leaving the chip
    line["detail"]["leave_s"] = time.time() - out["window_close_wall"]
    line["detail"]["wall_s"] = time.time() - T_START
    if line["why_not_correct"]:
        print(f"{args.workload}: not correct: {line['why_not_correct']}",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # daemon client threads may still sit in a closed stream
