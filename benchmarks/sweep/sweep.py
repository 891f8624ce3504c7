"""Find an open-loop cell's knee: the highest arrival rate the deployment
sustains with no growing backlog. One set-up, then one window per rate.

    python3 benchmarks/sweep/sweep.py --workload serve-internlm2-prefill-open \
        --rates 1,1.5,2,2.5,3,4 --seconds 25 --out chiprun_out/sweep.json

Each rate runs the cell's traffic file with ``rate_per_s`` replaced and a
seed of its own (a repeated prompt would be answered from the prefix
cache). A rate is sustained when requests complete as fast as they are
sent: nearly none in flight when the window closes, and a short drain. The
cell then runs at four fifths of the highest sustained rate; write that
into the traffic file as ``rate_per_s`` and keep this command's output
beside it. Run it on the chip (``chiprun -- python3 benchmarks/sweep/...``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1000)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    args.trace, args.keep, args.fault = 0, None, None

    from ray_tpu.core.accelerators import detect_num_tpu_chips
    from ray_tpu.util import compile_cache

    from benchmarks.lib import serve_cell, spec

    bundle = spec.cell_bundle(args.workload, rehearsal=args.rehearsal)
    if bundle["traffic"]["kind"] != "open_loop":
        print("a sweep is for an open-loop cell", file=sys.stderr)
        return 2
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif detect_num_tpu_chips() < 1:
        print("no TPU chip on this host", file=sys.stderr)
        return 3
    compile_cache.configure(os.environ)
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

    rows = []
    t0 = time.time()
    ctx = serve_cell.start(bundle, args)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            tr = dict(bundle["traffic"], rate_per_s=rate)
            args.seed += 1
            ctx["stream"] = serve_cell.new_stream(
                tr, bundle["config"], args.seed, args.seconds)
            res = serve_cell.run_window(ctx, tr, args, args.seconds)
            row = {"rate_per_s": rate, "seed": args.seed, **{
                key: res.get(key) for key in (
                    "attempted", "failed", "completed", "requests_per_s_done",
                    "in_flight_at_close", "drain_s", "ttft_p50_ms",
                    "ttft_p95_ms", "lateness_p95_ms", "errors")}}
            spans = res["spans"].get("serve.prefill", [])
            if spans:
                durs = sorted(d for _, d in spans)
                row["prefill_ms_median"] = 1e3 * durs[len(durs) // 2]
            row["device"] = {k2: res["after"]["device"][k2]
                             for k2 in ("platform", "kind", "count")}
            rows.append(row)
            print(json.dumps(row), flush=True)
            if (row["in_flight_at_close"] or 0) > 0.3 * row["attempted"]:
                break  # far past the knee: higher rates only cost drain time
    finally:
        serve_cell.stop()
    out = {"workload": args.workload, "seconds": args.seconds,
           "traffic": bundle["traffic"], "prepared": ctx["prepared"],
           "wall_s": time.time() - t0, "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
