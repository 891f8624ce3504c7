"""Time to first token, joined request by request: what the client measured
against the replica's own spans of the same request.

    python3 benchmarks/sweep/ttft_parts.py --workload serve-internlm2-prefill-open \
        --seed 707 --seconds 50 --out chiprun_out/ttft_parts.json

One window of the cell's traffic, as ``run.py`` offers it (no profiler). The
replica's spans come the way an operator gets them: the worker reports its
flight recorder to the head and ``ray_tpu.util.timeline.timeline()`` merges
them, tags and all. For every request of the window the four spans that carry
its ``corr`` (``dag.stream_ingress``, ``serve.sched_wait``, ``serve.prefill``,
``serve.first_token_hold``) are summed and set against the client's time from
sending the request to its first token; the remainder is the driver's dispatch
and the reply's way back. The k-th request sent is the k-th ``corr`` of the
window: the lane is one ring, written in send order. Also prints what
``python -m ray_tpu timeline --attribute`` would. Run it on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STOPS = ("dag.stream_ingress", "serve.sched_wait", "serve.prefill",
        "serve.first_token_hold")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=707)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    args.trace, args.keep, args.fault = 0, None, None

    from ray_tpu.core.accelerators import detect_num_tpu_chips
    from ray_tpu.core.config import global_config
    from ray_tpu.util import compile_cache, flight_recorder as fr
    from ray_tpu.util.timeline import timeline

    from benchmarks.lib import serve_cell, spec, stats

    bundle = spec.cell_bundle(args.workload, rehearsal=args.rehearsal)
    if bundle["traffic"]["kind"] != "open_loop":
        print("for an open-loop cell", file=sys.stderr)
        return 2
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif detect_num_tpu_chips() < 1:
        print("no TPU chip on this host", file=sys.stderr)
        return 3
    compile_cache.configure(os.environ)
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

    tr = bundle["traffic"]
    ctx = serve_cell.start(bundle, args)
    try:
        load = serve_cell.Load(ctx["handle"], ctx["stream"],
                               tr["stream_item_timeout_s"])
        win = serve_cell.open_loop(load, tr, args.seconds)
        # the worker reports its spans every so often: wait out two rounds
        time.sleep(2 * global_config().flight_recorder_report_interval_ms / 1e3 + 0.5)
        events = [e for e in timeline()
                  if e.get("cat") == "span" and e.get("ph") == "X"]
        device = ctx["handle"].snapshot.remote().result(timeout=60)["device"]
    finally:
        serve_cell.stop()

    # the window's requests: every corr whose ring residency began after the
    # window opened (the warm-up's streams ended 50 ms or more before)
    lo_us = (win["wall0"] - 0.04) * 1e6
    parts = {name: {} for name in STOPS}
    for e in events:
        if e["name"] in parts and isinstance(e["args"].get("corr"), int):
            parts[e["name"]][e["args"]["corr"]] = e
    corrs = sorted(c for c, e in parts["dag.stream_ingress"].items()
                   if e["ts"] >= lo_us)
    outs = sorted((o for o in load.snapshot() if o.arrivals and not o.error),
                  key=lambda o: o.sent)
    rows = []
    if len(corrs) != len(outs):
        print(f"{len(outs)} answered requests but {len(corrs)} corrs in the "
              f"window: no join", file=sys.stderr)
    else:
        for corr, o in zip(corrs, outs):
            ms = {n: parts[n][corr]["dur"] / 1e3 if corr in parts[n] else None
                  for n in STOPS}
            client = 1e3 * (o.arrivals[0] - o.sent)
            inside = sum(v for v in ms.values() if v is not None)
            rows.append({"corr": corr, "index": o.index, "n_prompt": o.n_prompt,
                         "late_ms": 1e3 * (o.sent - o.due),
                         "client_ttft_ms": client, **ms,
                         "inside_ms": inside, "rest_ms": client - inside})
    summary = {}
    if rows:
        for key in ("client_ttft_ms", *STOPS, "inside_ms", "rest_ms", "late_ms"):
            vals = [r[key] for r in rows if r[key] is not None]
            summary[key] = {"n": len(vals), "median": stats.median(vals),
                            "p95": stats.percentile(vals, 0.95),
                            "min": min(vals), "max": max(vals)}
        summary["rest_share_median"] = stats.median(
            [r["rest_ms"] / r["client_ttft_ms"] for r in rows])
    report = fr.attribute_trace(events)
    out = {"workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "device": {
               k: device[k] for k in ("platform", "kind", "count")},
           "requests": len(outs), "summary": summary, "rows": rows,
           "serving": report.get("serving")}
    print(fr.format_attribution(report))
    print(json.dumps({k: out[k] for k in ("workload", "device", "requests",
                                          "summary")}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0 if rows else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
