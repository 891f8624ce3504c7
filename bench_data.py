"""Data ingest microbenchmarks: operator fusion + zero-copy rechunk.

Prints ONE JSON line (same convention as bench_serve.py):

    {"bench": "data",
     "fused":   {"rows_per_s": .., "store_puts": ..},
     "unfused": {"rows_per_s": .., "store_puts": ..},
     "fusion_speedup": ..,
     "puts_bound": <stages x blocks>,
     "rechunk": {"short_us_per_batch": .., "long_us_per_batch": ..,
                 "cost_ratio": ..}}

Pipeline bench: rows/s through read -> map_batches -> map_batches ->
iter_batches on a fresh cluster per rep. Each mode runs in its OWN
subprocess (the fusion knob is snapshotted by pools/caches, and a fresh
interpreter per rep keeps reps independent); fused/unfused reps are
INTERLEAVED and the per-mode MAX of rows/s (i.e. min runtime) is
reported — this box is ~1.5 cores and noisy, scheduling luck swings a
single rep far more than the effect being measured.

The fused phase also reports object-store puts observed in the driver
registry: fusion's mechanism is materializing ONE block per chain
instead of one per stage, so fused puts must come in under
stages x blocks (the unfused floor).

Rechunk bench: iter_batches over pre-materialized in-process blocks at
two stream lengths; per-batch cost must be flat in stream length (the
old carry re-concat grew linearly -> quadratic total).

``--check`` exits non-zero when fused rows/s regresses below unfused
(--min-speedup, default 1.0) or the rechunk per-batch cost ratio
exceeds --max-rechunk-ratio (default 3.0: generous noise allowance on
a cost that used to scale ~8x at these stream lengths).

Runs under ``JAX_PLATFORMS=cpu`` (no accelerator needed).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROWS = 200_000
BLOCKS = 8
STAGES = 3  # read + 2 map_batches


def _store_puts() -> float:
    from ray_tpu.util.metrics import registry

    m = registry().snapshot().get("ray_tpu_object_store_puts_total")
    return sum(m["values"].values()) if m else 0.0


def run_pipeline_phase(rows: int, blocks: int) -> dict:
    import ray_tpu
    from ray_tpu import data as rd

    ray_tpu.init(num_cpus=4, num_tpus=0)
    ds = (rd.range(rows, parallelism=blocks)
          .map_batches(lambda b: {"id": b["id"] * 2}, batch_format="numpy")
          .map_batches(lambda b: {"id": b["id"] + 1}, batch_format="numpy"))
    # warmup: worker pool spin-up, function registration, first-run jits
    sum(len(b["id"]) for b in rd.range(
        rows // 10, parallelism=blocks).map_batches(
        lambda b: {"id": b["id"]}, batch_format="numpy")
        .iter_batches(batch_size=4096, batch_format="numpy"))

    puts_before = _store_puts()
    t0 = time.perf_counter()
    seen = 0
    for batch in ds.iter_batches(batch_size=4096, batch_format="numpy",
                                 prefetch_batches=2):
        seen += len(batch["id"])
    dt = time.perf_counter() - t0
    puts = _store_puts() - puts_before
    assert seen == rows, (seen, rows)
    ray_tpu.shutdown()
    return {"rows_per_s": round(rows / dt, 1), "elapsed_s": round(dt, 4),
            "store_puts": puts}


def run_rechunk_phase() -> dict:
    """Per-batch rechunk cost at two stream lengths, pure in-process
    (no cluster): the iterator's BlockBuffer against synthetic blocks."""
    import numpy as np

    from ray_tpu.data.block import block_from_numpy
    from ray_tpu.data.iterator import BlockBuffer

    def bench(n_blocks: int, rounds: int = 5) -> float:
        rows_per_block, batch = 1000, 900  # misaligned -> spanning batches
        blocks = [block_from_numpy(
            {"x": np.arange(rows_per_block, dtype=np.int64)})
            for _ in range(n_blocks)]
        best = float("inf")
        for _ in range(rounds):
            buf = BlockBuffer()
            batches = 0
            t0 = time.perf_counter()
            for b in blocks:
                buf.add_block(b)
                while buf.num_rows() >= batch:
                    buf.take(batch)
                    batches += 1
            while buf.num_rows():
                buf.take(min(batch, buf.num_rows()))
                batches += 1
            dt = time.perf_counter() - t0
            best = min(best, dt / batches * 1e6)
        return best

    short = bench(40)
    long_ = bench(320)
    return {"short_us_per_batch": round(short, 2),
            "long_us_per_batch": round(long_, 2),
            "cost_ratio": round(long_ / short, 3)}


def _spawn_phase(mode: str, rows: int, blocks: int) -> dict:
    env = dict(os.environ)
    env["RAY_TPU_DATA_FUSION"] = "1" if mode == "fused" else "0"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--phase", "pipeline",
         "--rows", str(rows), "--blocks", str(blocks)],
        env=env, capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(
            f"phase {mode} failed:\n{out.stdout}\n{out.stderr}")
    for line in reversed(out.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"phase {mode} printed no JSON:\n{out.stdout}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--blocks", type=int, default=BLOCKS)
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved repetitions per mode; best rep "
                         "(min runtime) is reported")
    ap.add_argument("--phase", choices=["pipeline"],
                    help="internal: run one pipeline rep in-process")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on fused-vs-unfused regression or "
                         "rechunk cost growth")
    ap.add_argument("--min-speedup", type=float, default=1.0)
    ap.add_argument("--max-rechunk-ratio", type=float, default=3.0)
    ap.add_argument("--out", default="BENCH_DATA.json",
                    help="also write the JSON result here ('' = skip)")
    args = ap.parse_args()

    if args.phase == "pipeline":
        print(json.dumps(run_pipeline_phase(args.rows, args.blocks)))
        return 0

    results = {"fused": [], "unfused": []}
    for rep in range(args.reps):  # interleave modes inside each rep
        for mode in ("fused", "unfused"):
            r = _spawn_phase(mode, args.rows, args.blocks)
            results[mode].append(r)
            print(f"# rep {rep} {mode}: {r}", file=sys.stderr)

    def best(mode: str) -> dict:
        by_time = min(results[mode], key=lambda r: r["elapsed_s"])
        return {"rows_per_s": by_time["rows_per_s"],
                "elapsed_s": by_time["elapsed_s"],
                "store_puts": min(r["store_puts"] for r in results[mode])}

    fused, unfused = best("fused"), best("unfused")
    rechunk = run_rechunk_phase()
    out = {
        "bench": "data",
        "rows": args.rows,
        "blocks": args.blocks,
        "fused": fused,
        "unfused": unfused,
        "fusion_speedup": round(
            fused["rows_per_s"] / unfused["rows_per_s"], 3),
        "puts_bound": STAGES * args.blocks,
        "rechunk": rechunk,
    }
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")

    ok = True
    if args.check:
        if fused["store_puts"] >= STAGES * args.blocks:
            print(f"# FAIL: fused store puts {fused['store_puts']} >= "
                  f"stages x blocks = {STAGES * args.blocks}",
                  file=sys.stderr)
            ok = False
        if out["fusion_speedup"] < args.min_speedup:
            print(f"# FAIL: fusion speedup {out['fusion_speedup']} < "
                  f"{args.min_speedup}", file=sys.stderr)
            ok = False
        if rechunk["cost_ratio"] > args.max_rechunk_ratio:
            print(f"# FAIL: rechunk cost ratio {rechunk['cost_ratio']} > "
                  f"{args.max_rechunk_ratio}", file=sys.stderr)
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
