"""Spreads of the runs ``measure_spread.sh`` left in a directory: for each
end-to-end metric and each set, the median and the interquartile range
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 benchmarks/sweep/spread.py chiprun_out/m_<cell>
"""

import glob
import json
import statistics
import sys


def last_line(path: str) -> dict:
    with open(path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def main(directory: str) -> int:
    sets = {s: [last_line(p) for p in sorted(glob.glob(f"{directory}/out_{s}_*.txt"))]
            for s in "AB"}
    rows = [r for runs in sets.values() for r in runs]
    if not rows:
        print(f"no runs under {directory}", file=sys.stderr)
        return 1
    print(directory, "runs", [len(v) for v in sets.values()], "all correct",
          all(r["correct"] for r in rows), "failed", sum(r["failed"] for r in rows))
    for metric in rows[0]["metrics"]:
        for s, runs in sets.items():
            values = [r["metrics"][metric]["value"] for r in runs]
            if len(values) < 3:
                continue
            q = statistics.quantiles(values, n=4)
            med = statistics.median(values)
            print(f"  {metric} set {s}: median {med!r} spread "
                  f"{100 * (q[2] - q[0]) / med:.3f}% values {sorted(values)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
