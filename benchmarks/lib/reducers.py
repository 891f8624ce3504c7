"""Per-layer metric readers. A metric's file in ``layer_metrics/`` names one
of these and its arguments; the reader gets the run's evidence and returns
the value, or None where there was nothing to read (the harness then leaves
the metric out of the line).

Evidence (all JSON-able, gathered by the cell runner):
  ``spans``     {span name: [[t0, dur], ...]} inside the measured window
  ``counters``  {name: number} over the measured window
  ``trace``     the digest of ``lib/trace.reduce`` (traced runs only)
  ``e2e``       {end-to-end metric name: value} of this run
  ``config``, ``traffic``, ``chips``, ``device_kind``
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

from . import flops, stats


def _dur(ev: dict, names) -> float:
    return sum(d for n in names for _, d in ev["spans"].get(n, []))


def span_share(ev: dict, numerator, denominator) -> Optional[float]:
    """Percent of the denominator spans' time spent in the numerator's."""
    den = _dur(ev, denominator)
    return 100.0 * _dur(ev, numerator) / den if den > 0 else None


def span_median_ms(ev: dict, span: str) -> Optional[float]:
    durs = [d for _, d in ev["spans"].get(span, [])]
    return 1e3 * stats.median(durs) if durs else None


def counter_value(ev: dict, counter: str) -> Optional[float]:
    v = ev["counters"].get(counter)
    return None if v is None else float(v)


def train_mfu(ev: dict, rate_metric: str) -> Optional[float]:
    """Percent of the chip's published peak: operations a trained token
    needs (forward and backward, recomputation not counted) x measured
    tokens per second per chip over the peak."""
    rate = ev["e2e"].get(rate_metric)
    if rate is None:
        return None
    per_token = flops.train_flops_per_token(ev["config"], ev["traffic"]["seq"])
    return 100.0 * per_token * rate / flops.peaks(ev["device_kind"])["flops_per_s"]


def kernel_roofline(ev: dict, kernels: Dict[str, dict]) -> Optional[float]:
    """Percent of the roofline reached by the named kernels together: the
    least time the chip could take for the calls the trace holds (each
    call's larger of operations/peak and bytes/peak, at the cell's shapes)
    over the device time those calls took. ``kernels``: trace key ->
    {"pattern": substring of the operation's name, "kind": fwd|dq|dkv}."""
    tr = ev.get("trace")
    if not tr:
        return None
    cfg, traffic = ev["config"], ev["traffic"]
    least = took = 0.0
    for key, spec in kernels.items():
        rec = tr["kernels"].get(key)
        if not rec or not rec["calls"]:
            return None  # a kernel of the set did not run: no share to give
        ob = flops.flash_ops_bytes(
            spec["kind"], traffic["batch_per_chip"], traffic["seq"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])
        least += rec["calls"] * flops.roofline_seconds(
            ob["ops"], ob["bytes"], ev["device_kind"])["seconds"]
        took += rec["seconds"]
    return 100.0 * least / took if took > 0 else None


READERS: Dict[str, Callable] = {
    "span_share": span_share,
    "span_median_ms": span_median_ms,
    "counter_value": counter_value,
    "train_mfu": train_mfu,
    "kernel_roofline": kernel_roofline,
}


def read_metric(spec: dict, evidence: dict) -> Optional[float]:
    return READERS[spec["reader"]](evidence, **spec.get("args", {}))


def wanted_spans(specs) -> set:
    """Span names the cell's metric files read (so that only those travel
    from the process that holds the chip)."""
    names = set()
    for spec in specs:
        args = spec.get("args", {})
        if "span" in args:
            names.add(args["span"])
        for key in ("numerator", "denominator"):
            names.update(args.get(key, []))
    return names


def kernel_patterns(specs) -> Dict[str, str]:
    out = {}
    for spec in specs:
        for key, k in spec.get("args", {}).get("kernels", {}).items():
            out[key] = k["pattern"]
    return out
