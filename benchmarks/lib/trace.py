"""From a profiler trace to numbers: device busy time, the operations that
took most of it, named kernels' time, and the idle gaps by what the host
was doing. ``extract`` reads an ``.xplane.pb`` (it needs jax, so it runs in
the process that traced); ``reduce`` is pure and works on what ``extract``
returned, so ``checks/`` can run it on a recorded extract.

Clocks: a trace's timestamps count from the trace's own start. The traced
process emits two ``jax.profiler.TraceAnnotation`` markers (``MARK_OPEN``,
``MARK_CLOSE``) and notes ``time.monotonic()`` at the first; that pair puts
the flight recorder's spans (monotonic clock) on the trace's clock and
bounds the window that is reduced.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

MARK_OPEN = "bench.window_open"
MARK_CLOSE = "bench.window_close"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MIN_GAP_S = 20e-6  # shorter holes between operations are launch latency


def newest_xplane(log_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _device_planes(planes) -> list:
    """One plane per chip: ``/device:TPU:<n>`` and nothing after the number
    (a chip's other units have planes of their own, with a suffix)."""
    out = []
    for p in planes:
        if p.name.startswith(DEVICE_PLANE_PREFIX) \
                and p.name[len(DEVICE_PLANE_PREFIX):].strip().isdigit():
            out.append(p)
    return out


def extract(path: str, kernel_patterns: Dict[str, str]) -> dict:
    """``{"markers": {name: start_s}, "devices": [{"plane": name, "ops":
    [[label, start_s, dur_s, kernel], ...]}], "lines": {plane: {line:
    n_events}}}``. An operation's event is named by its whole HLO text;
    ``label`` is its name and result type (``op_label``). ``kernel`` is
    the key of the first of ``kernel_patterns`` (key -> regular expression)
    that matches the whole text or one of the event's string stats, else
    "". The program gives its Pallas kernels no names, so a pattern has to
    tell them apart by their result types."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    markers: Dict[str, float] = {}
    lines: Dict[str, Dict[str, int]] = {}
    patterns = {k: re.compile(p) for k, p in kernel_patterns.items()}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (MARK_OPEN, MARK_CLOSE) \
                        and ev.name not in markers:
                    markers[ev.name] = ev.start_ns / 1e9
    devices = []
    for plane in _device_planes(planes):
        ops: List[list] = []
        lines[plane.name] = {}
        for line in plane.lines:
            events = list(line.events)
            lines[plane.name][line.name] = len(events)
            if line.name != OPS_LINE:
                continue
            for ev in events:
                kernel = ""
                if patterns:
                    hay = [ev.name] + [str(v) for _, v in ev.stats
                                       if isinstance(v, (str, bytes))]
                    for key, pat in patterns.items():
                        if any(pat.search(h) for h in hay):
                            kernel = key
                            break
                ops.append([op_label(ev.name), ev.start_ns / 1e9,
                            ev.duration_ns / 1e9, kernel])
        ops.sort(key=lambda e: (e[1], -e[2]))
        devices.append({"plane": plane.name, "ops": ops})
    return {"markers": markers, "devices": devices, "lines": lines}


_LAYOUT = re.compile(r"\{[^}]*\}")


def op_label(hlo: str, width: int = 72) -> str:
    """``%fusion.3 = bf16[8,256]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.3 bf16[8,256]``: the instruction's name and its result type
    without layouts, cut to ``width``."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:width]
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):  # the result type ends at the first
        if ch in "([{":            # space outside brackets
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            end = i
            break
    return (head.lstrip("%") + " " + _LAYOUT.sub("", rest[:end]))[:width]


def describe(path: str, per_line: int = 12) -> dict:
    """What a trace file holds, for a reader who has not seen one: planes,
    lines, event counts, and a few events of each line with their stats."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        out[plane.name] = {}
        for line in plane.lines:
            events = list(line.events)
            seen, shown = set(), []
            for ev in events:
                if ev.name in seen:
                    continue
                seen.add(ev.name)
                shown.append({"name": ev.name, "start_ns": ev.start_ns,
                              "dur_ns": ev.duration_ns,
                              "stats": {k: str(v)[:200]
                                        for k, v in ev.stats}})
                if len(shown) >= per_line:
                    break
            out[plane.name][line.name] = {"events": len(events),
                                          "sample": shown}
    return out


def merge_intervals(intervals: Sequence[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def self_times(ops: Sequence[Sequence]) -> Dict[str, List[float]]:
    """Per operation name ``[self seconds, calls]``: an operation that
    spans others (a loop, a call) is charged only the time none of its
    children covers. ``ops`` sorted by start, longer first on ties."""
    acc: Dict[str, List[float]] = {}
    stack: List[list] = []  # [name, end, self]

    def close(item):
        rec = acc.setdefault(item[0], [0.0, 0])
        rec[0] += max(0.0, item[2])
        rec[1] += 1

    for name, start, dur, *_ in ops:
        while stack and start >= stack[-1][1] - 1e-12:
            close(stack.pop())
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        close(stack.pop())
    return acc


def _clip(ops, lo, hi):
    out = []
    for name, start, dur, kernel in ops:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a, kernel])
    return out


def reduce(extracted: dict, host_spans: Sequence[Sequence] = (),
           mono_at_open: Optional[float] = None, top: int = 10) -> Optional[dict]:
    """The digest of one traced window. ``host_spans``: ``[name, t0, dur]``
    on the monotonic clock; ``mono_at_open``: that clock at ``MARK_OPEN``.
    Returns None where no operation ran on a device (nothing to read)."""
    devices = [d for d in extracted["devices"] if d["ops"]]
    if not devices:
        return None
    marks = extracted.get("markers", {})
    all_lo = min(d["ops"][0][1] for d in devices)
    all_hi = max(max(e[1] + e[2] for e in d["ops"]) for d in devices)
    lo = marks.get(MARK_OPEN, all_lo)
    hi = marks.get(MARK_CLOSE, all_hi)
    if not hi > lo:
        lo, hi = all_lo, all_hi
    busy, kernels = [], {}
    for d in devices:
        ops = _clip(d["ops"], lo, hi)
        merged = merge_intervals([(s, s + t) for _, s, t, _ in ops])
        busy.append(sum(b - a for a, b in merged))
        for _, _, dur, kernel in ops:
            if kernel:
                rec = kernels.setdefault(kernel, {"seconds": 0.0, "calls": 0})
                rec["seconds"] += dur
                rec["calls"] += 1
    n = len(devices)
    for rec in kernels.values():  # per chip
        rec["seconds"] /= n
        rec["calls"] /= n
    first = _clip(devices[0]["ops"], lo, hi)
    st = self_times(first)
    device_ops = sorted(([k, v[0]] for k, v in st.items()),
                        key=lambda kv: -kv[1])[:top]
    # idle gaps of the first chip, by what the host was doing
    merged = merge_intervals([(s, s + t) for _, s, t, _ in first])
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= MIN_GAP_S]
    spans = []
    if mono_at_open is not None and MARK_OPEN in marks:
        shift = marks[MARK_OPEN] - mono_at_open
        spans = [(name, t0 + shift, t0 + shift + dur)
                 for name, t0, dur, *_ in host_spans]
    idle: Dict[str, float] = {}
    for a, b in gaps:
        best, best_key = "(no span)", (0.0, 0.0)
        for name, s0, s1 in spans:
            ov = min(b, s1) - max(a, s0)
            key = (ov, -(s1 - s0))  # most overlap, then the narrower span
            if ov > 0 and key > best_key:
                best, best_key = name, key
        idle[best] = idle.get(best, 0.0) + (b - a)
    idle_gaps = sorted(([k, v] for k, v in idle.items()),
                       key=lambda kv: -kv[1])[:top]
    return {"window_s": hi - lo, "busy_s": sum(busy) / n, "chips": n,
            "kernels": kernels, "device_ops": device_ops,
            "idle_gaps": idle_gaps,
            "longest_gap_s": max((b - a for a, b in gaps), default=0.0)}
