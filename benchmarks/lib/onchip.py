"""What both kinds of cell do inside the process that holds the chip: read
the flight recorder's spans of a window, count compilations, trace a short
window with the profiler and reduce it. Imports jax lazily: this module is
imported by the driver too, which never touches jax."""

from __future__ import annotations

import shutil
import tempfile
import time
from typing import Dict, List, Optional, Sequence


def _ring() -> tuple:
    """This process's flight-recorder records and its table of span names."""
    from ray_tpu.util import flight_recorder as fr

    payload = fr.snapshot_payload()
    return payload["events"], {int(sid): d["name"]
                               for sid, d in payload["names"].items()}


def local_spans(names: Sequence[str], mono_lo: float, mono_hi: float
                ) -> Dict[str, List[List[float]]]:
    """This process's flight-recorder spans called one of ``names`` that
    ENDED inside ``[mono_lo, mono_hi]``: {name: [[t0, dur], ...]}."""
    events, table = _ring()
    out: Dict[str, List[List[float]]] = {n: [] for n in names}
    for _seq, sid, kind, t0, dur, _tags in events:
        name = table.get(int(sid))
        if name in out and kind == 0 and mono_lo <= t0 + dur <= mono_hi:
            out[name].append([t0, dur])
    return out


def all_local_spans(mono_lo: float, mono_hi: float) -> List[list]:
    """Every span of this process overlapping the interval, as
    ``[name, t0, dur]``: what the host was doing, for the idle gaps."""
    events, table = _ring()
    return [[table.get(int(sid), str(sid)), t0, dur]
            for _seq, sid, kind, t0, dur, _tags in events
            if kind == 0 and t0 + dur >= mono_lo and t0 <= mono_hi]


def compile_counts() -> dict:
    """Compilations this process has asked for so far (each is a hit or a
    miss of the persistent cache) and the seconds the backend compiled."""
    from ray_tpu.util.device_telemetry import process_device_report

    rep = process_device_report()
    return {"requests": rep["cache_hits"] + rep["cache_misses"],
            "misses": rep["cache_misses"], "hits": rep["cache_hits"],
            "compile_s": rep["compile_s"]}


def device_fields() -> dict:
    from ray_tpu.util.device_telemetry import process_device_report

    rep = process_device_report()
    peaks = [m["peak_bytes_in_use"] for m in rep["memory"]
             if m["peak_bytes_in_use"] is not None]
    return {"platform": rep["platform"], "kind": rep["device_kind"],
            "count": rep["devices"],
            "memory_peak_bytes": max(peaks) if peaks else None,
            "compile_s": rep["compile_s"], "cache_hits": rep["cache_hits"],
            "cache_misses": rep["cache_misses"]}


class WindowTrace:
    """The profiler over a short window, in the process that holds the
    chip. ``start()`` then ``stop()``; ``digest()`` reads and reduces."""

    def __init__(self, kernel_patterns: Dict[str, str]):
        self.kernel_patterns = kernel_patterns
        self.dir: Optional[str] = None
        self.mono_open = self.mono_close = None

    def start(self) -> None:
        import jax

        from . import trace as tr

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the host's Python is not traced:
        opts.host_tracer_level = 1     # markers only, to keep the host fast
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.mono_open = time.monotonic()
        with jax.profiler.TraceAnnotation(tr.MARK_OPEN):
            pass

    def stop(self) -> None:
        import jax

        from . import trace as tr

        with jax.profiler.TraceAnnotation(tr.MARK_CLOSE):
            pass
        self.mono_close = time.monotonic()
        jax.profiler.stop_trace()

    def digest(self, keep_dir: Optional[str] = None) -> Optional[dict]:
        """None where the trace holds no device operation (the CPU)."""
        from . import trace as tr

        try:
            path = tr.newest_xplane(self.dir) if self.dir else None
            if path is None:
                return None
            extracted = tr.extract(path, self.kernel_patterns)
            spans = all_local_spans(self.mono_open, self.mono_close)
            out = tr.reduce(extracted, spans, self.mono_open)
            if keep_dir:
                import json
                import os

                os.makedirs(keep_dir, exist_ok=True)
                with open(os.path.join(keep_dir, "trace_describe.json"),
                          "w") as f:
                    json.dump(tr.describe(path), f)
                with open(os.path.join(keep_dir, "trace_extract.json"),
                          "w") as f:
                    json.dump({"extracted": extracted, "host_spans": spans,
                               "mono_at_open": self.mono_open}, f)
            return out
        finally:
            if self.dir:
                shutil.rmtree(self.dir, ignore_errors=True)
