"""XLA compile observatory: a per-process registry of jitted programs.

Every observability plane built so far watches the *runtime*; this one
watches the *XLA compile plane* — the ``ray memory`` analog for
compiled programs. :func:`observe_compiled` wraps a jitted callable
with an ahead-of-time (``jax.stages``) cache: the first call under a
new input-aval fingerprint pays an explicit ``lower()`` +
``compile()`` (so compile wall time is measured, not inferred),
records the executable's ``cost_analysis()`` FLOPs / bytes-accessed
and ``memory_analysis()`` byte breakdown plus avals, shardings and
donation, and caches the compiled executable; steady-state calls pay
only the fingerprint (a tree-flatten and shape/dtype tuple build,
bench-gated <=1% of the spmd step in ``BENCH_XLA.json``).

Cluster transport reuses the existing planes — **no new wire ops**:

- numeric columns ride the standard metrics registry tagged
  ``{program}`` (``ray_tpu_xla_recompiles_total``,
  ``ray_tpu_xla_compile_seconds_total``, flops / bytes / peak-bytes /
  variant-count gauges) and flush on the worker report cadence;
- each measured compile records an ``xla.compile`` flight-recorder
  span (feeds ``timeline --attribute`` compile rows and the goodput
  ledger's compile column for non-SPMD processes);
- shape churn (old -> new avals on a re-lower) rides a bounded
  ``ray_tpu_xla_shape_churn{program,from,to}`` gauge so the head's
  recompile-storm detector (``train/health.py``) can name the delta.

:func:`xla_report` is the ONE head-side fold behind ``python -m
ray_tpu xla``, ``GET /api/xla`` and the registry gauges: it joins the
analytic FLOPs/bytes with measured flight-recorder spans
(``spmd.compute``, ``serve.decode_step``, ...) into per-program
achieved-FLOPs/s, arithmetic intensity, MFU and a compute-bound vs
memory-bound roofline verdict against the peak table, which is keyed by
TPU ``device_kind``. A device that is not in the table has no peak: the
lookup raises :class:`UnknownDeviceError`, and the report then carries
the analytic and measured columns without MFU or verdict and says why.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from ray_tpu.core.config import global_config
from ray_tpu.util import flight_recorder as _fr
from ray_tpu.util.metrics import Counter, Gauge, aggregate_series, registry

__all__ = [
    "observe_compiled",
    "ObservedFunction",
    "analyses",
    "snapshot",
    "get_program",
    "program_names",
    "xla_report",
    "format_xla",
    "peak_flops_per_chip",
    "peak_hbm_bytes_per_sec",
    "UnknownDeviceError",
    "reset_for_tests",
]

logger = logging.getLogger(__name__)

_sp_compile = _fr.register_span("xla.compile", tag_keys=("program",))

_c_compiles = Counter(
    "ray_tpu_xla_compiles_total",
    "Measured lower+compile events per observed program",
    tag_keys=("program",))
_c_recompiles = Counter(
    "ray_tpu_xla_recompiles_total",
    "Re-lowers of an observed program under a NEW input-aval "
    "fingerprint (shape churn)", tag_keys=("program",))
_c_compile_seconds = Counter(
    "ray_tpu_xla_compile_seconds_total",
    "Measured lower+compile wall seconds per observed program",
    tag_keys=("program",))
_g_flops = Gauge(
    "ray_tpu_xla_program_flops",
    "cost_analysis() FLOPs of the most recent executable",
    tag_keys=("program",))
_g_bytes = Gauge(
    "ray_tpu_xla_program_bytes_accessed",
    "cost_analysis() bytes accessed of the most recent executable",
    tag_keys=("program",))
_g_peak_bytes = Gauge(
    "ray_tpu_xla_program_peak_bytes",
    "memory_analysis() argument+output+temp bytes of the most recent "
    "executable", tag_keys=("program",))
_g_variants = Gauge(
    "ray_tpu_xla_program_variants",
    "Distinct input-aval fingerprints compiled for a program (for the "
    "decode engine this is the padded-bucket count)",
    tag_keys=("program",))
_g_churn = Gauge(
    "ray_tpu_xla_shape_churn",
    "Count of one observed aval transition (old -> new), bounded "
    "per-program so tag cardinality stays small",
    tag_keys=("program", "from", "to"))

# worker-side caps that bound metric tag cardinality and record growth
_MAX_CHURN_TAGS = 8
_MAX_CHURN_RECORDS = 16
_AVAL_STR_LEN = 120

_LOCK = threading.Lock()
_REGISTRY: Dict[str, "ProgramRecord"] = {}


class ProgramRecord:
    """Everything this process knows about one observed program."""

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.recompiles = 0
        self.compile_seconds = 0.0
        self.variants: Dict[tuple, dict] = {}   # fingerprint -> info
        self.churn: List[dict] = []             # bounded transition log
        self.last: Dict[str, Any] = {}          # latest analyses
        self.last_avals = ""

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "compiles": self.compiles,
            "recompiles": self.recompiles,
            "compile_seconds": round(self.compile_seconds, 6),
            "variants": len(self.variants),
            "avals": self.last_avals,
            "churn": list(self.churn),
            **self.last,
        }


# --------------------------------------------------------------------------- #
# Fingerprints
# --------------------------------------------------------------------------- #

_DTYPE_SHORT = {"float": "f", "uint": "u", "int": "i", "complex": "c",
                "bfloat": "bf", "bool": "b"}


def _short_dtype(dt) -> str:
    s = str(getattr(dt, "name", dt))
    for long, short in _DTYPE_SHORT.items():
        if s.startswith(long):
            return short + s[len(long):]
    return s


def _fingerprint(args, kwargs) -> tuple:
    """Hashable aval fingerprint for one call — the per-step hot path,
    so no string work happens here (``_describe`` renders it, and only
    on a cache miss).

    Shape + dtype per array leaf; plain-Python scalars contribute only
    their type (jit traces them weakly typed, so one compilation covers
    every value — including them by value would fake recompile storms).
    """
    import jax

    fp: List[tuple] = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            fp.append((dtype, tuple(shape)))
        else:
            fp.append((type(leaf).__name__,))
    return tuple(fp)


def _describe(fp: tuple) -> str:
    """Compact human string for a fingerprint (cache-miss path only)."""
    parts: List[str] = []
    for entry in fp:
        if len(parts) >= 6:
            break
        if len(entry) == 2:
            dtype, shape = entry
            dims = ",".join(str(d) for d in shape)
            parts.append(f"{_short_dtype(dtype)}[{dims}]")
    if len(fp) > 6:
        parts.append(f"+{len(fp) - 6} leaves")
    return ";".join(parts)[:_AVAL_STR_LEN]


# --------------------------------------------------------------------------- #
# Analyses extraction (every accessor guarded: backends differ)
# --------------------------------------------------------------------------- #


def analyses(compiled, lowered=None) -> Dict[str, Any]:
    """What a compiled executable says of itself, each part only where the
    backend gives it: ``flops`` / ``bytes_accessed`` (``cost_analysis()``),
    ``memory`` (``memory_analysis()``: ``argument`` / ``output`` / ``temp`` /
    ``code`` / ``alias`` bytes, and ``peak`` where the runtime gives it) and their ``peak_bytes``, the input
    shardings, and of ``lowered`` the count of donated arguments."""
    info: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            flops = float(ca.get("flops", 0.0) or 0.0)
            nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
            if flops > 0:
                info["flops"] = flops
            if nbytes > 0:
                info["bytes_accessed"] = nbytes
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        mem = {}
        for key, attr in (("argument", "argument_size_in_bytes"),
                          ("output", "output_size_in_bytes"),
                          ("temp", "temp_size_in_bytes"),
                          ("code", "generated_code_size_in_bytes"),
                          ("alias", "alias_size_in_bytes")):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[key] = int(v)
        # the most the live buffers hold at once, arguments among them:
        # newer runtimes give it; temp counts every temporary as live
        if getattr(ma, "peak_memory_in_bytes", 0):
            mem["peak"] = int(ma.peak_memory_in_bytes)
        if mem:
            info["memory"] = mem
            info["peak_bytes"] = (mem.get("argument", 0)
                                  + mem.get("output", 0)
                                  + mem.get("temp", 0))
    except Exception:
        pass
    try:
        sh = getattr(compiled, "input_shardings", None)
        if sh is not None:
            info["in_shardings"] = repr(sh)[:200]
    except Exception:
        pass
    if lowered is not None:
        try:
            import jax

            donated = sum(
                1 for a in jax.tree_util.tree_leaves(lowered.args_info)
                if getattr(a, "donated", False))
            info["donated_args"] = donated
        except Exception:
            pass
    return info


def _record_compiled(name: str, fp: tuple, fp_str: str, compiled,
                     compile_s: float, lowered=None) -> None:
    info = analyses(compiled, lowered)
    with _LOCK:
        rec = _REGISTRY.get(name)
        if rec is None:
            rec = _REGISTRY[name] = ProgramRecord(name)
        is_recompile = bool(rec.variants) and fp not in rec.variants
        prev_avals = rec.last_avals
        rec.compiles += 1
        rec.compile_seconds += compile_s
        rec.variants[fp] = {"avals": fp_str,
                            "compile_s": round(compile_s, 6)}
        rec.last = info
        rec.last_avals = fp_str
        if is_recompile:
            rec.recompiles += 1
            if len(rec.churn) >= _MAX_CHURN_RECORDS:
                rec.churn.pop(0)
            rec.churn.append({"from": prev_avals, "to": fp_str,
                              "compile_s": round(compile_s, 6)})
        n_variants = len(rec.variants)
        n_churn_tags = len({(c["from"], c["to"]) for c in rec.churn})
    tk = (("program", name),)
    _c_compiles.inc(tag_key=tk)
    _c_compile_seconds.inc(compile_s, tag_key=tk)
    _g_variants.set(float(n_variants), tag_key=tk)
    if "flops" in info:
        _g_flops.set(info["flops"], tag_key=tk)
    if "bytes_accessed" in info:
        _g_bytes.set(info["bytes_accessed"], tag_key=tk)
    if "peak_bytes" in info:
        _g_peak_bytes.set(float(info["peak_bytes"]), tag_key=tk)
    if is_recompile:
        _c_recompiles.inc(tag_key=tk)
        if n_churn_tags <= _MAX_CHURN_TAGS:
            _g_churn.set(1.0, tags={"program": name,
                                    "from": prev_avals, "to": fp_str})


# --------------------------------------------------------------------------- #
# The observation hook
# --------------------------------------------------------------------------- #


class ObservedFunction:
    """AOT-caching wrapper around one jitted callable.

    What XLA says reaches the caller as XLA said it: a failed ``lower()``
    or ``compile()`` and a failed execution propagate unchanged. Neither
    is retried through plain ``jit``: that would compile the whole program
    a second time, and re-run a step on buffers the first attempt may
    already have donated, so an out-of-memory on the chip would surface as
    "array has been deleted".

    Only a failure of the observation itself is absorbed, and it says so
    once, with the exception: the aval fingerprint cannot be built, or the
    AOT executable refuses the call's arguments before running anything (a
    sharding or layout the fingerprint does not see; ``jit`` would have
    re-specialised). From then on this program goes through the original
    jitted function, unobserved.
    """

    def __init__(self, fn: Callable, name: str):
        self._fn = fn
        self.program_name = name
        self._cache: Dict[tuple, Any] = {}
        self._unobserved = False

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def _stop_observing(self, what: str, exc: BaseException) -> None:
        self._unobserved = True
        logger.warning(
            "xla observatory: %s for program %r (%s: %s); it runs "
            "unobserved through jit from here on",
            what, self.program_name, type(exc).__name__, exc)

    def _ahead_of_time(self) -> bool:
        """Whether calls run executables this wrapper lowered and compiled
        itself: while the observatory is on. A subclass that needs a
        program's analyses whatever the knob says answers True."""
        return global_config().xla_observatory_enabled

    def _compile(self, fn, args, kwargs) -> "_Build":
        """``fn`` lowered and compiled for these arguments, timed (and, with
        the observatory on, under an ``xla.compile`` span)."""
        t0 = time.monotonic()
        lowered = fn.lower(*args, **kwargs)
        compiled = lowered.compile()
        if global_config().xla_observatory_enabled:
            _sp_compile.end(t0, self.program_name)
        return _Build(lowered, compiled, time.monotonic() - t0)

    def _record(self, fp: tuple, build: "_Build") -> None:
        """One compile into the registry; the last one recorded is what the
        program's analyses (FLOPs, bytes, memory) say."""
        if global_config().xla_observatory_enabled:
            _record_compiled(self.program_name, fp, _describe(fp),
                             build.compiled, build.seconds, build.lowered)

    def _executable(self, fp: tuple, args, kwargs):
        """What runs calls with a new fingerprint."""
        build = self._compile(self._fn, args, kwargs)
        self._record(fp, build)
        return build.compiled

    def __call__(self, *args, **kwargs):
        if self._unobserved or not self._ahead_of_time():
            return self._fn(*args, **kwargs)
        try:
            fp = _fingerprint(args, kwargs)
        except Exception as e:  # noqa: BLE001 - observation, not the step
            self._stop_observing("cannot fingerprint the arguments", e)
            return self._fn(*args, **kwargs)
        compiled = self._cache.get(fp)
        if compiled is None:
            compiled = self._cache[fp] = self._executable(fp, args, kwargs)
        try:
            return compiled(*args, **kwargs)
        except (TypeError, ValueError) as e:
            # raised by the executable's argument check, before any buffer
            # is consumed; XLA's own run-time errors are JaxRuntimeError
            # and are not caught here
            self._stop_observing(
                "the AOT executable rejected the call's arguments", e)
            return self._fn(*args, **kwargs)


class _Build(NamedTuple):
    """One lower + compile: the stages and the wall seconds they took."""
    lowered: Any
    compiled: Any
    seconds: float


def observe_compiled(fn_or_lowered, name: str):
    """Register a jitted callable (or an already lowered/compiled
    ``jax.stages`` object) with the observatory under ``name``.

    - jitted callable (has ``.lower``): returns the observing wrapper —
      a drop-in replacement for the jitted fn;
    - ``jax.stages.Lowered``: compiles it now (timed), records the
      analyses, returns the ``Compiled``;
    - ``jax.stages.Compiled``: records its analyses, returns it as-is.
    """
    if not global_config().xla_observatory_enabled:
        if hasattr(fn_or_lowered, "lower"):
            return fn_or_lowered
        if hasattr(fn_or_lowered, "compile"):
            return fn_or_lowered.compile()
        return fn_or_lowered
    if hasattr(fn_or_lowered, "lower"):
        return ObservedFunction(fn_or_lowered, name)
    if hasattr(fn_or_lowered, "compile"):
        t0 = time.monotonic()
        compiled = fn_or_lowered.compile()
        dt = time.monotonic() - t0
        _sp_compile.end(t0, name)
        _record_compiled(name, ("lowered",), "", compiled, dt,
                         fn_or_lowered)
        return compiled
    if hasattr(fn_or_lowered, "cost_analysis"):
        _record_compiled(name, ("compiled",), "", fn_or_lowered, 0.0)
    return fn_or_lowered


def snapshot() -> Dict[str, Dict[str, Any]]:
    """This process's program registry as plain dicts."""
    with _LOCK:
        return {name: rec.to_dict() for name, rec in _REGISTRY.items()}


def get_program(name: str) -> Optional[Dict[str, Any]]:
    with _LOCK:
        rec = _REGISTRY.get(name)
        return rec.to_dict() if rec is not None else None


def program_names() -> List[str]:
    with _LOCK:
        return sorted(_REGISTRY)


def reset_for_tests() -> None:
    with _LOCK:
        _REGISTRY.clear()


# --------------------------------------------------------------------------- #
# Per-platform peaks (roofline ceilings)
# --------------------------------------------------------------------------- #

# Published per-chip peaks by TPU generation (Google Cloud TPU system
# architecture pages): bf16 FLOP/s and HBM bytes/s
_TPU_PEAK_FLOPS = {"v4": 275e12, "v5e": 197e12, "v5p": 459e12,
                   "v6e": 918e12}
_TPU_PEAK_HBM = {"v4": 1228e9, "v5e": 819e9, "v5p": 2765e9,
                 "v6e": 1638e9}


class UnknownDeviceError(LookupError):
    """The local device has no entry in the peak table. There is no
    default: a roofline against a guessed peak is a wrong number."""


def _device_info() -> Tuple[Optional[str], Optional[str], int]:
    """(platform, device_kind, device count) of the backend this process
    has ALREADY initialised, or (None, None, 0): the fold also runs in the
    head (dashboard, CLI), which holds no device and must not take one by
    asking."""
    from ray_tpu.util.device_telemetry import jax_with_backend

    jax = jax_with_backend()
    if jax is None:
        return None, None, 0
    dev = jax.devices()[0]
    return dev.platform, dev.device_kind, jax.device_count()


# device_kind strings as the runtime reports them -> generation key;
# ordered (v5lite before v5: the bare "v5" kind is a v5p). A v5e chip
# reports "TPU v5 lite" (checked on the chip, PR 21).
_TPU_KIND_ALIASES = (("v6lite", "v6e"), ("v6e", "v6e"),
                     ("v5lite", "v5e"), ("v5e", "v5e"),
                     ("v5p", "v5p"), ("v5", "v5p"), ("v4", "v4"))


def _tpu_table_lookup(table: Dict[str, float], platform: Optional[str],
                      kind: Optional[str]) -> float:
    if platform is None:
        raise UnknownDeviceError(
            "this process has initialised no JAX backend, so it has no "
            "device to look a peak up for (the head never takes one); set "
            "xla_peak_flops / xla_peak_hbm_bytes to get MFU and verdicts")
    if platform == "tpu":
        k = kind.lower().replace(" ", "")
        for pat, gen in _TPU_KIND_ALIASES:
            if pat in k:
                return table[gen]
    raise UnknownDeviceError(
        f"no peak-table entry for device platform={platform!r} "
        f"device_kind={kind!r}; known TPU kinds: "
        f"{sorted(set(g for _, g in _TPU_KIND_ALIASES))}. Set "
        f"xla_peak_flops / xla_peak_hbm_bytes to measure against a "
        f"peak of your own")


def peak_flops_per_chip() -> float:
    """bf16 peak FLOPs/s per chip (``xla_peak_flops`` overrides). Raises
    :class:`UnknownDeviceError` for a device that is not in the table."""
    override = global_config().xla_peak_flops
    if override > 0:
        return float(override)
    return _tpu_table_lookup(_TPU_PEAK_FLOPS, *_device_info()[:2])


def peak_hbm_bytes_per_sec() -> float:
    """Memory bandwidth per chip in bytes/s (``xla_peak_hbm_bytes``
    overrides). Raises :class:`UnknownDeviceError` like the above."""
    override = global_config().xla_peak_hbm_bytes
    if override > 0:
        return float(override)
    return _tpu_table_lookup(_TPU_PEAK_HBM, *_device_info()[:2])


# --------------------------------------------------------------------------- #
# The head-side fold (one fold -> CLI, /api/xla, gauges agree)
# --------------------------------------------------------------------------- #

# program -> the measured flight-recorder span family its executions
# land in. Programs without an entry get analytic columns only.
_MEASURE_SPAN = {
    "spmd.train_step": "spmd.compute",
    "llama.gspmd_train_step": "spmd.compute",
    "llama.decode": "serve.decode_step",
    "llama.prefill": "serve.prefill",
}


def _merged_program_columns() -> Dict[str, Dict[str, Any]]:
    """Per-program numeric columns from the (head-side merged) metrics
    registry: counters sum across sources, gauges take the max."""
    flat = aggregate_series(registry())
    programs: Dict[str, Dict[str, Any]] = {}

    def fold(metric: str, field: str, how: str) -> None:
        for tags, value in flat.get(metric, ()):
            d = dict(tags)
            prog = d.get("program")
            if not prog:
                continue
            row = programs.setdefault(prog, {})
            if how == "sum":
                row[field] = row.get(field, 0.0) + value
            else:
                row[field] = max(row.get(field, 0.0), value)

    fold("ray_tpu_xla_compiles_total", "compiles", "sum")
    fold("ray_tpu_xla_recompiles_total", "recompiles", "sum")
    fold("ray_tpu_xla_compile_seconds_total", "compile_seconds", "sum")
    fold("ray_tpu_xla_program_flops", "flops", "max")
    fold("ray_tpu_xla_program_bytes_accessed", "bytes_accessed", "max")
    fold("ray_tpu_xla_program_peak_bytes", "peak_bytes", "max")
    fold("ray_tpu_xla_program_variants", "variants", "max")
    for tags, value in flat.get("ray_tpu_xla_shape_churn", ()):
        d = dict(tags)
        prog = d.get("program")
        if not prog:
            continue
        row = programs.setdefault(prog, {})
        row.setdefault("churn", []).append(
            {"from": d.get("from", ""), "to": d.get("to", "")})
    return programs


def _measured_span_stats(head=None) -> Dict[str, Dict[str, float]]:
    """span name -> {count, total_s}: cluster-wide when a head is given,
    the local ring otherwise (the bench / driver-only path)."""
    if head is not None:
        payloads = _fr.cluster_span_payloads(head)
    else:
        payloads = [_fr.snapshot_payload()]
    stats: Dict[str, Dict[str, float]] = {}
    for ev in _fr.build_span_events(payloads):
        if ev.get("ph") != "X" or ev.get("cat") != "span":
            continue
        row = stats.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
        row["count"] += 1
        row["total_s"] += ev.get("dur", 0.0) / 1e6
    return stats


def xla_report(head=None) -> Dict[str, Any]:
    """The compile-plane report: merged registry columns joined with
    measured spans, rooflined against the platform peaks."""
    platform, kind, n_devices = _device_info()
    peaks_unknown = None
    try:
        peak_f = peak_flops_per_chip()
        peak_b = peak_hbm_bytes_per_sec()
        ridge = peak_f / peak_b
    except UnknownDeviceError as e:
        # the report still carries what was counted and measured; it has
        # no MFU and no verdict, and says why
        peak_f = peak_b = ridge = None
        peaks_unknown = str(e)

    programs = _merged_program_columns()
    # head-process registry detail (avals, shardings, donation) for the
    # programs compiled in this process — numeric columns stay
    # cluster-wide via the merged registry
    for name, rec in snapshot().items():
        row = programs.setdefault(name, {})
        for key in ("avals", "in_shardings", "donated_args", "memory"):
            if key in rec and rec.get(key) not in (None, ""):
                row[key] = rec[key]
        if rec.get("churn"):
            row["churn"] = rec["churn"]

    spans = _measured_span_stats(head)
    recompiles_total = 0.0
    for name, row in programs.items():
        recompiles_total += row.get("recompiles", 0.0)
        flops = row.get("flops", 0.0)
        nbytes = row.get("bytes_accessed", 0.0)
        if flops and nbytes:
            row["arithmetic_intensity"] = round(flops / nbytes, 4)
        measure = _MEASURE_SPAN.get(name)
        st = spans.get(measure) if measure else None
        if st and st["count"] and st["total_s"] > 0:
            mean_s = st["total_s"] / st["count"]
            row["measured_span"] = measure
            row["measured_steps"] = int(st["count"])
            row["mean_step_s"] = round(mean_s, 6)
            if flops:
                # cost_analysis describes the PER-DEVICE executable
                # (XLA compiles the partitioned module), so achieved
                # FLOPs/s rooflines against ONE chip's peak
                achieved = flops / mean_s
                row["achieved_flops_per_s"] = round(achieved, 2)
                if peak_f is not None:
                    row["mfu"] = round(achieved / peak_f, 6)
        ai = row.get("arithmetic_intensity")
        if ai is not None and ridge is not None:
            row["verdict"] = ("compute-bound" if ai >= ridge
                              else "memory-bound")
    report: Dict[str, Any] = {
        "platform": platform,
        "device_kind": kind,
        "devices": n_devices,
        "peak_flops_per_chip": peak_f,
        "peak_hbm_bytes_per_sec": peak_b,
        "ridge_intensity": round(ridge, 4) if ridge else None,
        "peaks_unknown": peaks_unknown,
        "programs": {k: programs[k] for k in sorted(programs)},
        "recompiles_total": int(recompiles_total),
    }
    monitor = getattr(head, "health_monitor", None)
    if monitor is not None and hasattr(monitor, "recompile"):
        report["storms"] = sorted(monitor.recompile.active)
    publish_report(report)
    return report


def publish_report(report: Dict[str, Any]) -> None:
    """Mirror the fold onto the registry so /api/metrics/history has
    the compile plane as time series (same pattern as publish_ledger)."""
    _g_report_programs.set(float(len(report.get("programs", {}))))
    _g_report_recompiles.set(float(report.get("recompiles_total", 0)))


_g_report_programs = Gauge(
    "ray_tpu_xla_programs",
    "Observed compiled programs, cluster-wide (from the xla fold)")
_g_report_recompiles = Gauge(
    "ray_tpu_xla_recompiles",
    "Cluster-wide recompile total (from the xla fold)")


def _fmt_num(v: float) -> str:
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.2f}{unit}"
    return f"{v:.0f}"


def format_xla(report: Dict[str, Any]) -> str:
    """Human rendering of :func:`xla_report` (the CLI view)."""
    lines = ["xla compile observatory", "-" * 23]
    if report["platform"] is None:
        lines.append("platform: this process holds no device")
    else:
        lines.append(
            f"platform: {report['platform']} ({report['device_kind']}), "
            f"{report['devices']} device(s)")
    ridge = report.get("ridge_intensity")
    if report.get("peaks_unknown"):
        lines.append("peaks: unknown, so no MFU and no verdict ("
                     + report["peaks_unknown"] + ")")
    else:
        lines.append(
            f"peaks: {_fmt_num(report['peak_flops_per_chip'])}FLOP/s, "
            f"{_fmt_num(report['peak_hbm_bytes_per_sec'])}B/s"
            + (f", ridge {ridge:.1f} FLOP/B" if ridge else ""))
    progs = report.get("programs", {})
    if not progs:
        lines.append("no observed programs")
        return "\n".join(lines)
    lines.append("")
    header = (f"{'program':<24}{'compiles':>9}{'recomp':>7}"
              f"{'compile_s':>10}{'GFLOPs':>9}{'AI':>7}"
              f"{'MFU':>7}  verdict")
    lines.append(header)
    for name, row in progs.items():
        flops = row.get("flops", 0.0)
        ai = row.get("arithmetic_intensity")
        mfu = row.get("mfu")
        lines.append(
            f"{name:<24}{int(row.get('compiles', 0) or 0):>9}"
            f"{int(row.get('recompiles', 0) or 0):>7}"
            f"{row.get('compile_seconds', 0.0):>10.3f}"
            f"{flops / 1e9:>9.2f}"
            f"{(f'{ai:.1f}' if ai is not None else '-'):>7}"
            f"{(f'{mfu:.3f}' if mfu is not None else '-'):>7}"
            f"  {row.get('verdict', '-')}")
        for c in (row.get("churn") or [])[-3:]:
            lines.append(f"    churn: {c.get('from', '?')} -> "
                         f"{c.get('to', '?')}")
        if row.get("measured_span"):
            lines.append(
                f"    measured: {row['measured_steps']} x "
                f"{row['measured_span']} spans, mean "
                f"{row['mean_step_s'] * 1e3:.2f} ms"
                + (f", achieved "
                   f"{_fmt_num(row['achieved_flops_per_s'])}FLOP/s"
                   if row.get("achieved_flops_per_s") else ""))
    storms = report.get("storms")
    if storms:
        lines.append("")
        lines.append("ACTIVE RECOMPILE STORMS: " + ", ".join(storms))
    return "\n".join(lines)
