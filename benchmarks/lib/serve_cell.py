"""A serving cell: ``serve.run`` of a ``decode=True`` deployment around the
program's decode engine on one chip, loaded by an open loop of streaming
clients in this (the driver's) process.

A request that raises, times out or is shed is counted in ``failed`` and
misses every limit; it never ends the run. ``BenchLM`` is the deployment:
its control methods are ``async`` and hand their blocking work to a thread,
so none of them waits behind a stream or holds an event loop.
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Any, Dict, List, Optional

from . import onchip, reducers, spec, stats, traffic as traffic_mod


def pages_for(length: int, page_size: int) -> int:
    return max(0, (length + page_size - 1) // page_size)


def shapes_of(tr: dict, page_size: int) -> Dict[str, List[int]]:
    """Page counts (one compiled program each) that the traffic can meet:
    prefill over the prompt lengths; decode over every position a sequence
    writes, from the shortest prompt to the longest prompt plus the longest
    answer (its last token is not fed back)."""
    p, o = tr["prompt_tokens"], tr["output_tokens"]
    p_lo, p_hi = (p["value"],) * 2 if p["dist"] == "const" else (p["min"], p["max"])
    o_hi = o["value"] if o["dist"] == "const" else o["max"]
    prefill = list(range(pages_for(p_lo, page_size),
                         pages_for(p_hi, page_size) + 1))
    decode = list(range(pages_for(p_lo + 1, page_size),
                        pages_for(p_hi + o_hi - 1, page_size) + 1)) \
        if o_hi > 1 else []
    return {"prefill": prefill, "decode": decode}


def check_prompt_len(shapes: Dict[str, List[int]], page_size: int) -> int:
    """A prompt length whose prefill and next three decode positions use
    only shapes the cell warms anyway, and cross a page boundary."""
    for p in shapes["prefill"]:
        if p in shapes["decode"] and p + 1 in shapes["decode"]:
            return p * page_size - 2
    return max(1, shapes["prefill"][0] * page_size - 2)


class BenchLM:
    """The deployment under test plus the benchmark's own control methods.
    Runs in the replica, the one process that holds the chip."""

    def __init__(self, bench: dict):
        self.b = bench
        self.decode_max_batch = bench["config"]["deployment"]["decode_max_batch"]
        self._engine = None
        self._build_lock = threading.Lock()
        self._tracer: Optional[onchip.WindowTrace] = None
        self._prepared = False

    # the replica calls this on its first request; ``prepare`` calls it
    # first, and both get the one engine
    def create_decode_engine(self):
        with self._build_lock:
            if self._engine is None:
                cfg = self.b["config"]
                dep = cfg["deployment"]
                engine = spec.resolve(cfg["program"]["engine_class"])(
                    spec.program_config(cfg), n_pages=dep["n_pages"],
                    page_size=dep["page_size"], seed=self.b["seed"])
                fault = self.b.get("fault")
                if fault:  # rehearsal only: once set-up is done, every
                    # n-th call of a method raises
                    kind, every = fault.split(":")
                    inner, calls = getattr(engine, kind), [0]

                    def faulty(*a, **kw):
                        calls[0] += self._prepared
                        if self._prepared and calls[0] % int(every) == 0:
                            raise RuntimeError(f"injected {kind} fault")
                        return inner(*a, **kw)

                    setattr(engine, kind, faulty)
                self._engine = engine
            return self._engine

    @staticmethod
    async def _in_thread(fn, *args):
        return await asyncio.get_running_loop().run_in_executor(None, fn, *args)

    async def prepare(self) -> dict:
        return await self._in_thread(self._prepare)

    async def snapshot(self) -> dict:
        return await self._in_thread(self._snapshot)

    async def spans(self, names: List[str], lo: float, hi: float) -> dict:
        return await self._in_thread(onchip.local_spans, names, lo, hi)

    async def trace_start(self) -> bool:
        self._tracer = onchip.WindowTrace(self.b["kernel_patterns"])
        await self._in_thread(self._tracer.start)
        return True

    async def trace_stop(self) -> Optional[dict]:
        def stop():
            self._tracer.stop()
            return self._tracer.digest(self.b.get("keep_dir"))

        return await self._in_thread(stop)

    # ---- blocking halves (threads)

    def _prepare(self) -> dict:
        """Build the engine (weights from the seed, on the device), run
        every shape the cell's traffic can meet once, and hold prefill and
        three decoded positions to the float32 reference."""
        import numpy as np

        t0 = time.time()
        engine = self.create_decode_engine()
        # the engine's page store is host memory that the OS maps on first
        # touch; a replica that has served before has touched all of it
        for store in (getattr(engine, "k_store", None),
                      getattr(engine, "v_store", None)):
            if store is not None:
                store.fill(0)
        t_built = time.time()
        cfg = self.b["config"]
        ps = cfg["deployment"]["page_size"]
        shapes = shapes_of(self.b["traffic"], ps)
        pool = engine.pool
        for p in shapes["prefill"]:
            pages = pool.alloc(p)
            engine.prefill([1] * (p * ps), pages)
            pool.release(pages)
        for d in shapes["decode"]:
            pages = pool.alloc(d)
            engine.decode(d * ps - 1, 1, pages)
            pool.release(pages)
        t_warm = time.time()
        # prefill, then three decoded positions, against the reference
        n = check_prompt_len(shapes, ps)
        toks = np.random.RandomState(self.b["seed"]).randint(
            0, cfg["vocab_size"], size=n + 3).astype(np.int32)
        pages = pool.alloc(pages_for(n + 3, ps))
        got = [engine.prefill([int(t) for t in toks[:n]],
                              pages[:pages_for(n, ps)])]
        for j in range(3):
            got.append(engine.decode(n + j, int(toks[n + j]),
                                     pages[:pages_for(n + j + 1, ps)]))
        pool.release(pages)
        import jax
        from functools import partial

        reference = spec.resolve(cfg["reference"] + ":logits_one")
        want = np.asarray(jax.jit(partial(reference, cfg))(
            engine.params, toks))[n - 1:n + 3]
        scale = float(np.max(np.abs(want)))
        errs = [float(np.max(np.abs(g - w))) / scale
                for g, w in zip(got, want)]
        self._prepared = True
        return {"shapes": shapes, "check_prompt_tokens": n,
                "rel_err": errs, "max_abs_logit": scale,
                "build_s": t_built - t0, "warm_s": t_warm - t_built,
                "check_s": time.time() - t_warm,
                "compile": onchip.compile_counts()}

    def _snapshot(self) -> dict:
        e = self._engine
        return {"mono": time.monotonic(), "wall": time.time(),
                "prefill_calls": e.prefill_calls,
                "decode_calls": e.decode_calls,
                "pages_used": e.pool.used,
                "compile": onchip.compile_counts(),
                "device": onchip.device_fields()}


# --------------------------------------------------------------------------- #
# Load (driver process; no jax)
# --------------------------------------------------------------------------- #


class Outcome:
    """One request as its client saw it, on ``time.perf_counter()``."""

    __slots__ = ("index", "due", "sent", "arrivals", "done", "error",
                 "abandoned", "n_prompt", "n_out")

    def __init__(self, index, due, n_prompt, n_out):
        self.index, self.due = index, due
        self.n_prompt, self.n_out = n_prompt, n_out
        self.sent = None
        self.arrivals: List[float] = []
        self.done = False
        self.error: Optional[str] = None
        self.abandoned = False


class Load:
    def __init__(self, handle, stream: traffic_mod.RequestStream,
                 item_timeout_s: float):
        self.handle = handle.options(stream=True,
                                     stream_item_timeout_s=item_timeout_s)
        self.stream = stream
        self.lock = threading.Lock()
        self.outcomes: List[Outcome] = []
        self.closing = threading.Event()
        self._next = 0

    def take_index(self) -> int:
        with self.lock:
            i = self._next
            self._next += 1
            return i

    def one(self, index: int, due: Optional[float] = None) -> Outcome:
        """Send request ``index`` and read its stream to the end (or until
        the run is closing). Never raises."""
        req = self.stream.request(index)
        out = Outcome(index, due, len(req["prompt"]), req["max_tokens"])
        with self.lock:
            self.outcomes.append(out)
        it = None
        try:
            out.sent = time.perf_counter()
            it = self.handle.remote(req)
            for item in it:
                now = time.perf_counter()
                if isinstance(item, dict) and item.get("done"):
                    out.done = True
                    break
                out.arrivals.append(now)
                if self.closing.is_set():
                    out.abandoned = True
                    break
            else:
                if not out.done:
                    out.error = "stream ended without its summary"
        except Exception as e:  # noqa: BLE001 - a failure is a count
            if self.closing.is_set():
                out.abandoned = True
            else:
                out.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001
                    pass
        return out

    def snapshot(self) -> List[Outcome]:
        with self.lock:
            return list(self.outcomes)


def open_loop(load: Load, tr: dict, seconds: float, trace_hook=None) -> dict:
    """Requests sent at their due times whatever the system does."""
    due = traffic_mod.arrival_times(tr, seconds)
    threads: List[threading.Thread] = []
    w0 = time.perf_counter() + 0.05
    wall0 = time.time() + 0.05
    if trace_hook:
        trace_hook(w0)
    for d in due:
        wait = w0 + d - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        t = threading.Thread(target=load.one,
                             args=(load.take_index(), w0 + d), daemon=True)
        t.start()
        threads.append(t)
    time.sleep(max(0.0, w0 + seconds - time.perf_counter()))
    in_flight_at_close = sum(t.is_alive() for t in threads)
    drain_by = time.perf_counter() + tr.get("drain_timeout_s", 120)
    for t in threads:
        t.join(max(0.0, drain_by - time.perf_counter()))
    w1 = time.perf_counter()
    load.closing.set()  # whatever still runs is abandoned, and counts failed
    return {"w0": w0, "w1": w1, "wall0": wall0, "threads": threads,
            "window_end": w0 + seconds,
            "in_flight_at_close": in_flight_at_close}


def summarize(outs: List[Outcome], win: dict) -> dict:
    """End-to-end numbers of one window, from the clients' own clocks."""
    mine = [o for o in outs if o.due is not None]
    ok = [o for o in mine if o.arrivals and not o.error]
    lat = stats.open_loop_latencies([o.due for o in ok], [o.sent for o in ok],
                                    [o.arrivals[0] for o in ok])
    # a request with no first token by the end of the drain has failed
    res: Dict[str, Any] = {
        "attempted": len(mine),
        "failed": sum(1 for o in mine if o.error or not o.arrivals),
        "completed": sum(1 for o in mine if o.done)}
    if ok:
        res["ttft_p95_ms"] = 1e3 * stats.percentile_with_misses(
            lat["ttft"], 0.95, res["failed"])
        res["ttft_p50_ms"] = 1e3 * stats.median(lat["ttft"])
        res["lateness_p95_ms"] = 1e3 * stats.percentile(lat["lateness"], 0.95)
        res["lateness_max_ms"] = 1e3 * max(lat["lateness"])
    res["requests_per_s_done"] = res["completed"] / (win["w1"] - win["w0"])
    res["in_flight_at_close"] = win["in_flight_at_close"]
    res["drain_s"] = win["w1"] - win["window_end"]
    res["errors"] = sorted({o.error for o in outs if o.error})[:5]
    return res


def new_stream(tr: dict, cfg: dict, seed: int, seconds: float
               ) -> traffic_mod.RequestStream:
    """The requests of one window of ``seconds``."""
    return traffic_mod.RequestStream(
        tr, cfg["vocab_size"], seed, traffic_mod.request_count(tr, seconds))


def start(bundle: dict, args) -> dict:
    """``ray_tpu.init``, the deployment, its engine warmed and checked, and
    the compiled stream lane up. Returns what a window needs."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import observability as obs

    cfg, tr = bundle["config"], bundle["traffic"]
    dep = cfg["deployment"]
    layer_specs = spec.layer_specs(bundle)
    bench = {"config": cfg, "traffic": tr,
             "seed": traffic_mod.fold_seed(args.seed),
             "kernel_patterns": reducers.kernel_patterns(layer_specs.values()),
             "keep_dir": args.keep, "fault": getattr(args, "fault", None)}
    ray_tpu.init()
    options = {"num_tpus": 1} if not args.rehearsal else {"num_cpus": 1}
    deployment = serve.deployment(
        decode=True, name="BenchLM", route_prefix=None,
        ray_actor_options=options, max_inflight=dep["max_inflight"],
        request_timeout_s=dep["control_timeout_s"])(BenchLM)
    handle = serve.run(deployment.bind(bench))
    prepared = handle.prepare.remote().result(
        timeout=dep["control_timeout_s"])

    def planes() -> dict:
        obs.drain_deferred()
        return serve.status().get("BenchLM", {}).get("dispatch_planes", {})

    # warm the serve plane itself: the first stream lands on the eager
    # generator while the compiled stream lane is built
    stream = new_stream(tr, cfg, bench["seed"], args.seconds)
    # a prompt of its own (no request of the window finds it cached), of
    # the shortest length: shapes the cell has warmed
    probe = {"prompt": traffic_mod.prompt_tokens(
        cfg["vocab_size"], bench["seed"], 10 ** 6,
        int(tr["prompt_tokens"].get("min", tr["prompt_tokens"].get("value")))),
        "max_tokens": 2}
    streaming = handle.options(stream=True,
                               stream_item_timeout_s=dep["control_timeout_s"])
    deadline = time.monotonic() + 120
    warm_streams = 0
    while planes().get("compiled_stream", 0) < 1:
        list(streaming.remote(probe))
        warm_streams += 1
        if time.monotonic() > deadline:
            raise RuntimeError(f"the compiled stream lane never came up: "
                               f"{planes()}")
    prepared["warm_streams"] = warm_streams
    return {"handle": handle, "stream": stream, "prepared": prepared,
            "layer_specs": layer_specs, "planes": planes, "bench": bench}


def stop() -> None:
    import ray_tpu
    from ray_tpu import serve

    try:
        serve.shutdown()
    finally:
        ray_tpu.shutdown()


def run_window(ctx: dict, tr: dict, args, seconds: float) -> dict:
    """One measured window against a started deployment."""
    handle = ctx["handle"]
    timeout = ctx["bench"]["config"]["deployment"]["control_timeout_s"]
    load = Load(handle, ctx["stream"], tr["stream_item_timeout_s"])
    traced: Dict[str, Any] = {}
    tracer_thread: List[threading.Thread] = []

    def trace_hook(w0: float) -> None:
        def body():
            time.sleep(max(0.0, w0 + tr.get("trace_after_s", 2.0)
                           - time.perf_counter()))
            handle.trace_start.remote().result(timeout=timeout)
            time.sleep(tr.get("trace_seconds", 3.0))
            traced["digest"] = handle.trace_stop.remote().result(
                timeout=timeout)

        t = threading.Thread(target=body, daemon=True)
        t.start()
        tracer_thread.append(t)

    before = handle.snapshot.remote().result(timeout=timeout)
    win = open_loop(load, tr, seconds, trace_hook if args.trace else None)
    after = handle.snapshot.remote().result(timeout=timeout)
    for t in tracer_thread:
        t.join(timeout)
    names = sorted(reducers.wanted_spans(ctx["layer_specs"].values()))
    spans = handle.spans.remote(names, before["mono"], after["mono"]).result(
        timeout=timeout) if names else {}
    res = summarize(load.snapshot(), win)
    res.update({"before": before, "after": after, "spans": spans,
                "digest": traced.get("digest"), "wall0": win["wall0"],
                "w0": win["w0"], "w1": win["w1"]})
    return res


def run(bundle: dict, args, t_start: float) -> dict:
    cfg, tr = bundle["config"], bundle["traffic"]
    ctx = start(bundle, args)
    try:
        res = run_window(ctx, tr, args, args.seconds)
        planes = ctx["planes"]()
    finally:
        stop()
    prepared, before, after = ctx["prepared"], res["before"], res["after"]
    why = []
    tol = cfg["correct"]["serve_logits_rel_tol"]
    if not max(prepared["rel_err"]) <= tol:
        why.append(f"prefill/decode logits vs float32 reference: rel err "
                   f"{prepared['rel_err']} > {tol}")
    compiles = after["compile"]["requests"] - before["compile"]["requests"]
    compile_s = after["compile"]["compile_s"] - before["compile"]["compile_s"]
    if compiles or compile_s > 0:
        why.append(f"{compiles} compilation(s) inside the window "
                   f"({compile_s:.3f} s)")
    if res["attempted"] == 0 or res["failed"] == res["attempted"]:
        why.append(f"no request succeeded: {res['errors']}")
    e2e = {"setup_s": res["wall0"] - t_start}
    if "ttft_p95_ms" in res:
        e2e["ttft_p95_ms"] = res["ttft_p95_ms"]
    counters = {
        "decode_tokens": after["decode_calls"] - before["decode_calls"],
        "prefills": after["prefill_calls"] - before["prefill_calls"],
        "compile_s": after["compile"]["compile_s"],
        "cache_hits": after["compile"]["hits"],
        "cache_misses": after["compile"]["misses"],
    }
    evidence = {"spans": res["spans"], "trace": res["digest"],
                "counters": counters, "e2e": e2e, "config": cfg,
                "traffic": tr, "chips": 1,
                "device_kind": after["device"]["kind"]}
    detail = {k: v for k, v in res.items() if k not in (
        "before", "after", "spans", "digest", "wall0", "w0", "w1")}
    detail.update({"prepared": prepared, "planes": planes,
                   "counters": counters, "compiles_in_window": compiles})
    return {"attempted": res["attempted"], "failed": res["failed"],
            "why_not_correct": why, "e2e": e2e, "evidence": evidence,
            "layer_specs": ctx["layer_specs"], "device": after["device"],
            "window_close_wall": res["wall0"] + (res["w1"] - res["w0"]),
            "detail": detail}
