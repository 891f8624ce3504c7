"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the main path once, through the entry points a user calls, at the
full width of ``LlamaConfig.bench()`` (1536d / 16L / 12 heads / 6 KV heads /
head_dim 128 / vocab 32,000; 664.6M parameters) with seeded random weights:

1. ``kernels``: in a worker bound to the chips, the Pallas flash kernel
   compiled (not interpreted), forward and ``jax.grad``, against
   ``plain_attention`` in float32; the shard_map train step's first loss
   against the GSPMD step's on the same seed and batch (two layers, full
   width); one decode position's logits through ``LlamaDecodeEngine``
   against ``forward``.
2. ``trainer``: ``ray_tpu.init()`` (chips found by detection) and
   ``JaxTrainer(...).fit()`` with the default ``spmd_train_loop`` in a worker
   that owns every local chip: batch 8 x 2048 per chip, 8 steps.
3. ``server``: after the trainer's worker has given the chip back,
   ``serve.run`` of a ``decode=True`` deployment with ``num_tpus=1`` around
   ``LlamaDecodeEngine``; four token streams, two of them sharing a prompt.

On a host with more than one chip it adds, unasked: two one-chip actors
alive at once on distinct chips (one of them computes the one-chip
first-step loss), the trainer on ``fsdp=N`` with the shards of a parameter
checked to lie on N devices, and three steps on ``fsdp=N/2,tensor=2``.

This process never imports jax: every device touch happens in a worker the
runtime bound to the chip. Each phase prints the platform, device kind and
device count of the process that did its work, and anything but ``tpu``
fails it. The exit code is 0 only if every phase passed. The run ends in two
lines of stdout: ``[chip_smoke] summary: {...}``, one JSON object with every
phase's record (versions, wall and compile seconds, cache hits and misses,
losses or token counts, peak device memory), and then, last, the verdict

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reported it to the worker that held every chip. The
verdict has these keys and no others; it is printed only if a worker reached
a device, and never by ``--rehearsal``. Step time is printed as a sanity
figure; it is not a metric and is written nowhere as a rate.

With no chip on the host it exits non-zero at once. ``--rehearsal`` runs the
same phases at a tiny size on the CPU backend (kernels interpreted), says
``"rehearsal": true`` and ``"platform": "cpu"`` in its result, and exists to
debug this script where there is no chip; it is never a pass on the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

REAL = {
    "model": "bench", "vocab": 32000,
    "seq": 2048, "batch_per_device": 8, "steps": 8,
    "kernel_shape": (2, 2048, 12, 6, 128),      # B, T, Hq, Hkv, D
    "parity_layers": 2, "parity_batch_per_device": 2,
    "page_size": 16, "n_pages": 64,
    "prompt_lens": (32, 64, 128), "max_tokens": 16,
    "interpret": False,
}
REHEARSAL = {
    "model": "debug", "vocab": 256,
    "seq": 64, "batch_per_device": 2, "steps": 4,
    "kernel_shape": (1, 128, 4, 2, 32),
    "parity_layers": 2, "parity_batch_per_device": 2,
    "page_size": 4, "n_pages": 64,
    "prompt_lens": (8, 12, 16), "max_tokens": 6,
    "interpret": True,
}
TP_STEPS = 3            # steps of the fsdp x tensor run on a multi-chip host
LOSS_REL_TOL = 0.02     # first-step loss agreement between two programs
STREAM_ITEM_TIMEOUT_S = 900.0  # the first token waits for engine + compiles
SUMMARY_PREFIX = "[chip_smoke] summary: "  # the line before the verdict


# --------------------------------------------------------------------------- #
# Work that runs in chip-bound workers (shipped by value; imports inside)
# --------------------------------------------------------------------------- #


def kernel_checks(p: dict) -> dict:
    """Section-6 checks, in one process that holds the chips."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import (LlamaConfig, LlamaDecodeEngine, forward,
                                      make_train_step)
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.parallel.ring_attention import plain_attention
    from ray_tpu.parallel.sharding import shard_device_put
    from ray_tpu.serve.kv_cache import pages_for
    from ray_tpu.train.spmd import build_train_mesh, make_spmd_train_step
    from ray_tpu.util.device_telemetry import process_device_report

    out: dict = {}
    interpret = p["interpret"]
    B, T, Hq, Hkv, D = p["kernel_shape"]
    want = fa.PATH_PALLAS_INTERPRET if interpret else fa.PATH_PALLAS
    path, reason = fa.attention_path((B, T, Hq, D), (B, T, Hkv, D),
                                     interpret=interpret)
    if path != want:
        raise AssertionError(
            f"flash_attention at q[{B},{T},{Hq},{D}] kv heads {Hkv} would "
            f"run {path!r} ({reason}); the {want!r} kernel is required here")

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, T, Hq, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.bfloat16)
    w = jnp.asarray(rng.randn(B, T, Hq, D), jnp.float32)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, interpret=interpret)

    def reference(q, k, v):
        rep = Hq // Hkv
        f32 = jnp.float32
        return plain_attention(q.astype(f32),
                               jnp.repeat(k.astype(f32), rep, axis=2),
                               jnp.repeat(v.astype(f32), rep, axis=2),
                               causal=True)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda q, k, v, w: (fn(q, k, v).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2)))(q, k, v, w)

    o, o_ref = jax.jit(kernel)(q, k, v), jax.jit(reference)(q, k, v)
    fwd_err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - o_ref)))
    grad_err = [
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
              / jnp.max(jnp.abs(b)))
        for a, b in zip(grads(kernel), grads(reference))]
    took = {r["path"] for r in fa.paths_taken()}
    out["flash"] = {"path": sorted(took), "fwd_max_abs_err": fwd_err,
                    "grad_max_rel_err": grad_err}
    # bf16 tolerance: the output is rounded to bf16 (8 bits of mantissa)
    # and |o| reaches a few units; gradients accumulate bf16 products
    if took != {want} or not fwd_err < 0.05 or not max(grad_err) < 0.05:
        raise AssertionError(f"flash kernel check failed: {out['flash']}")

    # shard_map step vs GSPMD step: same seed, same batch, first loss
    cfg = dataclasses.replace(getattr(LlamaConfig, p["model"])(),
                              n_layers=p["parity_layers"])
    mesh = build_train_mesh(p["mesh"])
    tokens = np.random.RandomState(1).randint(
        0, cfg.vocab_size,
        (p["parity_batch_per_device"] * mesh.size, p["seq"] + 1)
    ).astype(np.int32)
    losses = {}
    for name, make in (("spmd", make_spmd_train_step),
                       ("gspmd", make_train_step)):
        init, step, data_sharding, _ = make(cfg, mesh)
        state = init(jax.random.PRNGKey(0))
        state, loss = step(state, shard_device_put(tokens, data_sharding))
        losses[name] = float(loss)
        del state
    rel = abs(losses["spmd"] - losses["gspmd"]) / abs(losses["gspmd"])
    out["step_parity"] = {**losses, "rel": rel, "mesh": dict(mesh.shape),
                          "layers": cfg.n_layers}
    if not rel <= p["loss_rel_tol"]:
        raise AssertionError(f"spmd/gspmd first loss differ: "
                             f"{out['step_parity']}")

    # one decode position through the engine's paged cache vs forward()
    ps = p["page_size"]
    engine = LlamaDecodeEngine(cfg, n_pages=8, page_size=ps, seed=0)
    n_prompt = 2 * ps
    toks = np.random.RandomState(2).randint(
        0, cfg.vocab_size, (n_prompt + 1,)).astype(np.int32)
    pages = engine.pool.alloc(pages_for(n_prompt + 1, ps))
    got_prefill = engine.prefill(list(toks[:n_prompt]),
                                 pages[:pages_for(n_prompt, ps)])
    got_decode = engine.decode(n_prompt, int(toks[n_prompt]), pages)
    full = np.asarray(jax.jit(lambda prm, t: forward(cfg, prm, t))(
        engine.params, jnp.asarray(toks[None, :])), np.float32)[0]
    scale = float(np.max(np.abs(full)))
    dec_err = float(np.max(np.abs(got_decode - full[n_prompt]))) / scale
    pre_err = float(np.max(np.abs(got_prefill - full[n_prompt - 1]))) / scale
    out["decode_logits"] = {"prefill_rel_err": pre_err,
                            "decode_rel_err": dec_err,
                            "max_abs_logit": scale}
    # bf16 tolerance: forward() attends with the flash kernel over the
    # whole sequence, the cache path with float32 scores over its pages
    if not max(dec_err, pre_err) < 0.05:
        raise AssertionError(f"cached decode logits differ from forward: "
                             f"{out['decode_logits']}")
    out["device_report"] = process_device_report()
    return out


class OneChipProbe:
    """An actor on ONE chip of a multi-chip host: says which device it
    sees, and computes the one-chip value the sharded runs are held to."""

    def devices(self) -> dict:
        """What this process holds. JAX numbers devices per process, so
        two one-chip processes both call theirs id 0; which physical chip
        it is shows in the binding and in the chip's device file this
        process has open (a chip's file opens for one process only)."""
        import jax

        import ray_tpu
        from ray_tpu.util.device_telemetry import process_device_report

        jax.local_devices()[0].memory_stats()  # the chip is really open
        opened = set()
        for fd in os.listdir("/proc/self/fd"):
            try:
                target = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                continue
            name = os.path.basename(target)
            if (target.startswith("/dev/vfio/") and name.isdigit()) \
                    or target.startswith("/dev/accel"):
                opened.add(target)
        return {"jax_ids": [int(d.id) for d in jax.local_devices()],
                "bound_chips": ray_tpu.get_runtime_context()
                .get_accelerator_ids().get("TPU"),
                "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
                "chip_files": sorted(opened),
                "device_report": process_device_report()}

    def first_step_loss(self, model: str, batch: int, seq: int,
                        seed: int) -> float:
        """The loss of the trainer's first step (it is computed before the
        update): same seed -> same params and same synthetic batch as the
        train loop's, evaluated forward-only on this one chip."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
        from ray_tpu.train.spmd import _synthetic_token_batches

        cfg = getattr(LlamaConfig, model)()
        tokens = next(_synthetic_token_batches(
            cfg.vocab_size, batch, seq, seed, distinct=1))
        params = jax.jit(lambda key: init_params(cfg, key))(
            jax.random.PRNGKey(seed))
        return float(jax.jit(lambda p, t: loss_fn(cfg, p, t))(
            params, jnp.asarray(tokens)))


def make_smoke_lm(serve, p: dict, actor_options: dict):
    @serve.deployment(decode=True, name="SmokeLM", route_prefix=None,
                      ray_actor_options=actor_options)
    class SmokeLM:
        def create_decode_engine(self):
            from ray_tpu.models.llama import LlamaConfig, LlamaDecodeEngine

            return LlamaDecodeEngine(
                getattr(LlamaConfig, p["model"])(), n_pages=p["n_pages"],
                page_size=p["page_size"], seed=0)

        def device_report(self):
            from ray_tpu.ops.flash_attention import paths_taken
            from ray_tpu.util.device_telemetry import process_device_report

            return {**process_device_report(),
                    "attention_paths": paths_taken()}

    return SmokeLM


# --------------------------------------------------------------------------- #
# Phases (driver side: no jax here)
# --------------------------------------------------------------------------- #


class PhaseFailed(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def device_fields(report: dict) -> dict:
    """The per-phase columns every phase shares, from the device report of
    the process that did the phase's work."""
    return {
        "platform": report["platform"],
        "device_kind": report["device_kind"],
        "devices": report["devices"],
        "compile_s": report["compile_s"],
        "cache_hits": report["cache_hits"],
        "cache_misses": report["cache_misses"],
        "peak_bytes_in_use": [m["peak_bytes_in_use"]
                              for m in report["memory"]],
        "bytes_in_use": [m["bytes_in_use"] for m in report["memory"]],
    }


def check_ran_on(fields: dict, want_platform: str, want_devices: int) -> None:
    check(fields["platform"] == want_platform,
          f"ran on platform {fields['platform']!r}, not {want_platform!r}")
    check(fields["devices"] == want_devices,
          f"process saw {fields['devices']} device(s), bound to "
          f"{want_devices}")


def phase_kernels(ctx) -> dict:
    import ray_tpu

    p = dict(ctx.sizes, mesh=ctx.mesh_spec(ctx.n), loss_rel_tol=LOSS_REL_TOL)
    task = ray_tpu.remote(**ctx.bind(ctx.n))(kernel_checks)
    res = ray_tpu.get(task.remote(p), timeout=900)
    fields = device_fields(res.pop("device_report"))
    check_ran_on(fields, ctx.platform, ctx.n)
    return {**fields, **res}


def phase_actors(ctx) -> dict:
    """Two one-chip actors alive at the same time: each sees exactly one
    device, on a different chip. One computes the one-chip first-step loss
    the multi-chip trainer runs are compared to."""
    import ray_tpu

    Probe = ray_tpu.remote(**ctx.bind(1))(OneChipProbe)
    a, b = Probe.remote(), Probe.remote()
    try:
        da, db = ray_tpu.get([a.devices.remote(), b.devices.remote()],
                             timeout=300)
        fa_, fb = (device_fields(d["device_report"]) for d in (da, db))
        for f in (fa_, fb):
            check_ran_on(f, ctx.platform, 1)
        shown = [{k: v for k, v in d.items() if k != "device_report"}
                 for d in (da, db)]
        for d in (da, db):
            check(len(d["jax_ids"]) == 1 and len(d["bound_chips"]) == 1
                  and d["visible_chips"] == d["bound_chips"][0]
                  and len(d["chip_files"]) == 1,
                  f"a one-chip actor holds other than one chip: {shown}")
        check(da["bound_chips"] != db["bound_chips"]
              and da["chip_files"] != db["chip_files"],
              f"two one-chip actors share a chip: {shown}")
        s = ctx.sizes
        ref = ray_tpu.get(a.first_step_loss.remote(
            s["model"], s["batch_per_device"] * ctx.n, s["seq"], 0),
            timeout=600)
        ctx.one_chip_first_loss = ref
        return {**fa_, "actors": shown, "one_chip_first_loss": ref}
    finally:
        for actor in (a, b):
            ray_tpu.kill(actor)


def run_trainer(ctx, mesh: str, steps: int) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    s = ctx.sizes
    config = {"model": s["model"], "seq": s["seq"],
              "batch_per_device": s["batch_per_device"],
              "distinct_batches": 1, "steps": steps, "seed": 0}
    if mesh:
        config["mesh"] = mesh
    result = JaxTrainer(
        train_loop_config=config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=ctx.use_tpu,
                                     chips_per_worker=ctx.n),
        run_config=RunConfig(name=f"smoke_{mesh or 'default'}".replace(
            "=", "").replace(",", "_"), storage_path=ctx.run_dir),
    ).fit()
    check(result.error is None, f"trainer failed: {result.error}")
    rows = result.metrics_dataframe
    check(len(rows) == steps, f"{len(rows)} reports for {steps} steps")
    losses = [r["loss"] for r in rows]
    last = rows[-1]
    fields = device_fields(last["device_report"])
    check_ran_on(fields, ctx.platform, ctx.n)
    check(all(r["devices"] == ctx.n and r["platform"] == ctx.platform
              for r in rows), "a report names another device set")
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - math.log(s["vocab"])) < 1.0,
          f"first loss {losses[0]} not near "
          f"ln({s['vocab']})={math.log(s['vocab']):.2f}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    paths = {r["path"] for r in last["attention_paths"]}
    if not ctx.rehearsal:
        check(paths == {"pallas"},
              f"train step attention ran {sorted(paths)}, not the kernel")
    shards = last["param_shards"]
    out = {**fields, "mesh": last["mesh"], "first_loss": losses[0],
           "last_loss": losses[-1], "attention_paths": sorted(paths),
           "param_shards": shards,
           "step_s_sanity": round(last["step_seconds"], 3)}
    if ctx.n > 1:
        check(shards["devices"] == ctx.n
              and shards["distinct_shards"] == ctx.n,
              f"largest parameter not spread over {ctx.n} devices: {shards}")
    if ctx.n > 1 and not ctx.rehearsal:  # (a CPU reports no memory stats,
        # and the rehearsal has no one-chip actor to compare to)
        check(all(b for b in fields["bytes_in_use"]),
              f"a device holds nothing: {fields['bytes_in_use']}")
        ref = ctx.one_chip_first_loss
        check(ref is not None, "no one-chip first-step loss to compare to")
        rel = abs(losses[0] - ref) / abs(ref)
        out["one_chip_first_loss"], out["first_loss_rel"] = ref, rel
        check(rel <= LOSS_REL_TOL,
              f"first loss {losses[0]} vs one-chip {ref}: rel {rel:.4f}")
    return out


def phase_trainer(ctx) -> dict:
    return run_trainer(ctx, ctx.mesh_spec(ctx.n), ctx.sizes["steps"])


def phase_trainer_tp(ctx) -> dict:
    return run_trainer(ctx, f"fsdp={ctx.n // 2},tensor=2", TP_STEPS)


def phase_server(ctx) -> dict:
    import random

    from ray_tpu import serve

    s = ctx.sizes
    rnd = random.Random(0)
    prompts = [[rnd.randrange(s["vocab"]) for _ in range(n)]
               for n in s["prompt_lens"]]
    # the second prompt is asked twice: the repeat must hit the prefix cache
    requests = [prompts[0], prompts[1], prompts[1], prompts[2]]
    handle = serve.run(make_smoke_lm(serve, s, ctx.bind(1)).bind())
    try:
        finals = []
        for prompt in requests:
            items = list(handle.options(
                stream=True,
                stream_item_timeout_s=STREAM_ITEM_TIMEOUT_S).remote(
                {"prompt": prompt, "max_tokens": s["max_tokens"]}))
            final = items[-1]
            check(final.get("done") is True, f"stream ended in {final}")
            check(final["n_generated"] == s["max_tokens"]
                  and [c["token"] for c in items[:-1]] == final["tokens"],
                  f"expected {s['max_tokens']} streamed tokens: {final}")
            finals.append(final)
        check(finals[1]["tokens"] == finals[2]["tokens"]
              and finals[2]["cached_prefix"]
              and not finals[1]["cached_prefix"],
              f"repeat prompt: {finals[1]} then {finals[2]}")
        report = handle.device_report.remote().result(timeout=120)
        fields = device_fields(report)
        check_ran_on(fields, ctx.platform, 1 if ctx.use_tpu else ctx.n)
        want = len(requests) * s["max_tokens"]
        deadline = time.time() + 60
        counted = 0
        while time.time() < deadline:  # replica metrics reach the head
            counted = serve.status().get("SmokeLM", {}).get(  # on a cadence
                "tokens_generated", 0)
            if counted >= want:
                break
            time.sleep(0.5)
        check(counted == want,
              f"serve.status() counts {counted} tokens, {want} were streamed")
        return {**fields, "requests": len(requests),
                "tokens_streamed": want, "tokens_counted": int(counted),
                "prefix_hits": sum(bool(f["cached_prefix"]) for f in finals),
                "prompt_lens": [len(r) for r in requests]}
    finally:
        serve.shutdown()


def print_cluster_warnings() -> None:
    """What the runtime itself said went wrong (a replica killed for a
    missed health check, a worker that died), next to a failed phase."""
    from ray_tpu.util import state

    for ev in state.list_cluster_events(min_severity="WARNING", limit=20):
        print(f"[cluster event] {ev.get('severity')} {ev.get('source')}: "
              f"{ev.get('message')}", file=sys.stderr, flush=True)


class Context:
    def __init__(self, n: int, rehearsal: bool, run_dir: str):
        self.n = n
        self.rehearsal = rehearsal
        self.use_tpu = not rehearsal
        self.platform = "cpu" if rehearsal else "tpu"
        self.sizes = REHEARSAL if rehearsal else REAL
        self.run_dir = run_dir
        self.one_chip_first_loss = None

    def mesh_spec(self, n: int) -> str:
        return f"fsdp={n}" if n > 1 else ""

    def bind(self, chips: int) -> dict:
        """Resource options that bind a task or actor to ``chips`` chips
        (none in the rehearsal: its workers run on the CPU backend)."""
        return {"num_tpus": chips} if self.use_tpu else {"num_cpus": 1}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="tiny sizes on the CPU backend, kernels interpreted; for "
             "debugging this script without a chip, never a pass on one")
    args = parser.parse_args()

    try:
        import ray_tpu
        from ray_tpu.core.accelerators import detect_num_tpu_chips
        from ray_tpu.core.object_store import allocator_kind
    except ImportError as e:
        print(f"chip_smoke.py runs from the root of the ray_tpu checkout "
              f"(cannot import the package: {e})", file=sys.stderr)
        return 2

    if args.rehearsal:
        n = 4  # virtual CPU devices, so the multi-device extras are walked
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            " ".join(f for f in os.environ.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f)
            + f" --xla_force_host_platform_device_count={n}").strip()
    else:
        n = detect_num_tpu_chips()
        if n == 0:
            print("chip_smoke.py: no TPU chip on this host (no /dev/accel* "
                  "and no /dev/vfio/<n>); nothing to prove here. "
                  "--rehearsal runs the script's own logic on the CPU.",
                  file=sys.stderr)
            return 3

    run_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    ctx = Context(n, args.rehearsal, run_dir)
    phases = [("kernels", phase_kernels)]
    if n > 1 and not args.rehearsal:
        # one-chip bindings beside each other need real chips: the
        # rehearsal's CPU workers all see the same virtual devices
        phases.append(("actors", phase_actors))
    phases.append(("trainer", phase_trainer))
    if n > 1:
        phases.append(("trainer_tp", phase_trainer_tp))
    phases.append(("server", phase_server))

    results: dict = {}
    t_start = time.time()
    ray_tpu.init()  # no num_tpus=: detection must find the chips
    try:
        found = int(ray_tpu.cluster_resources().get("TPU", 0))
        if not args.rehearsal and found != n:
            raise RuntimeError(f"ray_tpu.init() advertises {found} TPU "
                               f"chip(s), the host has {n}")
        for name, fn in phases:
            t0 = time.time()
            try:
                res = fn(ctx)
                res["ok"] = True
            except Exception as e:  # noqa: BLE001 - recorded, fails the run
                res = {"ok": False, "error": f"{type(e).__name__}: {e}"}
                traceback.print_exc()
                print_cluster_warnings()
            res["wall_s"] = round(time.time() - t0, 1)
            results[name] = res
            print(f"[chip_smoke] {name}: {json.dumps(res, default=str)}",
                  flush=True)
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(run_dir, ignore_errors=True)

    ok = all(r["ok"] for r in results.values())
    reports = [r for r in results.values() if "platform" in r]
    # the device, as JAX reported it to the worker that held every chip
    seen = max(reports, key=lambda r: r["devices"], default={})
    kinds = {r["device_kind"] for r in reports}
    platforms = {r["platform"] for r in reports}
    ok = ok and platforms == {ctx.platform} and len(kinds) == 1
    assert "jax" not in sys.modules, "the smoke's parent imported jax"
    import importlib.metadata as md

    summary = {
        "ok": bool(ok),
        "rehearsal": args.rehearsal,
        "platform": seen.get("platform"),
        "device_kind": seen.get("device_kind"),
        "devices": n,
        "jax": md.version("jax"),
        "libtpu": md.version("libtpu"),
        "plasma_allocator": allocator_kind(),
        "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "wall_s": round(time.time() - t_start, 1),
        "phases": results,
    }
    print(f"{SUMMARY_PREFIX}{json.dumps(summary, default=str)}", flush=True)
    if reports and not args.rehearsal:
        # the verdict: these keys and no others. Nothing is printed for a
        # device no worker reached, and a rehearsal is not a verdict.
        print(json.dumps({"ok": bool(ok), "device": {
            "platform": str(seen["platform"]),
            "kind": str(seen["device_kind"]),
            "count": int(seen["devices"])}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
