"""A training cell: ``JaxTrainer`` with the program's default
``spmd_train_loop``, driven so that the measured window lasts ``--seconds``.

``bench_train_loop`` runs in the trainer's worker (the process that holds
the chips). It calls the program's ``spmd_train_loop`` twice: a few steps
that compile, warm up and time a step, then ``1 + ceil(seconds / step)``
steps of which the first (trace, lower, load from the compile cache) is
still set-up. The benchmark takes its own clock readings when each step
reports, so tokens and seconds are the benchmark's, not the program's.
"""

from __future__ import annotations

import math
import shutil
import tempfile
import time
from typing import Any, Dict

from . import onchip, spec, traffic as traffic_mod


def first_batch(vocab: int, batch: int, seq: int, seed: int):
    """The first batch of the program's synthetic stream for ``seed``
    (``train/spmd.py _synthetic_token_batches``: one RandomState, batches
    drawn in order)."""
    import numpy as np

    return np.random.RandomState(seed).randint(
        0, vocab, (batch, seq + 1)).astype(np.int32)


def check_against_reference(cfg_file: dict, cfg_obj, tokens, seed: int,
                            ref_rows: int, rows_per_call: int) -> dict:
    """On one device, same seeded parameters: the program's loss (its
    compute type, its attention kernel) over the whole batch,
    ``rows_per_call`` sequences at a time, and over the first ``ref_rows``
    sequences against the plain float32 reference."""
    import gc
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import init_params, loss_fn

    reference = jax.jit(partial(
        spec.resolve(cfg_file["reference"] + ":loss"), cfg_file))
    program = jax.jit(partial(loss_fn, cfg_obj))
    params = jax.jit(partial(init_params, cfg_obj))(jax.random.PRNGKey(seed))
    parts = [float(program(params, jnp.asarray(tokens[i:i + rows_per_call])))
             for i in range(0, len(tokens), rows_per_call)]
    got_all = sum(parts) / len(parts)
    got = (float(program(params, jnp.asarray(tokens[:ref_rows])))
           if ref_rows != rows_per_call else parts[0])
    want = float(reference(params, jnp.asarray(tokens[:ref_rows])))
    del params
    gc.collect()
    return {"program_loss": got_all, "program_loss_ref_rows": got,
            "reference_loss": want, "ref_rows": ref_rows,
            "rel_err": abs(got - want) / abs(want)}


def bench_train_loop(config: Dict[str, Any]):
    import jax

    from ray_tpu.ops.flash_attention import paths_taken
    from ray_tpu.train import session
    from ray_tpu.train.spmd import spmd_train_loop

    b = config["bench"]
    cfg_file, tr_file = b["config"], b["traffic"]
    seed = b["seed"]
    cfg_obj = spec.program_config(cfg_file)
    chips = jax.local_device_count()
    batch, seq = tr_file["batch_per_chip"] * chips, tr_file["seq"]
    out: Dict[str, Any] = {"chips": chips}

    out["reference"] = check_against_reference(
        cfg_file, cfg_obj,
        first_batch(cfg_file["vocab_size"], batch, seq, seed), seed,
        min(batch, tr_file.get("reference_sequences", batch)),
        tr_file["batch_per_chip"])

    loop_cfg = {"llama_config": cfg_obj, "seq": seq, "seed": seed,
                "batch_per_device": tr_file["batch_per_chip"],
                "distinct_batches": tr_file["distinct_batches"],
                "report_every": 1}
    if tr_file.get("mesh"):
        loop_cfg["mesh"] = tr_file["mesh"]

    stamps: list = []      # (step, perf_counter, monotonic, wall, loss)
    hooks: Dict[int, list] = {}  # measured-call step -> what to do there
    program_report = session.report

    def stamping_report(metrics, checkpoint=None):
        stamps.append((metrics.get("step"), time.perf_counter(),
                       time.monotonic(), time.time(), metrics.get("loss")))
        for hook in hooks.get(metrics.get("step"), ()):
            hook()
        last.update(metrics)
        program_report(metrics, checkpoint)  # what a user's step pays too

    last: Dict[str, Any] = {}
    session.report = stamping_report
    try:
        # 1. compile, warm up, time a step
        spmd_train_loop(dict(loop_cfg, steps=tr_file["warm_steps"]))
        warm = list(stamps)
        # the last warm step's own time: earlier ones still warm up
        step_s = warm[-1][1] - warm[-2][1] if len(warm) > 1 else 1.0
        n = max(2, math.ceil(b["seconds"] / step_s))
        # 2. the measured call: step 1 is still set-up, then n steps
        del stamps[:]
        tracer = None
        at_open: Dict[str, Any] = {}
        hooks[1] = [lambda: at_open.update(onchip.compile_counts())]
        if b["trace"]:
            tracer = onchip.WindowTrace(b["kernel_patterns"])
            t_from = min(tr_file.get("trace_from_step", 4), n - 1)
            t_to = min(t_from + tr_file.get("trace_steps", 3), n + 1)
            hooks.setdefault(t_from, []).append(tracer.start)
            hooks.setdefault(t_to, []).append(tracer.stop)
        spmd_train_loop(dict(loop_cfg, steps=n + 1))
    finally:
        session.report = program_report
    at_close = onchip.compile_counts()
    open_, close = stamps[0], stamps[-1]
    window_s = close[1] - open_[1]
    # the profiler's start and stop cost seconds inside a traced window, so
    # a traced run's per-layer rate is taken over the steps after them
    clear = [s for s in stamps if tracer is not None and s[0] > t_to]
    if len(clear) < 3:
        clear = stamps
    out.update({
        "steps": n, "step_s_warm": step_s,
        "tokens": n * batch * seq, "window_s": window_s,
        "clear_tokens": (len(clear) - 1) * batch * seq,
        "clear_s": clear[-1][1] - clear[0][1],
        "window_open_wall": open_[3], "window_close_wall": close[3],
        "compiles_in_window": at_close["requests"] - at_open["requests"],
        "compile_s_in_window": at_close["compile_s"] - at_open["compile_s"],
        "first_loss": warm[0][4], "last_loss": close[4],
        "measured_first_loss": open_[4],
        "attention_paths": sorted({r["path"] for r in paths_taken()}),
        "mesh": last.get("mesh"),
        "spans": onchip.local_spans(b["wanted_spans"], open_[2], close[2]),
        "device": onchip.device_fields(),
    })
    if tracer is not None and tracer.mono_close is not None:
        out["trace"] = tracer.digest(b.get("keep_dir"))
    program_report({"bench_result": out, "step": -1})
    return close[4]


def run(bundle: dict, args, t_start: float) -> dict:
    """Driver side: no jax here."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from . import reducers

    cell, cfg, tr = bundle["cell"], bundle["config"], bundle["traffic"]
    layer_specs = spec.layer_specs(bundle)
    run_dir = tempfile.mkdtemp(prefix="bench_train_")
    bench = {"config": cfg, "traffic": tr, "seconds": args.seconds,
             "trace": bool(args.trace), "seed": traffic_mod.fold_seed(args.seed),
             "wanted_spans": sorted(reducers.wanted_spans(layer_specs.values())),
             "kernel_patterns": reducers.kernel_patterns(layer_specs.values()),
             "keep_dir": args.keep}
    ray_tpu.init()
    try:
        result = JaxTrainer(
            bench_train_loop, train_loop_config={"bench": bench},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=not args.rehearsal,
                chips_per_worker=cell["chips"]),
            run_config=RunConfig(name="bench", storage_path=run_dir),
        ).fit()
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(run_dir, ignore_errors=True)
    if result.error is not None:
        raise RuntimeError(f"the trainer failed: {result.error}")
    rows = [r for r in result.metrics_dataframe if "bench_result" in r]
    if not rows:
        raise RuntimeError("the train loop reported no result")
    res = rows[-1]["bench_result"]
    chips = res["device"]["count"]
    rate = res["tokens"] / res["window_s"] / chips
    tol = cfg["correct"]
    why = []
    ref = res.get("reference")
    if ref is None:
        why.append("no reference comparison ran")
    else:
        if not ref["rel_err"] <= tol["train_loss_rel_tol"]:
            why.append(f"program loss vs float32 reference: {ref}")
        # the loop's first loss is over the whole first batch, on its own
        # layout: held to the program's loss on one device, which the
        # reference holds on the rows it can afford
        loop_rel = abs(res["first_loss"] - ref["program_loss"]) \
            / abs(ref["program_loss"])
        res["reference"]["loop_first_loss_rel_err"] = loop_rel
        if not loop_rel <= tol["train_loss_rel_tol"]:
            why.append(f"the loop's first loss {res['first_loss']} vs the "
                       f"program's loss on one device {ref['program_loss']}")
    losses = [res["first_loss"], res["measured_first_loss"], res["last_loss"]]
    if not all(isinstance(x, float) and math.isfinite(x) for x in losses):
        why.append(f"loss not finite: {losses}")
    elif not res["last_loss"] < res["measured_first_loss"]:
        why.append(f"loss did not fall: {losses}")
    want_path = ["pallas_interpret" if args.rehearsal else "pallas"]
    if res["attention_paths"] != want_path and not (
            args.rehearsal and res["attention_paths"]):
        why.append(f"attention ran {res['attention_paths']}, not the kernel")
    if res["compiles_in_window"] or res["compile_s_in_window"] > 0:
        why.append(f"{res['compiles_in_window']} compilation(s) inside the "
                   f"window ({res['compile_s_in_window']:.3f} s)")
    evidence = {
        "spans": res["spans"], "trace": res.get("trace"),
        "counters": {"compile_s": res["device"]["compile_s"],
                     "cache_hits": res["device"]["cache_hits"],
                     "cache_misses": res["device"]["cache_misses"]},
        "e2e": {"train_tokens_per_s_chip":
                res["clear_tokens"] / res["clear_s"] / chips},
        "config": cfg, "traffic": tr, "chips": chips,
        "device_kind": res["device"]["kind"],
    }
    return {
        "attempted": res["steps"], "failed": 0, "why_not_correct": why,
        "e2e": {"train_tokens_per_s_chip": rate,
                "setup_s": res["window_open_wall"] - t_start},
        "evidence": evidence, "layer_specs": layer_specs,
        "device": res["device"], "window_close_wall": res["window_close_wall"],
        "detail": {k: res[k] for k in (
            "steps", "step_s_warm", "window_s", "clear_tokens", "clear_s",
            "first_loss", "last_loss",
            "reference", "mesh", "compiles_in_window") if k in res},
    }
