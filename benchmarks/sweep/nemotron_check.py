"""Nemotron-3-Nano's share at published widths, once, outside any measured
window: what the cell's own check (one loss against the reference) cannot
show.

    chiprun --chips 1 --timeout 1800 -- python3 benchmarks/sweep/nemotron_check.py [--seed N]

One process holds the chip. It prints one JSON object and writes it to
``chiprun_out/nemotron_check/result_<seed>.json``:

1. ``loss`` / ``gradients``: on ONE sequence of the cell's length, the
   program (``ray_tpu.models.llama.loss_parts``, its compute type, its
   kernels, its chunked scan) against the float32 reference (the
   recurrence, a step a token): the loss, the router's scalars, and for
   every parameter leaf the norm of the program's gradient, of the
   reference's, and of their difference over the reference's. Held to
   ``GRADIENT_REL_TOL``: the exit code is 1 if a leaf is beyond it.
2. ``controls``: what the file's ``train_loss_rel_tol`` has to refuse, as
   the relative distance of the loss from the reference's: the reference
   with its weights rounded to 8-bit floats (the nearest precision below
   the configuration's bfloat16), and the program with rotation switched
   on, with the last choice left out, with unscaled weights, and with the
   shared expert's output zeroed.
3. ``step``: the trainer's step (``make_spmd_train_step``, the cell's
   batch) traced for three calls: device time by ``ssm.*`` and ``moe.*``
   scope (an operation's scope is read from the compiled program's
   ``op_name`` metadata; the trace carries only instruction names), the
   flash kernels, the rest with its largest operations; the router's
   scalars; the compiler's account of the step's memory.

The reference's gradient keeps one layer's intermediates at a time (each
layer function under ``jax.checkpoint``, set here: the values are the same).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "train-nemotron3nano-1chip"
# A leaf's distance: the norm of (program's gradient - reference's) over the
# reference's norm, on one sequence of 8,192 at published widths. Three
# readings set the three limits (my chip runs, PR 31; PERF.md section 6):
# the program in float32 with whole float32 products (the reference's own
# mathematics through the chunked scan, the kernels and the held rows)
# reads FLOAT32 distances; the program as the cell runs it (bfloat16 products)
# reads 5-8% on every leaf but the routed ones, the rounding of nine layers'
# activations that a random-weight gradient does not average away; the
# router and the held experts read 15-21%, because a rounded router input
# moves the sixth choice of some tokens, each of which then runs another
# expert, and a held expert sees some 360 rows of one sequence. A wrong
# term reads near 1 on its leaf. Each limit is about 1.6 times its reading.
GRADIENT_REL_TOL = 0.12
ROUTED_REL_TOL = 0.35
FLOAT32_REL_TOL = 0.02
ROUTED = re.compile(r"\['moe'\]\['(router|w_up|w_down|w_gate)'\]")
SCOPE = re.compile(r"(ssm\.(?:in_proj|conv|scan|gate_norm|out_proj)"
                   r"|moe\.(?:route|dispatch|experts|combine|shared))")


def rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


def loss_and_gradients(cfg_file, cfg, params, tokens) -> dict:
    import dataclasses
    import importlib
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    ref = importlib.import_module(cfg_file["reference"])
    plain = dict(ref.LAYER)
    # one layer's intermediates alive at a time in the reference's backward
    ref.LAYER = {kind: (lambda c, h, p, f=f: jax.checkpoint(partial(f, c))(h, p))
                 for kind, f in plain.items()}

    def leaf_norms(tree):
        return {jax.tree_util.keystr(p): float(jnp.linalg.norm(g.astype(jnp.float32)))
                for p, g in jax.tree_util.tree_flatten_with_path(tree)[0]}

    try:
        r_loss, r_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(cfg_file, p, tokens)))(params)
    finally:
        ref.LAYER = plain
    rn = leaf_norms(r_grads)
    minus = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))

    def against_reference(c):
        (loss, rep), grads = jax.jit(jax.value_and_grad(
            lambda p: llama.loss_parts(c, p, tokens), has_aux=True))(params)
        pn, dn = leaf_norms(grads), leaf_norms(minus(grads, r_grads))
        return float(loss), rep, {
            k: {"program": pn[k], "reference": rn[k],
                "rel_diff": dn[k] / rn[k] if rn[k] else None} for k in rn}

    p_loss, p_rep, grads = against_reference(cfg)
    # the control that says whose the distance is: the same program in
    # float32 with whole float32 products is the reference's mathematics
    with jax.default_matmul_precision("highest"):
        f_loss, _, f_grads = against_reference(
            dataclasses.replace(cfg, dtype=jnp.float32))

    def beyond(tree, tols):
        return sorted(k for k, v in tree.items() if v["rel_diff"] is not None
                      and not v["rel_diff"] <= tols(k))

    return {
        "loss": {"program": p_loss, "reference": float(r_loss),
                 "rel_err": rel(p_loss, float(r_loss)),
                 "program_float32": f_loss,
                 "rel_err_float32": rel(f_loss, float(r_loss))},
        "router": {k: float(v) for k, v in p_rep.items()},
        "gradients": grads, "gradients_float32": f_grads,
        "gradient_rel_tol": {"leaf": GRADIENT_REL_TOL,
                             "routed_leaf": ROUTED_REL_TOL,
                             "float32": FLOAT32_REL_TOL},
        "gradient_leaves_beyond_tol": beyond(
            grads, lambda k: ROUTED_REL_TOL if ROUTED.search(k)
            else GRADIENT_REL_TOL) + beyond(
                f_grads, lambda k: FLOAT32_REL_TOL),
        "reference_loss": float(r_loss),
    }


def controls(cfg_file, cfg, params, tokens, want: float) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks.lib import spec
    from ray_tpu.models import llama

    def program(c, p):
        return float(jax.jit(lambda q: llama.loss_fn(c, q, tokens))(p))

    moe = dict(params["layers"]["moe"])
    moe["shared_down"] = jnp.zeros_like(moe["shared_down"])
    no_shared = dict(params, layers=dict(params["layers"], moe=moe))
    # float8_e4m3's 4 exponent and 3 mantissa bits; reduce_precision and
    # not a pair of casts, which the compiler may drop as excess precision
    eight_bit = jax.jit(lambda p: jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 4, 3), p))(params)
    ref_loss = spec.resolve(cfg_file["reference"] + ":loss")
    out = {
        "reference_8bit_weights": float(jax.jit(
            lambda p: ref_loss(cfg_file, p, tokens))(eight_bit)),
        "rotation_switched_on": program(
            dataclasses.replace(cfg, rope=True), params),
        "last_choice_left_out": program(dataclasses.replace(
            cfg, experts_per_token=cfg.experts_per_token - 1), params),
        "weights_unscaled": program(
            dataclasses.replace(cfg, routed_scale=1.0), params),
        "shared_expert_zeroed": program(cfg, no_shared),
    }
    return {k: {"loss": v, "rel_err": rel(v, want)} for k, v in out.items()}


def scope_of(instruction: str, op_name: str) -> str:
    """The ``ssm.*`` / ``moe.*`` scope of a compiled instruction, "" for
    none. XLA renames the grouped products (``ragged-dot...``) and drops
    their ``op_name``; only ``routed_mlp``'s expert products are such."""
    if instruction.startswith("ragged-dot"):
        return "moe.experts"
    found = SCOPE.findall(op_name)
    return found[-1] if found else ""


def traced_step(cfg_file, cfg, traffic, seed: int) -> dict:
    import jax

    from benchmarks.lib import spec, trace as tr
    from benchmarks.lib.train_cell import first_batch
    from ray_tpu.train.spmd import build_train_mesh, make_spmd_train_step

    batch, seq = traffic["batch_per_chip"], traffic["seq"]
    tokens = first_batch(cfg_file["vocab_size"], batch, seq, seed)
    init, step, sharding, _ = make_spmd_train_step(cfg, build_train_mesh(""))
    state = init(jax.random.PRNGKey(seed))
    toks = jax.device_put(tokens, sharding)
    compiled = step._fn.lower(state, toks).compile()
    scopes = {}
    for line in compiled.as_text().splitlines():
        m = re.match(r'\s*(?:ROOT )?%(\S+) = .*op_name="([^"]*)"', line)
        if m:
            scopes[m.group(1)] = m.group(2)
    for _ in range(2):
        state, loss, router = compiled(state, toks)
    jax.block_until_ready(loss)
    log_dir = tempfile.mkdtemp(prefix="nemotron_check_")
    jax.profiler.start_trace(log_dir)
    for _ in range(3):
        state, loss, router = compiled(state, toks)
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    flash = spec.load_layer_metric("flash_roofline")["args"]["kernels"]
    extracted = tr.extract(tr.newest_xplane(log_dir),
                           {k: v["pattern"] for k, v in flash.items()})
    devices = extracted["devices"]  # none on the CPU (--rehearsal)
    ops = devices[0]["ops"] if devices else []
    keyed = []
    for label, start, dur, kernel in ops:
        name = label.split(" ", 1)[0]
        scope = scope_of(name, scopes.get(name, ""))
        keyed.append([scope or ("flash" if kernel else "rest:" + label),
                      start, dur])
    groups, rest = {}, []
    for key, (seconds, calls) in tr.self_times(keyed).items():
        if key.startswith("rest:"):
            rest.append([key[5:], seconds, calls])
            key = "rest"
        groups[key] = groups.get(key, 0.0) + seconds
    total = sum(groups.values()) or float("nan")
    rest.sort(key=lambda r: -r[1])
    by_family = {"ssm": 0.0, "moe": 0.0}
    for key, seconds in groups.items():
        if key[:3] in by_family:
            by_family[key[:3]] += seconds
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]
    return {
        "steps_traced": 3, "device_seconds": total,
        "share_by_scope": {k: v / total for k, v in sorted(groups.items())},
        "share_by_family": {k: v / total for k, v in by_family.items()},
        "ms_a_step_by_scope": {k: 1e3 * v / 3 for k, v in sorted(groups.items())},
        "largest_of_the_rest": [
            {"op": op, "ms_a_step": 1e3 * s / 3, "calls": c,
             "op_name": scopes.get(op.split(" ", 1)[0], "")[-120:]}
            for op, s, c in rest[:12]],
        "router": {k: float(v) for k, v in router.items()},
        "loss_after_steps": float(loss), "peak_bytes_in_use": peak,
        "memory_analysis": str(compiled.memory_analysis()),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3100000031)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--skip", default="", help="comma list: gradients,"
                    "controls,step")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from ray_tpu.util import compile_cache

    compile_cache.configure(os.environ)
    import gc
    from functools import partial

    import jax

    from benchmarks.lib import spec, traffic as traffic_mod
    from benchmarks.lib.train_cell import first_batch
    from ray_tpu.models.llama import init_params

    bundle = spec.cell_bundle(CELL, rehearsal=args.rehearsal)
    cfg_file, traffic = bundle["config"], bundle["traffic"]
    if args.rehearsal:  # the rehearsal's two layers hold no attention: nine
        cfg_file["num_hidden_layers"] = 9
    cfg = spec.program_config(cfg_file)
    seed = traffic_mod.fold_seed(args.seed)
    dev = jax.devices()[0]
    out = {"seed": args.seed, "device": {"platform": dev.platform,
                                         "kind": dev.device_kind}}
    if dev.platform != "tpu" and not args.rehearsal:
        print("no TPU: nothing is measured on anything else", file=sys.stderr)
        return 3
    skip = set(args.skip.split(","))
    tokens = first_batch(cfg_file["vocab_size"], 1, traffic["seq"], seed)
    if not {"gradients", "controls"} <= skip:
        params = jax.jit(partial(init_params, cfg))(jax.random.PRNGKey(seed))
        if "gradients" not in skip:
            out.update(loss_and_gradients(cfg_file, cfg, params, tokens))
            gc.collect()
        if "controls" not in skip:
            want = out.get("reference_loss") or float(jax.jit(partial(
                spec.resolve(cfg_file["reference"] + ":loss"), cfg_file))(
                    params, tokens))
            out["controls"] = controls(cfg_file, cfg, params, tokens, want)
            out["train_loss_rel_tol"] = cfg_file["correct"]["train_loss_rel_tol"]
        del params
        gc.collect()
    if "step" not in skip:
        out["step"] = traced_step(cfg_file, cfg, traffic, seed)
    out_dir = os.path.join(ROOT, "chiprun_out", "nemotron_check")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"result_{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 1 if out.get("gradient_leaves_beyond_tol") else 0


if __name__ == "__main__":
    sys.exit(main())
