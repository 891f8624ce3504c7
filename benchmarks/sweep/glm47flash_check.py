"""GLM-4.7-Flash's share at published widths, once, outside any measured
window: what the cell's own check (one loss against the reference) cannot
show.

    chiprun --chips 1 --timeout 1800 -- python3 benchmarks/sweep/glm47flash_check.py [--seed N]

One process holds the chip. It prints one JSON object and writes it to
``chiprun_out/glm47flash_check/result_<seed>.json``:

1. ``loss`` / ``gradients``: on ONE sequence of the cell's length, the
   program (``ray_tpu.models.llama.loss_parts``, its compute type, the flash
   kernels forward AND backward at head width 256) against the float32
   reference: the loss with the module's, the two losses apart, the router's
   scalars, and for every parameter leaf the norm of the program's gradient,
   of the reference's, and of their difference over the reference's. Held to
   ``GRADIENT_REL_TOL``: the exit code is 1 if a leaf is beyond it.
2. ``parts``: one leading block and one routed block, each against the
   reference's on the same seeded stream (the module's loss apart is under
   ``loss``).
3. ``controls``: what the file's ``train_loss_rel_tol`` has to refuse, as
   the relative distance of the loss from the reference's: the reference
   with its weights rounded to 8-bit floats (the nearest precision below
   the configuration's bfloat16), and the faults a loss near ln(vocab) can
   or cannot see at random weights, each with its reading: both
   ``sqrt(dim / rank)`` factors switched on, weights not times 1.8, the last
   choice left out, the shared expert zeroed, lambda 0, the halves of
   ``W_eh``'s input swapped.
4. ``step``: the trainer's step (``make_spmd_train_step``, the cell's
   batch) traced for three calls: device time by ``mla.*`` / ``moe.*`` /
   ``ffn.dense`` / ``mtp.*`` scope (an operation's scope is read from the
   compiled program's ``op_name`` metadata, the INNERMOST of them; what runs
   under ``mtp.*`` at all is summed apart as the module's share), the flash
   kernels, the rest with its largest operations; the step's scalars; the
   compiler's account of the step's memory.
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

CELL = "train-glm47flash-1chip"
# A leaf's distance: the norm of (program's gradient - reference's) over the
# reference's norm, on one sequence of 8,192 at published widths. Three
# readings set the three limits (my chip run, PR 48, seed 4800000048;
# glm47flash_check.md has every leaf): the program in float32 with whole
# float32 products (the reference's own mathematics through the flash kernels
# at width 256, the held rows and the chunked losses) reads 0 to 3e-5 on
# every leaf; the program as the cell runs it (bfloat16 products) reads
# 2.5-9.4% on every leaf but the routed ones, the rounding of six blocks'
# activations that a random-weight gradient does not average away; the
# routers read 26% and 33% and the held experts 19-23%, because a rounded
# router input moves the fourth choice of some tokens, each of which then
# runs another expert or none held here, and a held expert sees some 900
# rows of one sequence. A wrong term reads near 1 on its leaf. Each limit
# is about 1.5 times its largest reading (the float32 one far more: it
# refuses a wrong term and no rounding).
GRADIENT_REL_TOL = 0.15
ROUTED_REL_TOL = 0.5
FLOAT32_REL_TOL = 1e-3
ROUTED = re.compile(r"\['latent'\]\['(router|w_up|w_down|w_gate)'\]")
SCOPE = re.compile(r"(mla\.(?:project|attend|out)|ffn\.dense"
                   r"|moe\.(?:route|dispatch|experts|combine|shared)"
                   r"|mtp\.(?:merge|block|head))")


def rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


def loss_and_gradients(cfg_file, cfg, params, tokens) -> dict:
    import dataclasses
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    ref = importlib.import_module(cfg_file["reference"])

    def leaf_norms(tree):
        return {jax.tree_util.keystr(p): float(jnp.linalg.norm(g.astype(jnp.float32)))
                for p, g in jax.tree_util.tree_flatten_with_path(tree)[0]}

    r_loss, r_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(cfg_file, p, tokens)))(params)
    r_main, r_ahead = jax.jit(lambda p: ref.losses(cfg_file, p, tokens))(params)
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
                 "rel_err_float32": rel(f_loss, float(r_loss)),
                 "main": {"program": float(p_rep["main_loss"]),
                          "reference": float(r_main)},
                 "mtp": {"program": float(p_rep["mtp_loss"]),
                         "reference": float(r_ahead)}},
        "scalars": {k: float(v) for k, v in p_rep.items()},
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


def parts(cfg_file, cfg, params, seq: int, seed: int) -> dict:
    """A leading and a routed block on one seeded stream, the program's
    (its compute type, the flash kernel) against the reference's: the norm
    of the difference of what each ADDS to the stream over the norm of what
    the reference adds."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    ref = importlib.import_module(cfg_file["reference"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, seq, cfg.dim),
                          jnp.float32)
    out = {}
    for kind, name in (("G", "dense"), ("L", "routed")):
        p = jax.tree.map(lambda a: a[0], params["layers"][ref.STACK[name]])
        got = jax.jit(lambda x, p, kind=kind: llama.latent_block(
            cfg, kind, llama.flash_causal, x.astype(cfg.dtype), p)[0])(x, p)

        def want_of(x, p, name=name):
            with jax.default_matmul_precision("highest"):
                return ref.block(cfg_file, x, p, name)

        want = jax.jit(want_of)(x[0], p)
        added = want - x[0]
        out[name + "_block"] = {
            "rel_diff": float(jnp.linalg.norm(
                got[0].astype(jnp.float32) - want) / jnp.linalg.norm(added)),
            "added_over_stream": float(jnp.linalg.norm(added)
                                       / jnp.linalg.norm(x[0]))}
    return out


def controls(cfg_file, cfg, params, tokens, want: float) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks.lib import spec
    from ray_tpu.models import llama

    def program(c, p):
        return float(jax.jit(lambda q: llama.loss_fn(c, q, tokens))(p))

    def with_leaf(tree, path, fn):
        if len(path) == 1:
            return dict(tree, **{path[0]: fn(tree[path[0]])})
        return dict(tree, **{path[0]: with_leaf(tree[path[0]], path[1:], fn)})

    no_shared = params
    for path in (("layers", "latent", "shared_down"),
                 ("mtp", "layers", "latent", "shared_down")):
        no_shared = with_leaf(no_shared, path, jnp.zeros_like)
    d = cfg.dim
    swapped = with_leaf(params, ("mtp", "eh_proj"),
                        lambda w: jnp.concatenate([w[d:], w[:d]]))
    # float8_e4m3's 4 exponent and 3 mantissa bits; reduce_precision and
    # not a pair of casts, which the compiler may drop as excess precision
    eight_bit = jax.jit(lambda p: jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 4, 3), p))(params)
    ref_loss = spec.resolve(cfg_file["reference"] + ":loss")
    out = {
        "reference_8bit_weights": float(jax.jit(
            lambda p: ref_loss(cfg_file, p, tokens))(eight_bit)),
        "mla_scales_switched_on": program(dataclasses.replace(
            cfg, mla_scale_q_lora=True, mla_scale_kv_lora=True), params),
        "weights_unscaled": program(
            dataclasses.replace(cfg, routed_scale=1.0), params),
        "last_choice_left_out": program(dataclasses.replace(
            cfg, experts_per_token=cfg.experts_per_token - 1), params),
        "shared_expert_zeroed": program(cfg, no_shared),
        "lambda_0": program(
            dataclasses.replace(cfg, mtp_loss_weight=0.0), params),
        "eh_proj_halves_swapped": program(cfg, swapped),
    }
    return {k: {"loss": v, "rel_err": rel(v, want)} for k, v in out.items()}


def scope_of(instruction: str, op_name: str) -> str:
    """The innermost ``mla.*`` / ``moe.*`` / ``ffn.dense`` / ``mtp.*`` scope
    of a compiled instruction, "" for none. XLA renames the grouped products
    (``ragged-dot...``) and drops their ``op_name``; only ``routed_mlp``'s
    expert products are such."""
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
    log_dir = tempfile.mkdtemp(prefix="glm47flash_check_")
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
    keyed, module = [], []
    for label, start, dur, kernel in ops:
        name = label.split(" ", 1)[0]
        scope = scope_of(name, scopes.get(name, ""))
        keyed.append([scope or ("flash" if kernel else "rest:" + label),
                      start, dur])
        if "mtp." in scopes.get(name, ""):
            module.append(["mtp", start, dur])
    groups, rest = {}, []
    for key, (seconds, calls) in tr.self_times(keyed).items():
        if key.startswith("rest:"):
            rest.append([key[5:], seconds, calls])
            key = "rest"
        groups[key] = groups.get(key, 0.0) + seconds
    total = sum(groups.values()) or float("nan")
    rest.sort(key=lambda r: -r[1])
    by_family = {"mla": 0.0, "moe": 0.0, "ffn": 0.0, "mtp": 0.0}
    for key, seconds in groups.items():
        if key[:3] in by_family:
            by_family[key[:3]] += seconds
    in_module = tr.self_times(module).get("mtp", (0.0, 0))[0]
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]
    return {
        "steps_traced": 3, "device_seconds": total,
        "share_by_scope": {k: v / total for k, v in sorted(groups.items())},
        "share_by_family": {k: v / total for k, v in by_family.items()},
        # everything traced under mtp.* whatever its innermost scope, the
        # module's flash calls among it
        "module_share": in_module / total,
        "ms_a_step_by_scope": {k: 1e3 * v / 3 for k, v in sorted(groups.items())},
        "largest_of_the_rest": [
            {"op": op, "ms_a_step": 1e3 * s / 3, "calls": c,
             "op_name": scopes.get(op.split(" ", 1)[0], "")[-120:]}
            for op, s, c in rest[:12]],
        "scalars": {k: float(v) for k, v in router.items()},
        "loss_after_steps": float(loss), "peak_bytes_in_use": peak,
        "memory_analysis": str(compiled.memory_analysis()),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=4800000048)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--skip", default="", help="comma list: gradients,"
                    "parts,controls,step")
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
    if not {"gradients", "parts", "controls"} <= skip:
        params = jax.jit(partial(init_params, cfg))(jax.random.PRNGKey(seed))
        if "gradients" not in skip:
            out.update(loss_and_gradients(cfg_file, cfg, params, tokens))
            gc.collect()
        if "parts" not in skip:
            out["parts"] = parts(cfg_file, cfg, params, traffic["seq"], seed)
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
    out_dir = os.path.join(ROOT, "chiprun_out", "glm47flash_check")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"result_{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 1 if out.get("gradient_leaves_beyond_tol") else 0


if __name__ == "__main__":
    sys.exit(main())
