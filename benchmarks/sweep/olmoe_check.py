"""OLMoE at published widths, once, outside any measured window: what the
cell's own check (one loss against the reference) cannot show.

    chiprun --chips 1 --timeout 1500 -- python3 benchmarks/sweep/olmoe_check.py [--seed N]

One process holds the chip. It prints one JSON object and writes it to
``chiprun_out/olmoe_check/result_<seed>.json``:

1. ``loss`` / ``gradients``: on ONE sequence of the cell's length, the
   program (``ray_tpu.models.llama.loss_parts``, its compute type, its
   kernels) against the float32 reference: cross-entropy and each router
   loss apart, and for every parameter leaf the norm of the program's
   gradient, of the reference's, and of their difference over the
   reference's.
2. ``controls``: what the file's ``train_loss_rel_tol`` has to refuse, as
   the relative distance of the total from the reference's: the program
   with the last choice left out, with renormalised weights, with the
   busiest expert's product skipped, and the reference with its weights
   rounded to 8-bit floats (the nearest precision below the configuration's
   bfloat16).
3. ``step``: the trainer's step (``make_spmd_train_step``, the cell's batch)
   traced for three calls: device time by ``moe.*`` scope (an operation's
   scope is read from the compiled program's ``op_name`` metadata; the
   trace carries only instruction names), the flash kernels, the rest with
   its largest operations; the tiles of 512 rows the grouped products visit
   against the assignments (executed over routed expert FLOPs); the
   heaviest expert's load; peak device memory.
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

CELL = "train-olmoe-1chip"
TILE_ROWS = 512  # XLA's grouped-matmul kernel: ragged_dot_tiling="512,512,512"


def rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


def loss_and_gradients(cfg_file, cfg, params, tokens) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import spec
    from ray_tpu.models import llama

    ref = spec.resolve(cfg_file["reference"] + ":loss_parts")

    def leaf_norms(tree):
        return {jax.tree_util.keystr(p): float(jnp.linalg.norm(g.astype(jnp.float32)))
                for p, g in jax.tree_util.tree_flatten_with_path(tree)[0]}

    def reference(p):
        parts = ref(cfg_file, p, tokens)
        return parts["total"], parts

    (_, r_parts), r_grads = jax.jit(jax.value_and_grad(
        reference, has_aux=True))(params)
    (p_total, p_rep), p_grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_parts(cfg, p, tokens), has_aux=True))(params)
    diff = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))(
        p_grads, r_grads)
    rn, pn, dn = leaf_norms(r_grads), leaf_norms(p_grads), leaf_norms(diff)
    p_lb, p_z = float(p_rep["lb_loss"]), float(p_rep["z_loss"])
    p_ce = float(p_total) - cfg.lb_loss_coef * p_lb - cfg.z_loss_coef * p_z
    want = {k: float(v) for k, v in r_parts.items()}
    got = {"total": float(p_total), "cross_entropy": p_ce, "lb_loss": p_lb,
           "z_loss": p_z}
    return {
        "loss": {k: {"program": got[k], "reference": want[k],
                     "rel_err": rel(got[k], want[k])} for k in want},
        "router": {k: float(v) for k, v in p_rep.items()},
        "gradients": {k: {"program": pn[k], "reference": rn[k],
                          "rel_diff": dn[k] / rn[k] if rn[k] else None}
                      for k in rn},
        "reference_total": want["total"],
    }


def controls(cfg_file, cfg, params, tokens, want_total: float) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks.lib import spec
    from ray_tpu.models import llama

    def program(c, p):
        return float(jax.jit(lambda q: llama.loss_fn(c, q, tokens))(p))

    busy = int(jnp.argmax(router_counts(cfg, params, tokens)))
    layers = dict(params["layers"])
    layers["w_down"] = layers["w_down"].at[:, busy].set(0.0)
    # float8_e4m3's 4 exponent and 3 mantissa bits; reduce_precision and
    # not a pair of casts, which the compiler may drop as excess precision
    eight_bit = jax.jit(lambda p: jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, 4, 3), p))(params)
    ref_loss = spec.resolve(cfg_file["reference"] + ":loss")
    out = {
        "last_choice_left_out": program(dataclasses.replace(
            cfg, experts_per_token=cfg.experts_per_token - 1), params),
        "weights_renormalised": program(dataclasses.replace(
            cfg, norm_topk_prob=True), params),
        "one_expert_skipped": program(cfg, dict(params, layers=layers)),
        "reference_8bit_weights": float(jax.jit(
            lambda p: ref_loss(cfg_file, p, tokens))(eight_bit)),
    }
    return {k: {"total": v, "rel_err": rel(v, want_total)}
            for k, v in out.items()}


def scope_of(instruction: str, op_name: str) -> str:
    """The ``moe.*`` scope of a compiled instruction, "" for none. XLA
    renames the grouped products (``ragged-dot-none.N``, their tile
    bookkeeping ``ragged-dot-metadata``) and drops their ``op_name``; only
    ``routed_mlp``'s expert products are such."""
    if instruction.startswith("ragged-dot"):
        return "moe.experts"
    m = re.search(r"moe\.(route|dispatch|experts|combine)", op_name)
    return "moe." + m.group(1) if m else ""


def traced_step(cfg_file, cfg, traffic, seed: int) -> dict:
    import jax
    import numpy as np

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
    log_dir = tempfile.mkdtemp(prefix="olmoe_check_")
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
    by_key = tr.self_times(keyed)
    groups, rest = {}, []
    for key, (seconds, calls) in by_key.items():
        if key.startswith("rest:"):
            rest.append([key[5:], seconds, calls])
            key = "rest"
        groups[key] = groups.get(key, 0.0) + seconds
    total = sum(groups.values()) or float("nan")
    rest.sort(key=lambda r: -r[1])
    # the grouped products' tiles: a tile of rows that straddles a group
    # boundary is visited once a group
    counts = np.asarray(router_counts(cfg, state["params"], tokens))
    ends = np.cumsum(counts)
    starts = ends - counts
    visits = int(sum(-(-e // TILE_ROWS) - s // TILE_ROWS
                     for s, e in zip(starts, ends) if e > s))
    assignments = int(counts.sum())
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]
    return {
        "steps_traced": 3, "device_seconds": total,
        "share_by_scope": {k: v / total for k, v in sorted(groups.items())},
        "ms_a_step_by_scope": {k: 1e3 * v / 3 for k, v in sorted(groups.items())},
        "largest_of_the_rest": [
            {"op": op, "ms_a_step": 1e3 * s / 3, "calls": c,
             "op_name": scopes.get(op.split(" ", 1)[0], "")[-120:]}
            for op, s, c in rest[:12]],
        "tiles_visited": visits, "assignments": assignments,
        "executed_over_routed_expert_flops": visits * TILE_ROWS / assignments,
        "max_load_ratio_after_training_steps": float(router["max_load_ratio"]),
        "heaviest_expert_share": float(counts.max() / assignments),
        "loss_after_steps": float(loss), "peak_bytes_in_use": peak,
        "memory_analysis": str(compiled.memory_analysis()),
    }


def router_counts(cfg, params, tokens):
    """The assignments each expert of layer 0 gets for ``tokens``, through
    the program's own attention half."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops.layers import rms_norm

    @jax.jit
    def counts(params, tokens):
        inputs = tokens[:, :-1]
        B, T = inputs.shape
        x = params["embedding"].astype(cfg.dtype)[inputs]
        pos = jnp.arange(T, dtype=jnp.int32)[None, :].repeat(B, axis=0)
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps).astype(cfg.dtype)
        q, k, v = llama._qkv(cfg, lp, h, cfg.n_heads, cfg.n_kv_heads, pos)
        a = llama._attention(cfg, q, k, v, None).reshape(B, T, -1)
        x = x + (a @ lp["wo"].astype(cfg.dtype)).astype(x.dtype)
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps).astype(cfg.dtype)
        logits = jnp.dot(h.reshape(B * T, -1).astype(jnp.float32),
                         lp["router"], precision=jax.lax.Precision.HIGHEST)
        top = jax.lax.top_k(jax.nn.softmax(logits, -1),
                            cfg.experts_per_token)[1]
        return jnp.bincount(top.reshape(-1), length=cfg.num_experts)

    return counts(params, tokens)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000000017)
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
            want = out.get("reference_total") or float(jax.jit(partial(
                spec.resolve(cfg_file["reference"] + ":loss"), cfg_file))(
                    params, tokens))
            out["controls"] = controls(cfg_file, cfg, params, tokens, want)
            out["train_loss_rel_tol"] = cfg_file["correct"]["train_loss_rel_tol"]
        del params
        gc.collect()
    if "step" not in skip:
        out["step"] = traced_step(cfg_file, cfg, traffic, seed)
    out_dir = os.path.join(ROOT, "chiprun_out", "olmoe_check")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"result_{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
