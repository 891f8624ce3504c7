"""command-a-plus-05-2026's cut at published widths, once, outside any
measured window: what the cell's own check (four rows of logits after all
its layers) cannot show.

    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/sweep/commandaplus_check.py [--seeds N,N,..]

One process holds the chip. It prints one JSON object a seed and writes it to
``chiprun_out/commandaplus_check/result_<seed>.json``:

1. ``check``: the harness's comparison (``lib/serve_cell.py
   BenchLM._prepare``: prefill of 5,118 tokens, beyond the 4,096 window,
   three decodes across a page boundary, largest difference of a row of
   logits over the reference's largest) on this one engine's four rows,
   first against the configuration's reference (``sound``: has to come out
   under ``serve_logits_rel_tol``), then against the reference computed each
   wrong way of ``--wrong`` (default: all of ``WRONG``), and against a decode
   that reads a wrong slot: each has to come out over the limit, or the
   limit cannot refuse it.
2. ``parts``: ONE layer's halves on ``--rows`` positions, compute type
   against the float32 reference at 'highest': a window layer's attention,
   the full layer's, the routed sum, the shared experts; and the pair
   rotation as one product (``ops/layers.py rotate_pairs``) against the
   sliced and stacked one at the cell's longest prefill: bits, the time of a
   call and the compiler's temporaries.
3. ``time_*``: device time by scope (``par.*``, ``attn.window`` /
   ``attn.full``, ``moe.*``; an operation's scope is read from the compiled
   program's ``op_name`` metadata) over traced prefills at 5, 9 and 16 pages
   and decode calls at 5 and 16 pages with each program's memory account and
   its LARGEST TEMPORARIES (a decode program must hold no ``[T, 128, 128]``
   float32 copy of the keys), the median ``engine.decode_program`` span, and
   which path each kernel's rule took with its reason
   (``prefill_attend_paths``, ``expert_product_paths``, ``held_sum_paths``,
   ``decode_attend_forms``).

``--init wq=2,wo=24`` starts the seeded matrices at other scales than
``models/llama.py PARALLEL_INIT`` (how its values were found). This PR adds
no Pallas kernel: there is no kernel's roofline share to count.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.sweep.longcat_check import (  # noqa: E402 - the same helpers
    decode_program_ms, dist, scopes_of)
from benchmarks.sweep.smallthinker_check import through_pages  # noqa: E402

CELL = "serve-commandaplus-prefill-open"
SCOPE = re.compile(r"(par\.(?:norm|qkv|out)|attn\.(?:window|full)"
                   r"|moe\.(?:route|dispatch|experts|combine|shared))")


def eight_bit(exponent, mantissa, experts_alone=False):
    """``_mm`` with every matrix the reference multiplies (``experts_alone``:
    the routed experts' three, whose stacked leaves have four axes, and no
    other) rounded to an 8-bit float where it is cut out; reduce_precision
    and not a pair of casts, which the compiler may drop as excess
    precision."""
    import jax
    import jax.numpy as jnp

    def rounded(x, w, at=()):
        w, _ = jax.lax.optimization_barrier((w, x))
        cut = w[at].astype(jnp.float32)
        if w.ndim == 4 or not experts_alone:
            cut = jax.lax.reduce_precision(cut, exponent, mantissa)
        return x @ cut
    return rounded


def _half_split(x, theta):
    """Pairs ``(i, i + D/2)`` where the model turns ``(2i, 2i + 1)``."""
    import jax.numpy as jnp

    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rms_for_layer_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _sequential(ref):
    """The block as a SEQUENTIAL one: the MLP fed a norm of ``x + A``."""
    def layer(cfg, x, p, l, first=None):
        eps = cfg["layer_norm_eps"]
        h = x + ref.attention(cfg, ref.layer_norm(x, p["norm"][l], eps), p, l)
        m = ref.layer_norm(h, p["norm"][l], eps)
        return (h + ref.experts(cfg, m, ref.route(cfg, m, p, l), p, l, first)
                + ref.shared(cfg, m, p, l))
    return layer


def _tiled_groups(ref):
    """Query head ``h`` reading KV head ``h mod 8`` where the model's reads
    ``h // 16``: the reference's attention on ONE layer's matrices whose
    query heads are regrouped, its output's rows put back."""
    attention = ref.attention

    def wrong(cfg, a, p, l):
        import jax.numpy as jnp

        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd, d = cfg["head_dim"], a.shape[-1]
        # head g * rep + r of the regrouped weights is head r * nkv + g
        wq = jnp.swapaxes(p["wq"][l].reshape(d, nq // nkv, nkv, hd), 1, 2)
        wo = jnp.swapaxes(p["wo"][l].reshape(nq // nkv, nkv, hd, d), 0, 1)
        one = {"wq": wq.reshape(1, d, -1), "wo": wo.reshape(1, -1, d),
               "wk": p["wk"][l][None], "wv": p["wv"][l][None]}
        return attention(dict(cfg, layer_types=[cfg["layer_types"][l]]), a,
                         one, 0)
    return wrong


def _rotated_full(ref):
    """The full layers rotated as the window layers are (and still full)."""
    attention, band = ref.attention, ref.band

    def wrong(cfg, a, p, l):
        if cfg["layer_types"][l] == "sliding_attention":
            return attention(cfg, a, p, l)
        types = list(cfg["layer_types"])
        types[l] = "sliding_attention"
        ref.band = lambda cfg, T, windowed: band(cfg, T, False)
        try:
            return attention(dict(cfg, layer_types=types), a, p, l)
        finally:
            ref.band = band
    return wrong


class made:
    """A replacement that is made FROM the module (it wraps what it has)."""

    def __init__(self, make):
        self.make = make


# the reference computed another way: ``(the file's keys changed, the
# module's functions replaced)``
WRONG = {
    "matrices_8bit_e5m2": ({}, {"_mm": eight_bit(5, 2)}),
    "matrices_8bit_e4m3": ({}, {"_mm": eight_bit(4, 3)}),
    "experts_alone_8bit_e5m2": ({}, {"_mm": eight_bit(5, 2, True)}),
    "sequential_for_parallel": ({}, {"layer": made(_sequential)}),
    "no_mean_subtraction": ({}, {"layer_norm": _rms_for_layer_norm}),
    "half_split_rotation": ({}, {"_rope": _half_split}),
    "a_rotated_full_layer": ({}, {"attention": made(_rotated_full)}),
    "an_unrotated_window_layer": ({}, {"_rope": lambda x, theta: x}),
    "a_window_of_3072": ({"sliding_window": 3072}, {}),
    "a_window_of_5120": ({"sliding_window": 5120}, {}),
    "a_window_of_4097": ({"sliding_window": 4097}, {}),
    "shared_experts_summed": (
        {"shared_expert_combination_strategy": "sum"}, {}),
    "softmax_for_sigmoid": ({"expert_selection_fn": "softmax"}, {}),
    "renormalisation_left_out": ({"norm_topk_prob": False}, {}),
    "head_h_reads_kv_head_h_mod_8": ({}, {"attention": made(_tiled_groups)}),
}


@contextlib.contextmanager
def wrong_reference(ref, name):
    """The reference module ``ref`` wrong the way ``name`` says, for the time
    of the block."""
    attrs = WRONG[name][1]
    was = {attr: getattr(ref, attr) for attr in attrs}
    for attr, fn in attrs.items():
        setattr(ref, attr, fn.make(ref) if isinstance(fn, made) else fn)
    try:
        yield
    finally:
        for attr, fn in was.items():
            setattr(ref, attr, fn)


def harness_check(file, traffic, engine, seed: int, names) -> dict:
    """The harness's four rows of this engine once, then a reference a
    name."""
    import importlib
    from functools import partial

    import jax
    import numpy as np

    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    ref = importlib.import_module(file["reference"])
    ps = engine.page_size
    n = check_prompt_len(shapes_of(traffic, ps), ps)
    toks = np.random.RandomState(seed).randint(
        0, file["vocab_size"], size=n + 3).astype(np.int32)
    pages = engine.pool.alloc(-(-(n + 3) // ps))
    got = through_pages(engine, toks, n, pages)
    wrong_slot = through_pages(engine, toks, n, pages, wrong_slot=True)
    engine.pool.release(pages)
    tol = file["correct"]["serve_logits_rel_tol"]

    def rows(want):
        return [dist(g, w) for g, w in zip(got, want)]

    def reference(name):
        keys = WRONG[name][0] if name != "sound" else {}
        with (wrong_reference(ref, name) if name != "sound"
              else contextlib.nullcontext()):  # in force while jit traces
            return np.asarray(jax.jit(partial(
                ref.logits_one, dict(file, **keys)))(
                    engine.params, toks))[n - 1:]

    want = reference("sound")
    out = {"serve_logits_rel_tol": tol, "prompt_tokens": n,
           "max_abs_logit": float(np.max(np.abs(want))),
           "sound": {"rel_err": rows(want),
                     "correct": bool(max(rows(want)) <= tol)},
           "decode_that_reads_a_wrong_slot": {
               "rel_err": [dist(g, w) for g, w in zip(wrong_slot, want)]}}
    out["decode_that_reads_a_wrong_slot"]["correct"] = bool(
        max(out["decode_that_reads_a_wrong_slot"]["rel_err"]) <= tol)
    for name in names:
        try:
            err = rows(reference(name))
            out[name] = {"rel_err": err, "correct": bool(max(err) <= tol)}
        except Exception as e:  # noqa: BLE001 - say which, go on
            out[name] = {"error": f"{type(e).__name__}: {e}"[:400]}
    return out


def _timed(fn, *args, calls: int = 5):
    """``({ms_a_call, temp_bytes}, the last result)`` of ``fn(*args)``."""
    import time

    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        y = compiled(*args)
    jax.block_until_ready(y)
    m = compiled.memory_analysis()
    return {"ms_a_call": 1e3 * (time.perf_counter() - t0) / calls,
            "temp_bytes": m.temp_size_in_bytes}, y


def parts(file, cfg, params, seed: int, rows: int, positions: int) -> dict:
    import importlib
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.ops.layers import rotary_embedding, rotate_pairs
    from ray_tpu.ops.moe import routed_mlp

    ref = importlib.import_module(file["reference"])
    block = params["layers"]["parallel"]
    cd, f32 = cfg.dtype, jnp.float32
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(k1, (rows, cfg.dim), f32).astype(cd)
    a32 = a.astype(f32)
    out = {"rows": rows}

    def at_highest(fn, *args):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)(*args)

    def attention(l):
        kind = cfg.kinds[l]

        def program(a, p):
            q, k, v = llama._qkv(
                cfg, {w: p[w][l] for w in ("wq", "wk", "wv")}, a[None],
                cfg.n_heads, cfg.n_kv_heads, llama.positions_of(1, rows),
                rope=kind == "R")
            o = llama.attend_parallel_tiles(cfg, kind, q, k, v)
            return o.reshape(rows, -1) @ p["wo"][l]
        return dist(jax.jit(program)(a, block), at_highest(
            lambda a, p: ref.attention(file, a, p, l), a32, block))

    kinds = cfg.kinds
    if "R" in kinds:
        out["attention_window"] = attention(kinds.index("R"))
    if "P" in kinds:
        out["attention_full"] = attention(kinds.index("P"))
    wide = cfg.router_experts or cfg.num_experts
    got, stats = jax.jit(lambda a, p: routed_mlp(
        a, p["router"][0], p["w_gate"], p["w_up"], p["w_down"],
        top_k=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
        scoring=cfg.router_scoring, held=(
            (cfg.first_expert, cfg.num_experts)
            if wide != cfg.num_experts else None), layer=0,
        router_input=a.astype(f32)))(a, block)
    out["routed_sum"] = dist(got, at_highest(lambda a, p: ref.experts(
        file, a, ref.route(file, a, p, 0), p, 0), a32, block))
    out["held_share"] = float(stats["held_share"]) \
        if "held_share" in stats else 1.0

    def shared(a, p):
        g = jax.nn.silu((a @ p["shared_gate"][0]).astype(f32)) \
            * (a @ p["shared_up"][0])
        return (g.astype(cd) @ p["shared_down"][0]).astype(f32) \
            / cfg.shared_experts
    out["shared_experts"] = dist(jax.jit(shared)(a, block), at_highest(
        lambda a, p: ref.shared(file, a, p, 0), a32, block))
    # the pair rotation two ways, at the longest prefill's queries
    x = jax.random.normal(k2, (1, positions, cfg.n_heads, cfg.head_dim),
                          f32).astype(cd)
    at = llama.positions_of(1, positions)
    one, y_one = _timed(lambda x: rotate_pairs(x, at, cfg.rope_theta), x)
    two, y_two = _timed(lambda x: rotary_embedding(
        x, x, at, cfg.rope_theta, interleaved=True)[0], x)
    out["rotation"] = {
        "positions": positions, "as_one_product": one,
        "sliced_and_stacked": two,
        "largest_difference": float(np.max(np.abs(
            np.asarray(y_one, np.float32) - np.asarray(y_two, np.float32))))}
    return out


def largest_temporaries(compiled, n: int = 8) -> list:
    """The ``n`` largest arrays a compiled program's instructions write
    (parameters and fused computations' insides left out): where a ``[T,
    128, 128]`` float32 copy of the keys would show."""
    width = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1, "f16": 2,
             "s8": 1, "u8": 1}
    seen = {}
    computations = re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()",
                            compiled.as_text())
    for comp in computations:
        if "fused_computation" in comp.split("\n", 1)[0]:
            continue
        for m in re.finditer(
                r"^\s+(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]+)\]\S* "
                r"([\w\-]+)\(", comp, re.M):
            name, dtype, dims, op = m.groups()
            if op in ("parameter", "get-tuple-element", "bitcast", "tuple") \
                    or dtype not in width \
                    or "dynamic-update-slice" in name:  # a store, in place
                continue
            size = math.prod(int(d) for d in dims.split(",")) * width[dtype]
            seen[name] = (size, f"{dtype}[{dims}]", op)
    return [{"op": name, "shape": shape, "bytes": size, "kind": op}
            for name, (size, shape, op) in sorted(
                seen.items(), key=lambda kv: -kv[1][0])[:n]]


def by_scope(engine, kind: str, n_pages: int, calls: int = 3) -> dict:
    """Device time of ``calls`` traced calls of one of the engine's programs
    at ``n_pages``, by named scope."""
    import jax
    import numpy as np

    from benchmarks.lib import trace as tr

    ps = engine.page_size
    table = engine.pool.alloc(n_pages)
    pages = np.asarray(table, np.int32)
    reach = min(n_pages, engine.window_pages)
    slots = (engine._slots_for(table[-reach:]),) if engine.n_slots else ()
    if kind == "prefill":
        fn = engine._prefill_fn
        args = (np.ones((1, n_pages * ps), np.int32), pages,
                np.asarray(n_pages * ps - 1, np.int32), *slots)
    else:
        fn = engine._decode_fn
        args = (np.asarray([1], np.int32),
                np.asarray(n_pages * ps - 1, np.int32), pages, *slots,
                *((np.asarray(n_pages - reach, np.int32),) if slots else ()))
    jit = getattr(fn, "_fn", fn)
    compiled = jit.lower(engine.params, *engine.stores, *args).compile()
    scopes = scopes_of(compiled)

    def call():
        out = compiled(engine.params, *engine.stores, *args)
        engine.stores = tuple(out[:len(engine.stores)])
        return out

    jax.block_until_ready(call())
    log_dir = tempfile.mkdtemp(prefix="commandaplus_check_")
    jax.profiler.start_trace(log_dir)
    for _ in range(calls):
        jax.block_until_ready(call())
    jax.profiler.stop_trace()
    engine.pool.release(table)
    devices = tr.extract(tr.newest_xplane(log_dir), {})["devices"]
    ops = devices[0]["ops"] if devices else []  # none on the CPU
    keyed = []
    for label, start, dur, _ in ops:
        name = label.split(" ", 1)[0]
        found = SCOPE.findall(scopes.get(name, ""))
        scope = ("moe.experts" if name.startswith("ragged-dot")
                 or "moe_ffn" in label
                 else "moe.combine" if "held_sum" in label
                 else found[-1] if found else "rest:" + label)
        keyed.append([scope, start, dur])
    groups, rest = {}, []
    for key, (seconds, n) in tr.self_times(keyed).items():
        if key.startswith("rest:"):
            rest.append([key[5:], seconds, n])
            key = "rest"
        groups[key] = groups.get(key, 0.0) + seconds
    total = sum(groups.values()) or float("nan")
    rest.sort(key=lambda r: -r[1])
    kinds = engine.cfg.kinds
    per_layer = {
        scope + "_ms_a_layer": 1e3 * groups[scope] / calls / kinds.count(c)
        for scope, c in (("attn.full", "P"), ("attn.window", "R"))
        if scope in groups and c in kinds}
    return {"program": kind, "pages": n_pages, "calls": calls,
            "device_ms_a_call": 1e3 * total / calls, **per_layer,
            "ms_a_call_by_scope": {k: 1e3 * v / calls
                                   for k, v in sorted(groups.items())},
            "share_by_scope": {k: v / total
                               for k, v in sorted(groups.items())},
            "largest_of_the_rest": [
                {"op": op, "ms_a_call": 1e3 * s / calls, "calls": n,
                 "op_name": scopes.get(op.split(" ", 1)[0], "")[-100:]}
                for op, s, n in rest[:8]],
            "largest_temporaries": largest_temporaries(compiled),
            "memory_analysis": str(compiled.memory_analysis())}


def one_seed(args, bundle, file, cfg, seed_arg: int, skip: set) -> dict:
    import jax

    from benchmarks.lib import spec, traffic as traffic_mod
    from ray_tpu.models import llama

    seed = traffic_mod.fold_seed(seed_arg)
    dev = jax.devices()[0]
    out = {"seed": seed_arg, "parallel_init": dict(llama.PARALLEL_INIT),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    out_dir = os.path.join(ROOT, "chiprun_out", "commandaplus_check")
    os.makedirs(out_dir, exist_ok=True)

    def keep(part, make):  # a part that fails loses no other
        try:
            out[part] = make()
        except Exception as e:  # noqa: BLE001 - say which, go on
            out[part] = {"error": f"{type(e).__name__}: {e}"[:600]}
        with open(os.path.join(out_dir, f"result_{seed_arg}.json"), "w") as f:
            json.dump(out, f, indent=1)

    dep = file["deployment"]
    engine = spec.resolve(file["program"]["engine_class"])(
        cfg, n_pages=dep["n_pages"] if not args.rehearsal else 64,
        page_size=dep["page_size"], seed=seed)
    out["n_slots"] = engine.n_slots
    if "check" not in skip:
        keep("check", lambda: harness_check(
            file, bundle["traffic"], engine, seed,
            [] if "faults" in skip else args.wrong))
    if "parts" not in skip:
        keep("parts", lambda: parts(
            file, cfg, engine.params, seed,
            args.rows if not args.rehearsal else 96,
            16384 if not args.rehearsal else 64))
    if "time" not in skip:
        few, mid, most = (5, 9, 16) if not args.rehearsal else (3, 4, 5)
        keep("decode_program_ms", lambda: {
            str(n): decode_program_ms(engine, n) for n in (few, most)})
        for kind, n in (("decode", few), ("decode", most), ("prefill", few),
                        ("prefill", mid), ("prefill", most)):
            keep(f"time_{kind}_{n}", lambda: by_scope(engine, kind, n))
    keep("prefill_attend_paths", llama.prefill_attend_paths)
    keep("expert_product_paths", llama.expert_product_paths)
    keep("held_sum_paths", llama.held_sum_paths)
    keep("decode_attend_forms", llama.decode_attend_forms)
    keep("peak_bytes_in_use", lambda: [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()])
    del engine  # its pool's release hook is a cycle: collect it now
    gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="5400000054",
                    help="comma list: one engine and one result a seed")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--rows", type=int, default=5120)
    ap.add_argument("--skip", default="", help="comma list: check,faults,"
                    "parts,time")
    ap.add_argument("--wrong", default=",".join(WRONG),
                    help="comma list: the wrong ways the check reads")
    ap.add_argument("--init", default="", help="name=scale,..: other "
                    "starting scales than models/llama.py PARALLEL_INIT")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from ray_tpu.util import compile_cache

    compile_cache.configure(os.environ)
    import jax

    from benchmarks.lib import spec
    from ray_tpu.models import llama
    from ray_tpu.util import flight_recorder as fr

    fr.configure(enabled=True)
    for item in filter(None, args.init.split(",")):
        name, scale = item.split("=")
        llama.PARALLEL_INIT[name] = float(scale)
    args.wrong = [w for w in args.wrong.split(",") if w]
    if set(args.wrong) - set(WRONG):
        ap.error(f"--wrong: of {sorted(WRONG)}")
    bundle = spec.cell_bundle(CELL, rehearsal=args.rehearsal)
    file = bundle["config"]
    if args.rehearsal:  # a check that crosses a page
        bundle["traffic"] = dict(bundle["traffic"], prompt_tokens={
            "dist": "log_uniform", "min": 24, "max": 40})
    cfg = spec.program_config(file)
    if jax.devices()[0].platform != "tpu" and not args.rehearsal:
        print("no TPU: nothing is measured on anything else", file=sys.stderr)
        return 3
    skip = set(args.skip.split(","))
    failed = False
    for seed in (int(s) for s in args.seeds.split(",")):
        out = one_seed(args, bundle, file, cfg, seed, skip)
        print(json.dumps(out), flush=True)
        failed |= any(isinstance(v, dict) and "error" in v
                      for v in out.values())
    return int(failed)


if __name__ == "__main__":
    sys.exit(main())
