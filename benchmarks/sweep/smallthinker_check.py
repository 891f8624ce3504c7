"""SmallThinker-21BA3B-Instruct's cut at published widths, once, outside any
measured window: what the cell's own check (four rows of logits after all
its layers) cannot show.

    chiprun --chips 1 --timeout 2400 -- python3 benchmarks/sweep/smallthinker_check.py [--seed N]

One process holds the chip. It prints one JSON object and writes it to
``chiprun_out/smallthinker_check/result_<seed>.json``:

1. ``grouped``: the experts' grouped product (``jax.lax.ragged_dot``) of a
   prefill's and of a decode call's rows by one layer's 64 experts, read
   where they lie in the kind's stack at the published width 768, against
   the layer cut out and filled up to 1024 (``ops/moe._expert_ffn``'s rule
   for a width that is no multiple of 512): which the engine should do.
2. ``parts``: a window layer, a full layer and the routed half (its router
   fed the attention's input), each ALONE on 5,120 rows, against the
   reference: largest difference over the reference's largest value. Beside
   them what the comparison must refuse, as distances from the same
   reference: the router fed the MLP's input, SwiGLU for ReGLU, a rotated
   full layer, an unrotated window layer, a window of 3,072 and of 5,120.
3. ``check``: the harness's own comparison (prefill of 5,118 tokens, three
   decodes across a page boundary; ``lib/serve_cell.py _prepare``), and
   what ``serve_logits_rel_tol`` has to refuse as the same distance: the
   reference with its weights rounded to 8-bit floats (the nearest
   precision below the configuration's bfloat16, in both 8-bit formats;
   and the experts' matrices ALONE, everything else as it is), a decode
   that reads a wrong slot, and the reference computed each wrong way of
   (2).
4. ``decode_program_ms`` / ``time_*``: device time by scope (an operation's
   scope is read from the compiled program's ``op_name`` metadata) over
   three traced prefills at 5, 9 and 16 pages and three decode calls at 5
   and 16 pages, and the median ``engine.decode_program`` span at 5 and 16.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.sweep.longcat_check import (  # noqa: E402 - the same helpers
    decode_program_ms, dist, scopes_of)

CELL = "serve-smallthinker-prefill-open"
SCOPE = re.compile(r"(attn\.(?:window|full)"
                   r"|moe\.(?:route|dispatch|experts|combine))")
# the reference computed another way: the file's keys changed
WRONG = {
    "full_layer_rotated": lambda f: dict(f, rope_layout=[1] * 52),
    "window_layer_not_rotated": lambda f: dict(f, rope_layout=[0] * 52),
    "window_3072": lambda f: dict(f, sliding_window_size=3072),
    "window_5120": lambda f: dict(f, sliding_window_size=5120),
}


def grouped(params, seed: int, rows_list) -> dict:
    """Milliseconds of one grouped product a layer, over every layer in a
    scan that carries the layer's number, median of five calls."""
    import jax
    import jax.numpy as jnp

    w = params["layers"]["block"]["w_up"]  # [L, 64, d, f]
    L, E, d, f = w.shape
    out = {"stack": list(w.shape)}

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            took.append(time.perf_counter() - t0)
        return 1e3 * statistics.median(took) / L

    for rows in rows_list:
        x = jax.random.normal(jax.random.PRNGKey(seed), (rows, d),
                              jnp.float32).astype(w.dtype)
        counts = jnp.full((E,), rows // E, jnp.int32).at[0].add(rows % E)

        @jax.jit
        def in_place(x, w):
            flat = w.reshape(L * E, d, f)

            def body(acc, i):
                sizes = jax.lax.dynamic_update_slice(
                    jnp.zeros(L * E, jnp.int32), counts, (i * E,))
                return acc + jax.lax.ragged_dot(
                    x, flat, sizes, preferred_element_type=jnp.float32
                ).sum(), None

            return jax.lax.scan(body, 0.0, jnp.arange(L, dtype=jnp.int32))[0]

        @jax.jit
        def cut_and_filled(x, w):
            def body(acc, i):
                mine = jnp.pad(w[i], ((0, 0), (0, 0), (0, -f % 512)))
                return acc + jax.lax.ragged_dot(
                    x, mine, counts, preferred_element_type=jnp.float32
                ).sum(), None

            return jax.lax.scan(body, 0.0, jnp.arange(L, dtype=jnp.int32))[0]

        @jax.jit
        def cut_only(x, w):
            def body(acc, i):
                return acc + jax.lax.ragged_dot(
                    x, w[i], counts, preferred_element_type=jnp.float32
                ).sum(), None

            return jax.lax.scan(body, 0.0, jnp.arange(L, dtype=jnp.int32))[0]

        out[str(rows)] = {
            "in_place_768_ms_a_layer": timed(in_place, x, w),
            "cut_out_768_ms_a_layer": timed(cut_only, x, w),
            "cut_out_filled_1024_ms_a_layer": timed(cut_and_filled, x, w)}
    return out


def parts(file, cfg, params, seed: int, rows: int) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops.moe import routed_mlp

    ref = importlib.import_module(file["reference"])
    block = params["layers"]["block"]
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    # in the compute type's values: both sides read the same numbers
    x, m = (jax.random.normal(key, (1, rows, cfg.dim), jnp.float32).astype(
        cfg.dtype).astype(jnp.float32) for key in k)
    li = min(2, cfg.n_layers - 1)  # the routed half's layer

    def reference(fn, *args):
        def at_highest(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)

        return jax.jit(at_highest)(*args)

    out = {"rows": rows}
    for name, i in (("full_layer", 0), ("window_layer", 1)):
        kind = cfg.kinds[i]
        # the whole layer: a rounded router input moves the last of a few
        # tokens' choices, and the largest difference is one of those
        got = jax.jit(lambda x, p: llama.window_block(
            cfg, kind, x, p, i, llama.positions_of(1, rows),
            lambda *a: llama.attend_window_tiles(cfg, kind, *a))[0])(
                x.astype(cfg.dtype), block)[0]
        want = reference(lambda x, p: ref.layer(file, x, p, i), x[0], block)
        out[name + "_rel_err"] = dist(got, want)
        # its attention alone, on normed rows: what the band and the
        # rotation are held to
        got = jax.jit(lambda a, p: llama._attn_half(
            cfg, {w: p[w][i] for w in ("wq", "wk", "wv", "wo")}, a,
            llama.positions_of(1, rows),
            lambda *qkv: llama.attend_window_tiles(cfg, kind, *qkv),
            rope=kind == "W")[0])(x.astype(cfg.dtype), block)[0]
        attn = name.replace("layer", "attention")
        out[attn + "_rel_err"] = dist(got, reference(
            lambda a, p: ref.attention(file, a, p, i), x[0], block))
        for wrong, change in WRONG.items():
            if wrong.startswith(name.split("_")[0]):
                out[attn + "_against_" + wrong] = dist(got, reference(
                    lambda a, p: ref.attention(change(file), a, p, i), x[0],
                    block))

    def routed(h, a, act="reglu"):
        y, stats = jax.jit(lambda h, a, p: routed_mlp(
            h, p["router"][li], p["w_gate"], p["w_up"], p["w_down"],
            top_k=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
            router_input=a, act=act, layer=li))(
                h.astype(cfg.dtype), a.astype(cfg.dtype), block)
        return y[0], {k: float(v) for k, v in stats.items()}

    want = reference(lambda a, m, p: ref.experts(
        file, m, ref.route(file, a, p, li), p, li), x[0], m[0], block)
    got, stats = routed(m, x)
    out.update(routed_half_rel_err=dist(got, want), router=stats,
               routed_half_router_fed_the_mlps_input=dist(routed(m, m)[0],
                                                          want),
               routed_half_swiglu_for_reglu=dist(routed(m, x, "swiglu")[0],
                                                 want))
    return out


def through_pages(engine, toks, n: int, pages, wrong_slot=False):
    """Prefill ``n`` tokens, decode the rest: a row of logits each. With
    ``wrong_slot`` the decodes read the last page but one's window rows
    from the slot of the page before it."""
    import numpy as np

    from benchmarks.lib.serve_cell import pages_for

    ps = engine.page_size
    got = [engine.prefill([int(t) for t in toks[:n]],
                          pages[:pages_for(n, ps)])]
    kept = dict(engine._slot_of)
    if wrong_slot:
        engine._slot_of[pages[max(0, pages_for(n, ps) - 2)]] = \
            engine._slot_of[pages[max(0, pages_for(n, ps) - 3)]]
    try:
        for j in range(n, len(toks)):
            got.append(engine.decode(j, int(toks[j]),
                                     pages[:pages_for(j + 1, ps)]))
    finally:
        for page, slot in kept.items():
            engine._slot_of[page] = slot
    return np.stack(got)


def harness_check(file, traffic, engine, seed: int) -> dict:
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
    wrong = through_pages(engine, toks, n, pages, wrong_slot=True)
    engine.pool.release(pages)

    def reference(file):
        return np.asarray(jax.jit(partial(ref.logits_one, file))(
            engine.params, toks))[n - 1:]

    want = reference(file)
    rows = lambda other: [dist(g, w) for g, w in zip(got, other)]  # noqa: E731
    out = {"prompt_tokens": n, "rel_err": rows(want),
           "max_abs_logit": float(np.max(np.abs(want))),
           "decode_that_reads_a_wrong_slot": [
               dist(g, w) for g, w in zip(wrong, want)]}
    mm = ref._mm

    def eight_bit(exponent, mantissa, experts_alone=False):
        """The reference with every matrix it multiplies (``experts_alone``:
        the experts' three, whose stacked leaves have four axes, and no
        other) rounded to an 8-bit float where it is cut out (no second
        copy of the weights); reduce_precision and not a pair of casts,
        which the compiler may drop as excess precision."""
        def rounded(x, w, at=()):
            w, _ = jax.lax.optimization_barrier((w, x))
            cut = w[at]
            if w.ndim == 4 or not experts_alone:
                cut = jax.lax.reduce_precision(cut, exponent, mantissa)
            return x @ cut.astype(ref.F32)

        ref._mm = rounded
        try:
            return rows(reference(file))
        finally:
            ref._mm = mm

    out["reference_8bit_weights_e4m3"] = eight_bit(4, 3)
    out["reference_8bit_weights_e5m2"] = eight_bit(5, 2)
    out["reference_8bit_experts_alone_e5m2"] = eight_bit(5, 2, True)
    for wrong_way, change in WRONG.items():
        out["reference_" + wrong_way] = rows(reference(change(file)))
    # the router fed the MLP's input; SwiGLU for ReGLU
    layer = ref.layer
    eps = file["rms_norm_eps"]

    def other_layer(gate, router_reads_m):
        def fn(cfg, x, p, l):
            a = ref._rms_norm(x, p["attn_norm"][l], eps)
            h = x + ref.attention(cfg, a, p, l)
            m = ref._rms_norm(h, p["mlp_norm"][l], eps)
            return h + ref.experts(cfg, m, ref.route(
                cfg, m if router_reads_m else a, p, l), p, l, gate=gate)
        return fn

    for name, fn in (
            ("reference_router_fed_the_mlps_input",
             other_layer(jax.nn.relu, True)),
            ("reference_swiglu_for_reglu", other_layer(jax.nn.silu, False))):
        ref.layer = fn
        try:
            out[name] = rows(reference(file))
        finally:
            ref.layer = layer
    return out


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
    slots = engine._slots_for(table[-reach:])
    if kind == "prefill":
        fn = engine._prefill_fn
        args = (np.ones((1, n_pages * ps), np.int32), pages,
                np.asarray(n_pages * ps - 1, np.int32), slots)
    else:
        fn = engine._decode_fn
        args = (np.asarray([1], np.int32),
                np.asarray(n_pages * ps - 1, np.int32), pages, slots,
                np.asarray(n_pages - reach, np.int32))
    jit = getattr(fn, "_fn", fn)
    compiled = jit.lower(engine.params, *engine.stores, *args).compile()
    scopes = scopes_of(compiled)

    def call():
        out = compiled(engine.params, *engine.stores, *args)
        engine.stores = tuple(out[:len(engine.stores)])
        return out

    jax.block_until_ready(call())
    log_dir = tempfile.mkdtemp(prefix="smallthinker_check_")
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
        for scope, c in (("attn.full", "F"), ("attn.window", "W"))
        if scope in groups}
    return {"program": kind, "pages": n_pages, "calls": calls,
            "device_ms_a_call": 1e3 * total / calls, **per_layer,
            "ms_a_call_by_scope": {k: 1e3 * v / calls
                                   for k, v in sorted(groups.items())},
            "share_by_scope": {k: v / total for k, v in sorted(groups.items())},
            "largest_of_the_rest": [
                {"op": op, "ms_a_call": 1e3 * s / calls, "calls": n,
                 "op_name": scopes.get(op.split(" ", 1)[0], "")[-100:]}
                for op, s, n in rest[:8]],
            "memory_analysis": str(compiled.memory_analysis())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3800000038)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--skip", default="", help="comma list: grouped,parts,"
                    "check,time")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from ray_tpu.util import compile_cache

    compile_cache.configure(os.environ)
    import jax

    from benchmarks.lib import spec, traffic as traffic_mod
    from ray_tpu.util import flight_recorder as fr

    fr.configure(enabled=True)
    bundle = spec.cell_bundle(CELL, rehearsal=args.rehearsal)
    file = bundle["config"]
    cfg = spec.program_config(file)
    seed = traffic_mod.fold_seed(args.seed)
    dev = jax.devices()[0]
    out = {"seed": args.seed, "device": {"platform": dev.platform,
                                         "kind": dev.device_kind}}
    if dev.platform != "tpu" and not args.rehearsal:
        print("no TPU: nothing is measured on anything else", file=sys.stderr)
        return 3
    skip = set(args.skip.split(","))
    out_dir = os.path.join(ROOT, "chiprun_out", "smallthinker_check")
    os.makedirs(out_dir, exist_ok=True)

    def keep(part, make):  # a part that fails loses no other
        try:
            out[part] = make()
        except Exception as e:  # noqa: BLE001 - say which, go on
            out[part] = {"error": f"{type(e).__name__}: {e}"[:600]}
        with open(os.path.join(out_dir, f"result_{args.seed}.json"),
                  "w") as f:
            json.dump(out, f, indent=1)

    dep = file["deployment"]
    ps = dep["page_size"]
    most = 16 if not args.rehearsal else 6  # the cell's longest page table
    engine = spec.resolve(file["program"]["engine_class"])(
        cfg, n_pages=dep["n_pages"] if not args.rehearsal else 16,
        page_size=ps, seed=seed)
    out["n_slots"] = engine.n_slots
    if "grouped" not in skip:
        keep("grouped", lambda: grouped(
            engine.params, seed,
            (6 * 16384, 6 * 5120, 16) if not args.rehearsal else (96, 16)))
    if "parts" not in skip:
        keep("parts", lambda: parts(file, cfg, engine.params, seed,
                                    5120 if not args.rehearsal else 64))
    if "check" not in skip:
        out["serve_logits_rel_tol"] = file["correct"]["serve_logits_rel_tol"]
        keep("check", lambda: harness_check(file, bundle["traffic"], engine,
                                            seed))
    if "time" not in skip:
        few = 5 if not args.rehearsal else 2
        keep("decode_program_ms", lambda: {
            str(n): decode_program_ms(engine, n) for n in (few, most)})
        for kind, n in (("decode", few), ("decode", most), ("prefill", few),
                        ("prefill", (few + most) // 2 + 1 if args.rehearsal
                         else 9), ("prefill", most)):
            keep(f"time_{kind}_{n}", lambda: by_scope(engine, kind, n))
    keep("peak_bytes_in_use", lambda: [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()])
    print(json.dumps(out))
    return 1 if any(isinstance(v, dict) and "error" in v
                    for v in out.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
