"""LongCat-Flash-Chat's share at published widths, once, outside any
measured window: what the cell's own check (four rows of logits after four
layers) cannot show.

    chiprun --chips 1 --timeout 2400 -- python3 benchmarks/sweep/longcat_check.py [--seed N]

One process holds the chip. It prints one JSON object and writes it to
``chiprun_out/longcat_check/result_<seed>.json``:

1. ``parts``: layer 0's routed branch (router, held experts, identity
   experts) and one latent attention sublayer, each ALONE on 2,048 normed
   rows, against the reference: largest difference over the reference's
   largest value. Beside them what logits after four layers cannot refuse:
   the branch with its identity experts' part taken away and with its
   weights not multiplied by 6, as distances from the same reference.
2. ``long``: the engine's prefill of 8,190 tokens (16 pages) and three
   latent decodes (the third opens a 17th page) against the reference's
   full forward of 8,193 tokens.
3. ``check``: the harness's own comparison (prefill of 2,558 tokens, three
   decodes across a page boundary; ``lib/serve_cell.py _prepare``), and
   what ``serve_logits_rel_tol`` has to refuse as the same distance: the
   reference with its weights rounded to 8-bit floats (the nearest
   precision below the configuration's bfloat16, in both 8-bit formats), a
   decode whose table names a page that is not the sequence's, the
   reference with the shared key left un-rotated and with both
   ``mla_scale_*`` off.
4. ``decode_program_ms`` / ``time_*``: device time by scope (an operation's scope is read from the
   compiled program's ``op_name`` metadata) over three traced prefills at
   8 and at 16 pages and three decode calls at 17 pages, and the median
   ``engine.decode_program`` span at 5 and at 17 pages.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "serve-longcatflash-prefill-open"
SCOPE = re.compile(r"(mla\.(?:project|attend|out)|ffn\.dense"
                   r"|moe\.(?:route|dispatch|experts|combine|zero))")


def dist(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def parts(file, cfg, params, seed: int, rows: int) -> dict:
    import dataclasses
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    ref = importlib.import_module(file["reference"])
    layers = params["layers"]["scmoe"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (1, rows, cfg.dim),
                          jnp.float32).astype(cfg.dtype)
    h32 = h[0].astype(jnp.float32)
    p0 = jax.tree.map(lambda a: a[0], layers)

    def branch(c):
        y, stats = jax.jit(lambda p, h: llama._mlp_half(c, p, h))(p0, h)
        return y[0], {k: float(v) for k, v in stats.items()}

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda L, h: ref.moe(file, h, L, 0))(layers, h32)
        want_mla = jax.jit(lambda L, h: ref.mla(file, h, L, (0, 0)))(
            layers, h32)
    got, stats = branch(cfg)
    unscaled, _ = branch(dataclasses.replace(cfg, routed_scale=1.0))
    # the identity experts' part is (sum of their weights) x h: the branch
    # of a router whose experts' outputs are zeroed
    zeroed = dict(p0, w_down=jnp.zeros_like(p0["w_down"]))
    identity = jax.jit(lambda p, h: llama._mlp_half(cfg, p, h)[0])(zeroed, h)[0]
    sub = {w: layers[w][0, 0] for w in ("wq_a", "q_norm", "wq_b", "wkv_a",
                                        "kv_norm", "wkv_b", "wo")}
    got_mla = jax.jit(lambda p, h: llama._latent_half(
        cfg, p, h, llama.positions_of(1, rows),
        lambda *a: llama.attend_latent_expanded(cfg, *a))[0])(sub, h)[0]
    # one whole layer on the same rows
    got_layer = jax.jit(lambda L, h: llama.shortcut_layer(
        cfg, h, L, 0, llama.positions_of(1, rows),
        lambda j, *a: llama.attend_latent_expanded(cfg, *a))[0])(layers, h)[0]
    with jax.default_matmul_precision("highest"):
        want_layer = jax.jit(lambda L, h: ref.layer(file, h, L, 0))(
            layers, h32)
    return {
        "layer_rel_err": dist(got_layer, want_layer),
        "rows": rows, "router": stats,
        "routed_branch_rel_err": dist(got, want),
        "routed_branch_without_identity_experts": dist(
            got.astype(jnp.float32) - identity.astype(jnp.float32), want),
        "routed_branch_weights_not_times_6": dist(unscaled, want),
        "latent_attention_rel_err": dist(got_mla, want_mla),
    }


def through_pages(engine, toks, n: int, pages, decode_pages=None):
    """Prefill ``n`` tokens, decode the rest: a row of logits each."""
    import numpy as np

    from benchmarks.lib.serve_cell import pages_for

    ps = engine.page_size
    got = [engine.prefill([int(t) for t in toks[:n]],
                          pages[:pages_for(n, ps)])]
    for j in range(n, len(toks)):
        table = (decode_pages or pages)[:pages_for(j + 1, ps)]
        got.append(engine.decode(j, int(toks[j]), table))
    return np.stack(got)


def long_context(file, engine, seed: int, n: int) -> dict:
    import importlib
    from functools import partial

    import jax
    import numpy as np

    ref = importlib.import_module(file["reference"])
    toks = np.random.RandomState(seed).randint(
        0, file["vocab_size"], size=n + 3).astype(np.int32)
    pages = engine.pool.alloc(-(-(n + 3) // engine.page_size))
    got = through_pages(engine, toks, n, pages)
    engine.pool.release(pages)
    block, ref.QUERY_BLOCK = ref.QUERY_BLOCK, 256  # 0.5 GB of scores a block
    try:
        want = np.asarray(jax.jit(partial(ref.logits_one, file))(
            engine.params, toks))[n - 1:]
    finally:
        ref.QUERY_BLOCK = block
    return {"prompt_tokens": n, "pages": len(pages),
            "rel_err": [dist(g, w) for g, w in zip(got, want)],
            "max_abs_logit": float(np.max(np.abs(want)))}


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
    # the ORDER of whole pages does not matter to a softmax over rows that
    # carry their own rotation; a page that is not the sequence's does
    other = [pages[1]] + pages[1:]
    wrong = through_pages(engine, toks, n, pages, decode_pages=other)
    engine.pool.release(pages)

    def reference(file, params):
        return np.asarray(jax.jit(partial(ref.logits_one, file))(
            params, toks))[n - 1:]

    want = reference(file, engine.params)
    mm = ref._mm

    def eight_bit(exponent, mantissa):
        """The reference with every matrix it multiplies rounded to an
        8-bit float where it is cut out (no second copy of the weights);
        reduce_precision and not a pair of casts, which the compiler may
        drop as excess precision."""
        def rounded(x, w, at=()):
            w, _ = jax.lax.optimization_barrier((w, x))
            return x @ jax.lax.reduce_precision(
                w[at], exponent, mantissa).astype(ref.F32)

        ref._mm = rounded
        try:
            return reference(file, engine.params)
        finally:
            ref._mm = mm

    rope = ref._rope
    ref._rope = lambda x, theta: x if x.shape[1] == 1 else rope(x, theta)
    try:
        not_rotated = reference(file, engine.params)
    finally:
        ref._rope = rope
    unscaled = reference(dict(file, mla_scale_q_lora=False,
                              mla_scale_kv_lora=False), engine.params)
    rows = lambda other: [dist(g, w) for g, w in zip(got, other)]  # noqa: E731
    return {"prompt_tokens": n, "rel_err": rows(want),
            "max_abs_logit": float(np.max(np.abs(want))),
            # e4m3 flushes most weights of 1 / sqrt(6144) to zero; e5m2
            # keeps their exponent and two bits
            "reference_8bit_weights_e4m3": rows(eight_bit(4, 3)),
            "reference_8bit_weights_e5m2": rows(eight_bit(5, 2)),
            "decode_with_a_page_of_another_place": [
                dist(g, w) for g, w in zip(wrong, want)],
            "reference_shared_key_not_rotated": rows(not_rotated),
            "reference_without_mla_scales": rows(unscaled)}


def scopes_of(compiled) -> dict:
    found = {}
    for line in compiled.as_text().splitlines():
        m = re.match(r'\s*(?:ROOT )?%(\S+) = .*op_name="([^"]*)"', line)
        if m:
            found[m.group(1)] = m.group(2)
    return found


def by_scope(engine, kind: str, n_pages: int, calls: int = 3) -> dict:
    """Device time of ``calls`` traced calls of one of the engine's programs
    at ``n_pages``, by named scope."""
    import jax
    import numpy as np

    from benchmarks.lib import trace as tr

    ps = engine.page_size
    pages = np.asarray(engine.pool.alloc(n_pages), np.int32)
    if kind == "prefill":
        fn = engine._prefill_fn
        args = (np.ones((1, n_pages * ps), np.int32), pages,
                np.asarray(n_pages * ps - 1, np.int32))
    else:
        fn = engine._decode_fn
        args = (np.asarray([1], np.int32),
                np.asarray(n_pages * ps - 1, np.int32), pages)
    jit = getattr(fn, "_fn", fn)
    compiled = jit.lower(engine.params, *engine.stores, *args).compile()
    scopes = scopes_of(compiled)

    def call():
        out = compiled(engine.params, *engine.stores, *args)
        engine.stores = tuple(out[:len(engine.stores)])
        return out

    jax.block_until_ready(call())
    log_dir = tempfile.mkdtemp(prefix="longcat_check_")
    jax.profiler.start_trace(log_dir)
    for _ in range(calls):
        jax.block_until_ready(call())
    jax.profiler.stop_trace()
    engine.pool.release([int(p) for p in pages])
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
    return {"program": kind, "pages": n_pages, "calls": calls,
            "device_ms_a_call": 1e3 * total / calls,
            "ms_a_call_by_scope": {k: 1e3 * v / calls
                                   for k, v in sorted(groups.items())},
            "share_by_scope": {k: v / total for k, v in sorted(groups.items())},
            "largest_of_the_rest": [
                {"op": op, "ms_a_call": 1e3 * s / calls, "calls": n,
                 "op_name": scopes.get(op.split(" ", 1)[0], "")[-100:]}
                for op, s, n in rest[:8]],
            "memory_analysis": str(compiled.memory_analysis())}


def decode_program_ms(engine, n_pages: int, calls: int = 7) -> float:
    """Median ``engine.decode_program`` span of ``calls`` decode calls at
    ``n_pages`` (after one that may compile)."""
    import time

    from benchmarks.lib import onchip

    pages = engine.pool.alloc(n_pages)
    pos = n_pages * engine.page_size - 1
    engine.decode(pos, 1, pages)
    lo = time.monotonic()
    for _ in range(calls):
        engine.decode(pos, 1, pages)
    spans = onchip.local_spans(["engine.decode_program"], lo,
                               time.monotonic())["engine.decode_program"]
    engine.pool.release(pages)
    return 1e3 * statistics.median(d for _, d in spans)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3300000033)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--skip", default="", help="comma list: parts,long,"
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
    out_dir = os.path.join(ROOT, "chiprun_out", "longcat_check")
    os.makedirs(out_dir, exist_ok=True)

    def keep(part, make):  # a part that fails loses no other
        try:
            out[part] = make()
        except Exception as e:  # noqa: BLE001 - say which, go on
            out[part] = {"error": f"{type(e).__name__}: {e}"[:600]}
        with open(os.path.join(out_dir, f"result_{args.seed}.json"),
                  "w") as f:
            json.dump(out, f, indent=1)

    ps = file["deployment"]["page_size"]
    most = 17 if not args.rehearsal else 6  # the cell's longest page table
    engine = spec.resolve(file["program"]["engine_class"])(
        cfg, n_pages=most + 3, page_size=ps, seed=seed)
    if "parts" not in skip:
        keep("parts", lambda: parts(file, cfg, engine.params, seed,
                                    2048 if not args.rehearsal else 64))
    if "long" not in skip:
        keep("long", lambda: long_context(file, engine, seed,
                                          (most - 1) * ps - 2))
    if "check" not in skip:
        out["serve_logits_rel_tol"] = file["correct"]["serve_logits_rel_tol"]
        keep("check", lambda: harness_check(file, bundle["traffic"], engine,
                                            seed))
    if "time" not in skip:
        keep("decode_program_ms", lambda: {
            str(n): decode_program_ms(engine, n) for n in (5, most)})
        for kind, n in (("decode", most), ("prefill", most // 2),
                        ("prefill", most - 1)):
            keep(f"time_{kind}_{n}", lambda: by_scope(engine, kind, n))
    keep("peak_bytes_in_use", lambda: [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()])
    print(json.dumps(out))
    return 1 if any(isinstance(v, dict) and "error" in v
                    for v in out.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
