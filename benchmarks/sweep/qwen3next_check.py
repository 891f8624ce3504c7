"""Qwen3-Next-80B-A3B-Instruct's cut at published widths, once, outside any
measured window: what the cell's own check (four rows of logits after all its
layers) cannot show.

    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/sweep/qwen3next_check.py [--seeds N,N,..]

One process holds the chip. It prints one JSON object a seed and writes it
to ``chiprun_out/qwen3next_check/result_<seed>.json``:

1. ``recurrence``: one layer's delta-rule operands at ``--rows`` positions
   (``models/llama.py delta_block``'s, as it hands them to ``attend``)
   through the chunked form (``ops/gdn.py gated_delta_chunked``, compute
   type) against the one-token step run token by token
   (``gated_delta_step``, float32) and against the REFERENCE's recurrence
   (float32 at 'highest'): outputs and the final state, and the chunked form
   with ``last`` short of the end against the state at ``last``.
2. ``check``: the harness's own comparison (prefill of 8,190 tokens, three
   decodes across a page boundary; ``lib/serve_cell.py _prepare``), and what
   ``serve_logits_rel_tol`` has to refuse as the same distance: the
   reference with every matrix in 8-bit floats (both formats), the reference
   each wrong way of ``WRONG``, and the engine's stores spoiled between
   prefill and the decodes as a wrong engine would leave them: the
   convolution's tail taken at the page's end, the state after the pads,
   ANOTHER sequence's state, the state not carried across the page
   boundary.
3. ``time_*``: device time by scope (``gdn.*``, ``attn.gated``, ``moe.*``;
   an operation's scope is read from the compiled program's ``op_name``
   metadata) over traced prefills at 3, 8 and 16 pages and decode calls at 4
   and 16 pages, the median ``engine.decode_program`` span, and the path the
   gated attention took at every page count (``prefill_attend_paths()``).

This PR adds no Pallas kernel and leaves ``ops/flash_prefill.py`` as it was:
there is no kernel's roofline share to count here.
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

from benchmarks.sweep.longcat_check import (  # noqa: E402 - the same helpers
    decode_program_ms, dist, scopes_of)

CELL = "serve-qwen3next-prefill-open"
SCOPE = re.compile(r"(gdn\.(?:in_proj|conv|scan|step|gate_norm|out_proj)"
                   r"|attn\.gated"
                   r"|moe\.(?:route|dispatch|experts|combine|shared))")

# the reference computed another way: its keywords
WRONG = {
    "no_decay": {"decay": False},
    "beta_one": {"beta_one": True},
    "no_l2_norm_of_q_k": {"l2norm": False},
    "no_convolution": {"conv": False},
    "norm_after_the_gate": {"norm_before_gate": False},
    "no_output_gate_in_attention": {"out_gate": False},
    "all_256_dimensions_rotated": {"partial": False},
    "norms_not_zero_centred": {"zero_centered": False},
    "no_shared_expert_gate": {"shared_gate": False},
    "router_not_renormalised": {"renormalised": False},
}


def recurrence(file, cfg, params, seed: int, rows: int) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops import gdn

    ref = importlib.import_module(file["reference"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, rows, cfg.dim),
                          jnp.float32)
    caught = []

    def catch(qkv, g, beta, conv_w):
        from ray_tpu.ops.ssm import causal_conv

        mixed = jax.nn.silu(causal_conv(qkv.astype(jnp.float32), conv_w, 0.0))
        caught.append((*llama._delta_heads(cfg, mixed), g, beta))
        hv, dv = cfg.lin_value_heads, cfg.lin_value_dim
        return (jnp.zeros((1, rows, hv, dv), jnp.float32),
                jnp.zeros((1, 1, hv, cfg.lin_key_dim, dv), jnp.float32),
                jnp.zeros((1, 1, cfg.lin_conv - 1, qkv.shape[-1]), qkv.dtype))

    def operands(x, stack):
        llama.delta_block(cfg, x, stack, 0, llama.positions_of(1, rows),
                          catch)
        return caught.pop()

    q, k, v, g, beta = jax.jit(operands)(x, params["layers"]["delta"])
    chunked = jax.jit(lambda *a, last=None: gdn.gated_delta_chunked(
        *a, cfg.lin_chunk, last=last))
    o, state = chunked(q, k, v, g, beta)

    def by_steps(q, k, v, g, beta):
        def step(state, row):
            o, state = gdn.gated_delta_step(*row, state)
            return state, o

        end, o = jax.lax.scan(
            step, jnp.zeros(state.shape, jnp.float32),
            tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), end

    def at_highest(fn):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)

        return jax.jit(run)

    o_step, state_step = at_highest(by_steps)(q, k, v, g, beta)
    f32 = [a[0].astype(jnp.float32) for a in (q, k, v, g, beta)]
    o_ref, state_ref = at_highest(ref.delta_rule)(*f32)
    last = rows - 1 - rows // 3
    _, state_last = chunked(q, k, v, g, beta, last=jnp.int32(last))
    _, want_last = at_highest(ref.delta_rule)(*(a[:last + 1] for a in f32))
    return {"rows": rows, "chunk": cfg.lin_chunk,
            "chunked_against_step": {"o": dist(o, o_step),
                                     "state": dist(state, state_step)},
            "chunked_against_reference": {"o": dist(o[0], o_ref),
                                          "state": dist(state[0], state_ref)},
            "step_against_reference": {"o": dist(o_step[0], o_ref),
                                       "state": dist(state_step[0],
                                                     state_ref)},
            "state_at_last_against_reference": dist(state_last[0], want_last),
            "largest_state_entry": float(jnp.max(jnp.abs(state_ref)))}


def harness_check(file, traffic, engine, seed: int, faults: bool) -> dict:
    import importlib
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib.serve_cell import check_prompt_len, pages_for, shapes_of
    from ray_tpu.models import llama

    ref = importlib.import_module(file["reference"])
    ps = engine.page_size
    n = check_prompt_len(shapes_of(traffic, ps), ps)
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, file["vocab_size"], size=n + 3).astype(np.int32)
    pages = engine.pool.alloc(pages_for(n + 3, ps))
    layout = llama.served_stores(engine.cfg)
    kept = [i for i, s in enumerate(layout) if s.table == "state"]

    def through_pages(spoil=None, before=n):
        """Prefill ``n`` tokens, decode the rest: a row of logits each.
        ``spoil()`` changes the stores before the decode of position
        ``before``."""
        got = [engine.prefill([int(t) for t in toks[:n]],
                              pages[:pages_for(n, ps)])]
        for j in range(n, len(toks)):
            if spoil and j == before:
                spoil()
            got.append(engine.decode(j, int(toks[j]),
                                     pages[:pages_for(j + 1, ps)]))
        return np.stack(got)

    def reference(**wrong):
        return np.asarray(jax.jit(partial(ref.logits_one, file, **wrong))(
            engine.params, toks))[n - 1:]

    def rows(some, other):
        return [dist(g, w) for g, w in zip(some, other)]

    got, want = through_pages(), reference()
    out = {"prompt_tokens": n, "rel_err": rows(got, want),
           "max_abs_logit": float(np.max(np.abs(want)))}
    if not faults:
        engine.pool.release(pages)
        return out
    # what a wrong engine would leave in the stores whose table is "state"
    others = engine.pool.alloc(len(pages))
    full = pages_for(n, ps) * ps

    def rows_of(page, which):
        return [engine.stores[i][:, page] for i in which]

    def put(page, which, values):
        stores = list(engine.stores)
        for i, a in zip(which, values):
            stores[i] = stores[i].at[:, page].set(a)
        engine.stores = tuple(stores)

    # the same prompt with its pads taken for tokens: the state after the
    # pads and the tail at the page's end lie in its last page
    engine.prefill([int(t) for t in toks[:n]] + [0] * (full - n),
                   others[:pages_for(n, ps)])
    padded = rows_of(others[pages_for(n, ps) - 1], kept)
    engine.prefill([int(t) for t in rng.randint(
        0, file["vocab_size"], size=n)], others[:pages_for(n, ps)])
    strangers = rows_of(others[pages_for(n, ps) - 1], kept)
    mine = pages[pages_for(n, ps) - 1]
    state_at = [i for i in kept if layout[i].tag == "state"]
    conv_at = [i for i in kept if layout[i].tag == "conv"]
    where = {i: j for j, i in enumerate(kept)}
    spoils = {
        "conv_tail_taken_at_the_pages_end": lambda: put(
            mine, conv_at, [padded[where[i]] for i in conv_at]),
        "state_after_the_pads": lambda: put(
            mine, state_at, [padded[where[i]] for i in state_at]),
        "another_sequences_state": lambda: put(
            mine, state_at, [strangers[where[i]] for i in state_at]),
    }
    for name, spoil in spoils.items():
        out["decode_with_" + name] = rows(through_pages(spoil), want)
    # the third decode opens a new page: a state left behind in the old one
    # is a state of zeros in the new one's place
    out["decode_with_state_not_carried_across_the_page_boundary"] = rows(
        through_pages(lambda: put(mine, kept, [
            jnp.zeros_like(a) for a in rows_of(mine, kept)]), before=n + 2),
        want)
    engine.pool.release(pages)
    engine.pool.release(others)
    mm = ref._mm

    def eight_bit(exponent, mantissa):
        """The reference with every matrix it multiplies rounded to an
        8-bit float where it is cut out; reduce_precision and not a pair of
        casts, which the compiler may drop as excess precision."""
        def rounded(x, w, at=()):
            w, _ = jax.lax.optimization_barrier((w, x))
            return x @ jax.lax.reduce_precision(
                w[at], exponent, mantissa).astype(ref.F32)

        ref._mm = rounded
        try:
            return rows(got, reference())
        finally:
            ref._mm = mm

    out["reference_8bit_weights_e4m3"] = eight_bit(4, 3)
    out["reference_8bit_weights_e5m2"] = eight_bit(5, 2)
    for name, wrong in WRONG.items():
        out["reference_" + name] = rows(got, reference(**wrong))
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
    if kind == "prefill":
        fn = engine._prefill_fn
        args = (np.ones((1, n_pages * ps), np.int32), pages,
                np.asarray(n_pages * ps - 3, np.int32))
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
    log_dir = tempfile.mkdtemp(prefix="qwen3next_check_")
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
            "share_by_scope": {k: v / total
                               for k, v in sorted(groups.items())},
            "largest_of_the_rest": [
                {"op": op, "ms_a_call": 1e3 * s / calls, "calls": n,
                 "op_name": scopes.get(op.split(" ", 1)[0], "")[-100:]}
                for op, s, n in rest[:8]],
            "memory_analysis": str(compiled.memory_analysis())}


def one_seed(args, bundle, file, cfg, seed_arg: int, skip: set) -> dict:
    import jax

    from benchmarks.lib import spec, traffic as traffic_mod
    from ray_tpu.models import llama

    seed = traffic_mod.fold_seed(seed_arg)
    dev = jax.devices()[0]
    out = {"seed": seed_arg, "init": dict(llama.DELTA_INIT),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    out_dir = os.path.join(ROOT, "chiprun_out", "qwen3next_check")
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{seed_arg}" + ("_" + re.sub(r"[^\w.]+", "_", args.init)
                           if args.init else "")

    def keep(part, make):  # a part that fails loses no other
        try:
            out[part] = make()
        except Exception as e:  # noqa: BLE001 - say which, go on
            out[part] = {"error": f"{type(e).__name__}: {e}"[:600]}
        with open(os.path.join(out_dir, f"result_{tag}.json"), "w") as f:
            json.dump(out, f, indent=1)

    dep = file["deployment"]
    engine = spec.resolve(file["program"]["engine_class"])(
        cfg, n_pages=dep["n_pages"] if not args.rehearsal else 64,
        page_size=dep["page_size"], seed=seed)
    rows = args.rows if not args.rehearsal else 96
    if "recurrence" not in skip:
        keep("recurrence", lambda: recurrence(file, cfg, engine.params, seed,
                                              rows))
    if "check" not in skip:
        out["serve_logits_rel_tol"] = file["correct"]["serve_logits_rel_tol"]
        traffic = bundle["traffic"]
        keep("check", lambda: harness_check(file, traffic, engine, seed,
                                            "faults" not in skip))
    if "time" not in skip:
        few, mid, most = (3, 8, 16) if not args.rehearsal else (3, 4, 5)
        keep("decode_program_ms", lambda: {
            str(n): decode_program_ms(engine, n) for n in (few + 1, most)})
        for kind, n in (("decode", few + 1), ("decode", most),
                        ("prefill", few), ("prefill", mid),
                        ("prefill", most)):
            keep(f"time_{kind}_{n}", lambda: by_scope(engine, kind, n))
        keep("prefill_attend_paths", llama.prefill_attend_paths)
        keep("expert_product_paths", llama.expert_product_paths)
    keep("peak_bytes_in_use", lambda: [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()])
    del engine
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="4000000045",
                    help="comma list: one engine and one result a seed")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--skip", default="", help="comma list: recurrence,"
                    "check,faults,time")
    ap.add_argument("--wrong", default="", help="comma list: these wrong "
                    "ways alone (default: all)")
    ap.add_argument("--init", default="", help="q_norm=2,wo=8,dt=0.001:0.1: "
                    "other starting values (models/llama.py DELTA_INIT)")
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
        name, value = item.split("=")
        if name not in llama.DELTA_INIT:
            ap.error(f"--init {name}: not one of {sorted(llama.DELTA_INIT)}")
        llama.DELTA_INIT[name] = (tuple(float(v) for v in value.split(":"))
                                  if ":" in value else float(value))
    bundle = spec.cell_bundle(CELL, rehearsal=args.rehearsal)
    file = bundle["config"]
    if args.rehearsal:  # a whole period, and a check that crosses a page
        file = dict(file, num_hidden_layers=4)
        bundle["traffic"] = dict(bundle["traffic"], prompt_tokens={
            "dist": "log_uniform", "min": 24, "max": 40})
    cfg = spec.program_config(file)
    for name in [n for n in WRONG if args.wrong
                 and n not in args.wrong.split(",")]:
        del WRONG[name]
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
