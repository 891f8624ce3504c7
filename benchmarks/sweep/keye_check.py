"""Keye-VL-2.0-30B-A3B's cut at published widths, once, outside any measured
window: what the cell's own check (four rows of logits after all its layers)
cannot show.

    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/sweep/keye_check.py [--seed N]

One process holds the chip. It prints one JSON object and writes it to
``chiprun_out/keye_check/result_<seed>.json``:

1. ``kernels``: one layer's selection and attention at ``--rows`` positions
   through the two Pallas calls (``ops/sparse_prefill.py``) against the XLA
   path (``models/llama.py _selected_tiles``), on the same bfloat16
   operands: the attention's largest difference, the mask's differing
   entries, and the selection against the REFERENCE's (float32 scores at
   'highest', ``jax.lax.top_k``): positions of S(t) that differ, a query.
   Each call's median wall time at 8,192, 16,384 and 32,768 positions with
   its share of its roofline (:func:`select_ops_bytes`,
   :func:`masked_flash_ops_bytes`).
2. ``parts``: the whole block ALONE on ``--rows`` rows against the
   reference's layer, and the reference's layer computed each wrong way
   (the selection ignored, ``topk`` 1,024 and 4,096, the score without its
   ReLU, the heads' weights left out, unrotated index keys, no QK-norm, the
   router's weights not renormalised), as distances from the same program.
3. ``check``: the harness's own comparison (prefill of 8,190 tokens, three
   decodes across a page boundary; ``lib/serve_cell.py _prepare``), and
   what ``serve_logits_rel_tol`` has to refuse as the same distance: the
   reference with every matrix in 8-bit floats (both formats), the
   reference each wrong way of (2), a decode that reads ANOTHER sequence's
   index rows, and a decode whose key and value rows lie in swapped pages.
4. ``time_*``: device time by scope (``dsa.index``, ``dsa.select``,
   ``dsa.attend``, ``moe.*``; an operation's scope is read from the compiled
   program's ``op_name`` metadata) over traced prefills at 4, 8 and 16
   pages and decode calls at 4 and 16 pages, each new kernel's share of its
   roofline from the device trace, and the median ``engine.decode_program``
   span at 4 and 16 pages.
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

CELL = "serve-keyevl2-prefill-open"
SCOPE = re.compile(r"(dsa\.(?:index|select|attend)"
                   r"|moe\.(?:route|dispatch|experts|combine))")
KERNELS = ("dsa_index_select", "dsa_masked_flash")


def topk_of(file, k):
    return dict(file, sa_config=dict(file["sa_config"], topk=k))


# the reference computed another way: (the file's keys changed, keywords)
WRONG = {
    "selection_ignored": (lambda f: f, {"select": False}),
    "topk_1024": (lambda f: topk_of(f, 1024), {}),
    "topk_4096": (lambda f: topk_of(f, 4096), {}),
    "score_without_relu": (lambda f: f, {"relu": False}),
    "head_weights_left_out": (lambda f: f, {"weighted": False}),
    "index_keys_not_rotated": (lambda f: f, {"rotate_index": False}),
    "qk_norm_left_out": (lambda f: f, {"qk_norm": False}),
    "router_not_renormalised": (lambda f: f, {"renormalised": False}),
}


# --------------------------------------------------------------------------- #
# What each new kernel needs: operations and the least bytes it moves
# --------------------------------------------------------------------------- #


def select_ops_bytes(seq: int, heads: int, head_dim: int, act_bytes: int = 2):
    """``ops/sparse_prefill.py index_select`` at ``seq`` positions: the
    index score of every visible pair (two operations a multiply-add, every
    query head; the ReLU, the weighted sum and the bisection's counts run
    beside the MXU and are not counted), and the least bytes: queries, keys
    and weights read once, the int8 mask written once."""
    pairs = seq * (seq + 1) / 2
    return {"ops": 2.0 * pairs * heads * head_dim,
            "bytes": float(seq * heads * head_dim * act_bytes
                           + seq * head_dim * act_bytes + seq * heads * 4
                           + seq * seq)}


def masked_flash_ops_bytes(seq: int, heads: int, kv_heads: int, head_dim: int,
                           topk: int, act_bytes: int = 2):
    """``masked_flash`` at ``seq`` positions: the operations the SELECTED
    pairs need (a query's ``min(t + 1, topk)`` keys, two products of
    ``head_dim`` each, every head), not the visible pairs the kernel
    computes under the mask (``computed_ops``: what its MXU time goes to);
    bytes: q, k, v and the mask read once, the output written once."""
    few = min(seq, topk)
    chosen = few * (few + 1) / 2 + (seq - few) * few
    per_pair = 2 * 2.0 * head_dim * heads
    q = seq * heads * head_dim * act_bytes
    return {"ops": per_pair * chosen,
            "computed_ops": per_pair * seq * (seq + 1) / 2,
            "bytes": float(2 * q + 2 * seq * kv_heads * head_dim * act_bytes
                           + seq * seq)}


def roofline_share(seconds: float, ops: float, nbytes: float, kind: str):
    from benchmarks.lib import flops

    least = flops.roofline_seconds(ops, nbytes, kind)
    return {"share_pct": 100.0 * least["seconds"] / seconds,
            "bound": least["bound"], "least_ms": 1e3 * least["seconds"]}


def kernel_shares(cfg, seq: int, seconds_of: dict, kind: str) -> dict:
    """``{kernel_roofline: ...}`` for one call of each kernel that took
    ``seconds_of[kernel]`` at ``seq`` positions."""
    out = {}
    if seconds_of.get("dsa_index_select"):
        need = select_ops_bytes(seq, cfg.index_heads, cfg.index_head_dim)
        out["dsa_index_select_roofline"] = roofline_share(
            seconds_of["dsa_index_select"], need["ops"], need["bytes"], kind)
    if seconds_of.get("dsa_masked_flash"):
        need = masked_flash_ops_bytes(seq, cfg.n_heads, cfg.n_kv_heads,
                                      cfg.head_dim, cfg.index_topk)
        took = seconds_of["dsa_masked_flash"]
        out["dsa_masked_flash_roofline"] = dict(
            roofline_share(took, need["ops"], need["bytes"], kind),
            computed_share_of_peak_pct=roofline_share(
                took, need["computed_ops"], 0.0, kind)["share_pct"])
    return out


# --------------------------------------------------------------------------- #


def timed(fn, *args, calls: int = 5) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t0)
    return statistics.median(took)


def layer_operands(cfg, params, seed: int, rows: int):
    """One layer's attention operands from random normed rows, as
    ``index_block`` hands them to ``attend``: ``(a, (q, k, v, qi, ki, w))``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    a = jax.random.normal(jax.random.PRNGKey(seed), (1, rows, cfg.dim),
                          jnp.float32)
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True))
    caught = []

    def catch(*ops):
        caught.append(ops)
        return jnp.zeros(ops[0].shape, cfg.dtype)

    def run(a, block):
        # N(x) of rows that are normed already is (nearly) the rows
        llama.index_block(cfg, a, block, 0, llama.positions_of(1, rows),
                          catch)
        return caught.pop()

    return a, jax.jit(run)(a, params["layers"]["index"])


def kernels(file, cfg, params, seed: int, rows: int, kind: str,
            sizes) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.ops import sparse_prefill as sp

    ref = importlib.import_module(file["reference"])
    interpret = jax.default_backend() != "tpu"
    K = cfg.index_topk
    a, ops = layer_operands(cfg, params, seed, rows)
    q, k, v, qi, ki, w = ops
    out = {"rows": rows, "topk": K}
    mask = jax.jit(lambda *o: sp.mask_rows(sp.index_select(
        *o, K, interpret=interpret)))(qi, ki, w)[0, :, :rows] != 0
    visible = jnp.arange(rows)[None, :] <= jnp.arange(rows)[:, None]

    def oracle(qi, ki, w):  # in query blocks: [block, rows] scores at a time
        return jax.lax.map(
            lambda n: llama.select_top(llama.index_scores(
                jax.lax.dynamic_slice_in_dim(qi, n * 128, 128, 1), ki,
                jax.lax.dynamic_slice_in_dim(w, n * 128, 128, 1))[0],
                jax.lax.dynamic_slice_in_dim(visible, n * 128, 128, 0), K),
            jnp.arange(rows // 128)).reshape(rows, rows)

    want = jax.jit(oracle)(qi, ki, w)
    out["mask_entries_differing_from_xla_path"] = int(jnp.sum(mask != want))
    out["mask_entries"] = int(jnp.sum(want))
    # against the reference's selection: float32 at 'highest', lax.top_k

    def blocks(x):
        return x.reshape(rows // 128, 128, *x.shape[1:])

    def reference_mask(a, block):
        with jax.default_matmul_precision("highest"):
            qi, ki, w = ref.indexer(file, a, block, 0,
                                    ref.text_positions(rows))

            def one(xs):
                qi_b, w_b, at = xs
                score = jnp.sum(w_b[:, :, None] * jax.nn.relu(
                    jnp.einsum("qhd,kd->qhk", qi_b, ki)), axis=1)
                return ref.selection(file, score, at)

            return jax.lax.map(one, (blocks(qi), blocks(w),
                                     blocks(jnp.arange(rows)))).reshape(
                                         rows, rows)

    theirs = jax.jit(reference_mask)(a[0], params["layers"]["index"])
    differ = np.asarray(jnp.sum(mask & ~theirs, axis=1))
    past = np.arange(rows) >= K  # rows that select at all
    out["selection_vs_reference"] = {
        "positions_of_S_differing_a_query_mean": float(differ[past].mean())
        if past.any() else 0.0,
        "positions_of_S_differing_a_query_max": int(differ.max()),
        "queries_with_any_difference_share": float((differ[past] > 0).mean())
        if past.any() else 0.0}
    got = jax.jit(lambda *o: sp.masked_flash(
        o[0], o[1], o[2], sp.index_select(*o[3:], K, interpret=interpret),
        interpret=interpret))(*ops)
    tiles = jax.jit(lambda *o: llama._selected_tiles(
        *o, K, cfg.dtype, cfg.index_chunk))(*ops)
    out["attention_kernels_against_xla_path"] = dist(got, tiles)
    if interpret:
        return out
    for seq in sizes:
        _, (q, k, v, qi, ki, w) = layer_operands(cfg, params, seed, seq)
        select = jax.jit(lambda *o: sp.index_select(*o, K))
        m = select(qi, ki, w)
        took = {"dsa_index_select": timed(select, qi, ki, w),
                "dsa_masked_flash": timed(jax.jit(sp.masked_flash), q, k, v,
                                          m)}
        out[f"wall_{seq}"] = {
            **{name + "_ms": 1e3 * s for name, s in took.items()},
            **kernel_shares(cfg, seq, took, kind)}
    return out


def parts(file, cfg, params, seed: int, rows: int) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    ref = importlib.import_module(file["reference"])
    block = params["layers"]["index"]
    # in the compute type's values: both sides read the same numbers
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, rows, cfg.dim),
                          jnp.float32).astype(cfg.dtype).astype(jnp.float32)

    def reference(fn, *args):
        def at_highest(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)

        return jax.jit(at_highest)(*args)

    got = jax.jit(lambda x, p: llama.index_block(
        cfg, x, p, 0, llama.positions_of(1, rows),
        lambda *a: llama.attend_selected(cfg, *a))[0])(x, block)[0]
    out = {"rows": rows, "layer_rel_err": dist(got, reference(
        lambda x, p: ref.layer(file, x, p, 0), x[0], block))}
    for name, (change, wrong) in WRONG.items():
        out["layer_against_" + name] = dist(got, reference(
            lambda x, p: ref.layer(change(file), x, p, 0, **wrong), x[0],
            block))
    return out


def through_pages(engine, toks, n: int, pages, after_prefill=None):
    """Prefill ``n`` tokens, decode the rest: a row of logits each.
    ``after_prefill()`` may spoil the stores between the two."""
    import numpy as np

    from benchmarks.lib.serve_cell import pages_for

    ps = engine.page_size
    got = [engine.prefill([int(t) for t in toks[:n]],
                          pages[:pages_for(n, ps)])]
    if after_prefill:
        after_prefill()
    for j in range(n, len(toks)):
        got.append(engine.decode(j, int(toks[j]),
                                 pages[:pages_for(j + 1, ps)]))
    return np.stack(got)


def harness_check(file, traffic, engine, seed: int, faults: bool) -> dict:
    import importlib
    from functools import partial

    import jax
    import numpy as np

    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    ref = importlib.import_module(file["reference"])
    ps = engine.page_size
    n = check_prompt_len(shapes_of(traffic, ps), ps)
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, file["vocab_size"], size=n + 3).astype(np.int32)
    pages = engine.pool.alloc(-(-(n + 3) // ps))
    got = through_pages(engine, toks, n, pages)

    def reference(file, **wrong):
        return np.asarray(jax.jit(partial(ref.logits_one, file, **wrong))(
            engine.params, toks))[n - 1:]

    want = reference(file)
    rows = lambda some, other: [dist(g, w)  # noqa: E731
                                for g, w in zip(some, other)]
    out = {"prompt_tokens": n, "rel_err": rows(got, want),
           "max_abs_logit": float(np.max(np.abs(want)))}
    if not faults:
        engine.pool.release(pages)
        return out
    # decode faults: the stores spoiled between prefill and the decodes
    others = engine.pool.alloc(len(pages))
    engine.prefill([int(t) for t in rng.randint(
        0, file["vocab_size"], size=n)], others[:-(-n // ps)])
    mine, theirs = np.asarray(pages), np.asarray(others)

    def others_index_rows():
        k, v, ki = engine.stores
        engine.stores = (k, v, ki.at[:, mine].set(ki[:, theirs]))

    def swapped_pages():
        k, v, ki = engine.stores
        a, b = mine[0], mine[1]
        engine.stores = (k.at[:, [a, b]].set(k[:, [b, a]]),
                         v.at[:, [a, b]].set(v[:, [b, a]]), ki)

    out["decode_that_reads_another_sequences_index_rows"] = rows(
        through_pages(engine, toks, n, pages, others_index_rows), want)
    out["decode_that_gathers_rows_by_a_wrong_page"] = rows(
        through_pages(engine, toks, n, pages, swapped_pages), want)
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
            return rows(got, reference(file))
        finally:
            ref._mm = mm

    out["reference_8bit_weights_e4m3"] = eight_bit(4, 3)
    out["reference_8bit_weights_e5m2"] = eight_bit(5, 2)
    for name, (change, wrong) in WRONG.items():
        out["reference_" + name] = rows(got, reference(change(file),
                                                       **wrong))
    return out


def by_scope(engine, kind: str, n_pages: int, calls: int = 3) -> dict:
    """Device time of ``calls`` traced calls of one of the engine's programs
    at ``n_pages``, by named scope, and the new kernels' roofline shares."""
    import jax
    import numpy as np

    from benchmarks.lib import trace as tr

    ps = engine.page_size
    table = engine.pool.alloc(n_pages)
    pages = np.asarray(table, np.int32)
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
    log_dir = tempfile.mkdtemp(prefix="keye_check_")
    jax.profiler.start_trace(log_dir)
    for _ in range(calls):
        jax.block_until_ready(call())
    jax.profiler.stop_trace()
    engine.pool.release(table)
    devices = tr.extract(tr.newest_xplane(log_dir), {})["devices"]
    ops = devices[0]["ops"] if devices else []  # none on the CPU
    keyed, kernel_s = [], dict.fromkeys(KERNELS, 0.0)
    for label, start, dur, _ in ops:
        name = label.split(" ", 1)[0]
        where = scopes.get(name, "")
        for kernel in KERNELS:
            if kernel in where or kernel in label:
                kernel_s[kernel] += dur
        found = SCOPE.findall(where)
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
    cfg = engine.cfg
    out = {"program": kind, "pages": n_pages, "calls": calls,
           "device_ms_a_call": 1e3 * total / calls,
           "ms_a_call_by_scope": {k: 1e3 * v / calls
                                  for k, v in sorted(groups.items())},
           "share_by_scope": {k: v / total for k, v in sorted(groups.items())},
           "largest_of_the_rest": [
               {"op": op, "ms_a_call": 1e3 * s / calls, "calls": n,
                "op_name": scopes.get(op.split(" ", 1)[0], "")[-100:]}
               for op, s, n in rest[:8]],
           "memory_analysis": str(compiled.memory_analysis())}
    if kind == "prefill" and any(kernel_s.values()):
        per_call = {k: s / calls / cfg.n_layers for k, s in kernel_s.items()}
        out["kernel_ms_a_layer"] = {k: 1e3 * s for k, s in per_call.items()}
        out.update(kernel_shares(cfg, n_pages * ps, per_call,
                                 jax.devices()[0].device_kind))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=4000000040)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--skip", default="", help="comma list: kernels,parts,"
                    "check,faults,time")
    ap.add_argument("--wrong", default="", help="comma list: these wrong "
                    "ways alone (default: all)")
    ap.add_argument("--init", default="", help="wo=2,q_norm=2: other "
                    "starting scales (models/llama.py INDEX_INIT)")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from ray_tpu.util import compile_cache

    compile_cache.configure(os.environ)
    import jax

    from benchmarks.lib import spec, traffic as traffic_mod
    from ray_tpu.models import llama
    from ray_tpu.util import flight_recorder as fr

    fr.configure(enabled=True)
    for item in filter(None, args.init.split(",")):
        name, value = item.split("=")
        if name not in llama.INDEX_INIT:
            ap.error(f"--init {name}: not one of {sorted(llama.INDEX_INIT)}")
        llama.INDEX_INIT[name] = float(value)
    bundle = spec.cell_bundle(CELL, rehearsal=args.rehearsal)
    file = bundle["config"]
    if args.rehearsal:  # a selection the tiny sizes meet
        file = dict(topk_of(file, 128), indexer_topk=128)
    cfg = spec.program_config(file)
    for name in [n for n in WRONG if args.wrong
                 and n not in args.wrong.split(",")]:
        del WRONG[name]
    seed = traffic_mod.fold_seed(args.seed)
    dev = jax.devices()[0]
    out = {"seed": args.seed, "init": dict(llama.INDEX_INIT),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    if dev.platform != "tpu" and not args.rehearsal:
        print("no TPU: nothing is measured on anything else", file=sys.stderr)
        return 3
    skip = set(args.skip.split(","))
    out_dir = os.path.join(ROOT, "chiprun_out", "keye_check")
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{args.seed}" + ("_" + args.init.replace(",", "_").replace(
        "=", "") if args.init else "")

    def keep(part, make):  # a part that fails loses no other
        try:
            out[part] = make()
        except Exception as e:  # noqa: BLE001 - say which, go on
            out[part] = {"error": f"{type(e).__name__}: {e}"[:600]}
        with open(os.path.join(out_dir, f"result_{tag}.json"), "w") as f:
            json.dump(out, f, indent=1)

    dep = file["deployment"]
    ps = dep["page_size"]
    few, most = (4, 16) if not args.rehearsal else (17, 24)
    rows = args.rows if not args.rehearsal else 256
    engine = spec.resolve(file["program"]["engine_class"])(
        cfg, n_pages=dep["n_pages"] if not args.rehearsal else 64,
        page_size=ps, seed=seed)
    if "kernels" not in skip:
        keep("kernels", lambda: kernels(
            file, cfg, engine.params, seed, rows, dev.device_kind,
            (8192, 16384, 32768)))
    if "parts" not in skip:
        keep("parts", lambda: parts(file, cfg, engine.params, seed, rows))
    if "check" not in skip:
        out["serve_logits_rel_tol"] = file["correct"]["serve_logits_rel_tol"]
        traffic = bundle["traffic"]
        if args.rehearsal:  # prompts the smaller topk cuts
            traffic = dict(traffic, prompt_tokens={
                "dist": "log_uniform", "min": 130, "max": 180})
        keep("check", lambda: harness_check(file, traffic, engine, seed,
                                            "faults" not in skip))
    if "time" not in skip:
        keep("decode_program_ms", lambda: {
            str(n): decode_program_ms(engine, n) for n in (few, most)})
        for kind, n in (("decode", few), ("decode", most), ("prefill", few),
                        ("prefill", (few + most) // 2 - 2), ("prefill", most)):
            keep(f"time_{kind}_{n}", lambda: by_scope(engine, kind, n))
    keep("peak_bytes_in_use", lambda: [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()])
    print(json.dumps(out))
    return 1 if any(isinstance(v, dict) and "error" in v
                    for v in out.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
