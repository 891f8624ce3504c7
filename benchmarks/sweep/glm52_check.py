"""GLM-5.2's cut at published widths, once, outside any measured window: what
the cell's own check (four rows of logits after all its layers) cannot show.

    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/sweep/glm52_check.py [--seeds A,B,C]

One process holds the chip. It prints one JSON object and writes it to
``chiprun_out/glm52_check/result_<first seed>.json``:

1. ``kernels``: a FULL layer's selection and attention at ``--rows``
   positions through the two Pallas calls (``ops/sparse_prefill.py`` at 32
   index heads of 128 and at head width 256, a key head a query head) against
   the XLA path (``models/llama.py _latent_selected_tiles``), on the same
   bfloat16 operands: the attention's largest difference, the mask's
   differing entries, and the selection against the REFERENCE's (float32
   scores at 'highest', ``jax.lax.top_k``): positions of S(t) that differ, a
   query. Each call's median wall time at 6,144 and 16,384 positions with
   its share of its roofline (``keye_check.py select_ops_bytes`` /
   ``masked_flash_ops_bytes`` at THESE shapes: :func:`kernel_shares`).
2. ``check_<seed>``: the harness's own comparison (prefill of 6,142 tokens,
   three decodes across a page boundary; ``lib/serve_cell.py _prepare``), a
   seed each of ``--seeds``. For the first seed also what
   ``serve_logits_rel_tol`` has to refuse, as the same distance from the
   SOUND engine's rows: the reference with every matrix in 8-bit floats
   (both formats: the nearest precision below), and each WRONG WAY: a shared
   layer that selects for itself (the reference is given indexer weights
   there), the last full layer attending under the FIRST full layer's
   selection (the wrong full layer's), the selection ignored, ``index_topk``
   1,024, index keys not rotated, the indexer's query from the stream and
   not ``cq``; and, through the engine, a decode whose shared layers gather
   their rows from ANOTHER layer's store.
3. ``time_*``: device time by scope (``mla.*``, ``dsa.index`` / ``.select`` /
   ``.attend`` in full layers, ``dsa.attend_shared`` in shared ones,
   ``dsa.gather``, ``moe.*``, ``ffn.dense``) over traced prefills at 3 and 8
   pages and decode calls at 3 and 8 pages, each kernel's share of its
   roofline from the device trace, and the median ``engine.decode_program``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.sweep import keye_check as kc  # noqa: E402 - the same helpers
from benchmarks.sweep.longcat_check import (  # noqa: E402
    decode_program_ms, dist)

CELL = "serve-glm52-prefill-open"
SCOPE = re.compile(r"(dsa\.(?:index|select|attend_shared|attend|gather)"
                   r"|mla\.(?:project|out)|ffn\.dense"
                   r"|moe\.(?:route|dispatch|experts|combine|shared))")

# the reference computed another way: (the file's keys changed, keywords)
WRONG = {
    "shared_layer_selects_for_itself": (lambda f: f, {"shared_selects": True}),
    "selection_of_the_wrong_full_layer": (lambda f: f, {"stale": True}),
    "selection_ignored": (lambda f: f, {"select": False}),
    "topk_1024": (lambda f: dict(f, index_topk=1024), {}),
    "index_keys_not_rotated": (lambda f: f, {"rotate_index": False}),
    "index_query_from_the_stream": (lambda f: f, {"index_from_stream": True}),
}


def layers_that(cfg, what: str) -> int:
    """How many of the built layers run ``index_select`` (the full ones) or
    ``masked_flash`` (all)."""
    from ray_tpu.models import llama

    return sum(c in llama.DSA_FULL for c in cfg.kinds) \
        if what == "dsa_index_select" else cfg.n_layers


def kernel_shares(cfg, seq: int, seconds_of: dict, kind: str) -> dict:
    """``{kernel_roofline: ...}`` for ONE call of each kernel that took
    ``seconds_of[kernel]`` at ``seq`` positions, at this model's shapes:
    ``index_select`` at 32 heads of 128, ``masked_flash`` at 64 query heads
    on 64 expanded key heads of 256 (score and value width)."""
    out = {}
    if seconds_of.get("dsa_index_select"):
        need = kc.select_ops_bytes(seq, cfg.index_heads, cfg.index_head_dim)
        out["dsa_index_select_roofline"] = kc.roofline_share(
            seconds_of["dsa_index_select"], need["ops"], need["bytes"], kind)
    if seconds_of.get("dsa_masked_flash"):
        need = kc.masked_flash_ops_bytes(
            seq, cfg.n_heads, cfg.n_heads, cfg.v_head_dim, cfg.index_topk)
        took = seconds_of["dsa_masked_flash"]
        out["dsa_masked_flash_roofline"] = dict(
            kc.roofline_share(took, need["ops"], need["bytes"], kind),
            computed_share_of_peak_pct=kc.roofline_share(
                took, need["computed_ops"], 0.0, kind)["share_pct"])
    return out


def layer_operands(cfg, params, seed: int, rows: int):
    """A FULL layer's attention operands from random normed rows, as
    ``dsa_block`` hands them to ``attend``: ``(a, (q, k, v), (qi, ki, w))``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    a = jax.random.normal(jax.random.PRNGKey(seed), (1, rows, cfg.dim),
                          jnp.float32)
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True))

    def run(a, stack):
        p = jax.tree.map(lambda w: w[0], stack)
        positions = llama.positions_of(1, rows)
        q, latent, cq = llama._latent_project(cfg, p, a.astype(cfg.dtype),
                                              positions)
        k, v = llama._latent_heads(cfg, latent, p["wkv_b"].astype(cfg.dtype))
        return (q, k, v), llama._indexer(cfg, p, a, positions, cq=cq)

    return (a, *jax.jit(run)(a, params["layers"]["dsa_dense"]))


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
    a, qkv, index = layer_operands(cfg, params, seed, rows)
    out = {"rows": rows, "topk": K}
    tiles = jax.jit(lambda *o: sp.index_select(*o, K, interpret=interpret))(
        *index)
    mask = sp.mask_rows(tiles)[0, :, :rows] != 0
    visible = jnp.arange(rows)[None, :] <= jnp.arange(rows)[:, None]

    def oracle(qi, ki, w):  # in query blocks: [block, rows] scores at a time
        return jax.lax.map(
            lambda n: llama.select_top(llama.index_scores(
                jax.lax.dynamic_slice_in_dim(qi, n * 128, 128, 1), ki,
                jax.lax.dynamic_slice_in_dim(w, n * 128, 128, 1))[0],
                jax.lax.dynamic_slice_in_dim(visible, n * 128, 128, 0), K),
            jnp.arange(rows // 128)).reshape(rows, rows)

    want = jax.jit(oracle)(*index)
    out["mask_entries_differing_from_xla_path"] = int(jnp.sum(mask != want))
    out["mask_entries"] = int(jnp.sum(want))

    def reference_mask(a, stack):  # float32 at 'highest', lax.top_k
        with jax.default_matmul_precision("highest"):
            _, _, _, cq = ref.qkv(file, a, stack, 0)
            return ref.selection(file, *ref.indexer(file, a, cq, stack, 0))

    theirs = jax.jit(reference_mask)(a[0], params["layers"]["dsa_dense"])
    differ = np.asarray(jnp.sum(mask & ~theirs, axis=1))
    past = np.arange(rows) >= K  # rows that select at all
    out["selection_vs_reference"] = {
        "positions_of_S_differing_a_query_mean": float(differ[past].mean())
        if past.any() else 0.0,
        "positions_of_S_differing_a_query_max": int(differ.max()),
        "queries_with_any_difference_share": float((differ[past] > 0).mean())
        if past.any() else 0.0}
    got = jax.jit(lambda q, k, v, m: sp.masked_flash(
        q, k, v, m, interpret=interpret))(*qkv, tiles)
    xla, _ = jax.jit(lambda q, k, v, m: llama._latent_selected_tiles(
        q, k, v, None, m, K, cfg.dtype, cfg.index_chunk))(
            *qkv, want[None].astype(jnp.int8))
    out["attention_kernels_against_xla_path"] = dist(got, xla)
    if interpret:
        return out
    for seq in sizes:
        _, qkv, index = layer_operands(cfg, params, seed, seq)
        select = jax.jit(lambda *o: sp.index_select(*o, K))
        m = select(*index)
        took = {"dsa_index_select": kc.timed(select, *index),
                "dsa_masked_flash": kc.timed(jax.jit(sp.masked_flash), *qkv,
                                             m)}
        out[f"wall_{seq}"] = {
            **{name + "_ms": 1e3 * s for name, s in took.items()},
            **kernel_shares(cfg, seq, took, kind)}
    return out


def harness_check(file, traffic, engine, seed: int, faults: bool) -> dict:
    import importlib
    from functools import partial

    import jax
    import numpy as np

    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of
    from ray_tpu.models import llama

    ref = importlib.import_module(file["reference"])
    ps = engine.page_size
    n = check_prompt_len(shapes_of(traffic, ps), ps)
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, file["vocab_size"], size=n + 3).astype(np.int32)
    pages = engine.pool.alloc(-(-(n + 3) // ps))
    got = kc.through_pages(engine, toks, n, pages)

    def reference(file, params=engine.params, **wrong):
        return np.asarray(jax.jit(partial(ref.logits_one, file, **wrong))(
            params, toks))[n - 1:]

    def rows(some, other):
        return [dist(g, w) for g, w in zip(some, other)]

    want = reference(file)
    out = {"prompt_tokens": n, "rel_err": rows(got, want),
           "max_abs_logit": float(np.max(np.abs(want)))}
    if not faults:
        engine.pool.release(pages)
        return out
    # a decode fault: the shared layers' store spoiled between prefill and
    # the decodes, its layers in reverse, so that a shared layer gathers the
    # full layer's row numbers from ANOTHER layer's store
    z = [s.kind for s in llama.served_stores(engine.cfg)].index("Z")

    def other_layers_store():
        stores = list(engine.stores)
        stores[z] = stores[z][::-1]
        engine.stores = tuple(stores)

    out["decode_rows_gathered_from_another_layers_store"] = rows(
        kc.through_pages(engine, toks, n, pages, other_layers_store), got)
    engine.pool.release(pages)
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
    # indexer weights for the shared layers, which hold none: seeded as a
    # full layer's are, for the one wrong way that needs them
    indexers = jax.jit(lambda key: llama._init_indexer(
        engine.cfg, engine.cfg.kinds.count("Z"), key))(
            jax.random.PRNGKey(seed % (2 ** 31)))
    with_indexers = dict(engine.params, layers=dict(
        engine.params["layers"], dsa_shared=dict(
            engine.params["layers"]["dsa_shared"], **indexers)))
    for name, (change, wrong) in WRONG.items():
        params = with_indexers if "shared_selects" in wrong else engine.params
        out["reference_" + name] = rows(got, reference(change(file), params,
                                                       **wrong))
    return out


def by_scope(engine, kind: str, n_pages: int) -> dict:
    """``keye_check.by_scope`` with this model's scopes, and each kernel's
    time a LAYER THAT RUNS IT (``index_select`` runs in the full layers
    alone) with its shares at this model's shapes."""
    import jax

    kc.SCOPE = SCOPE
    out = kc.by_scope(engine, kind, n_pages)
    cfg = engine.cfg
    if "kernel_ms_a_layer" in out:
        per_call = {k: ms * cfg.n_layers / layers_that(cfg, k) / 1e3
                    for k, ms in out["kernel_ms_a_layer"].items()}
        out["kernel_ms_a_layer"] = {k: 1e3 * s for k, s in per_call.items()}
        for name in ("dsa_index_select_roofline", "dsa_masked_flash_roofline"):
            out.pop(name, None)
        out.update(kernel_shares(cfg, n_pages * engine.page_size, per_call,
                                 jax.devices()[0].device_kind))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="6300000063")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--skip", default="", help="comma list: kernels,check,"
                    "faults,time")
    ap.add_argument("--scales", default="", help="wo=8,expert_down=0.5: "
                    "other seeded_scales than the file's")
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
    file, traffic = bundle["config"], bundle["traffic"]
    if args.scales:
        file["seeded_scales"] = {name: float(value) for name, value in (
            item.split("=") for item in args.scales.split(","))}
    if args.rehearsal:  # a selection the tiny sizes meet, and a whole period
        file.update(index_topk=32, num_hidden_layers=5)
        traffic = dict(traffic, prompt_tokens={
            "dist": "log_uniform", "min": 40, "max": 60})
        WRONG["topk_1024"] = (lambda f: dict(f, index_topk=16), {})
    cfg = spec.program_config(file)
    seeds = [int(s) for s in args.seeds.split(",")]
    dev = jax.devices()[0]
    out = {"seeds": seeds, "seeded_scales": file["seeded_scales"],
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    if dev.platform != "tpu" and not args.rehearsal:
        print("no TPU: nothing is measured on anything else", file=sys.stderr)
        return 3
    skip = set(args.skip.split(","))
    out_dir = os.path.join(ROOT, "chiprun_out", "glm52_check")
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{seeds[0]}" + ("_" + args.scales.replace(",", "_").replace(
        "=", "") if args.scales else "")

    def keep(part, make):  # a part that fails loses no other
        try:
            out[part] = make()
        except Exception as e:  # noqa: BLE001 - say which, go on
            out[part] = {"error": f"{type(e).__name__}: {e}"[:600]}
        with open(os.path.join(out_dir, f"result_{tag}.json"), "w") as f:
            json.dump(out, f, indent=1)

    dep = file["deployment"]
    few, most = (3, 8) if not args.rehearsal else (6, 8)
    rows = args.rows if not args.rehearsal else 256
    out["serve_logits_rel_tol"] = file["correct"]["serve_logits_rel_tol"]
    for i, seed in enumerate(seeds):
        folded = traffic_mod.fold_seed(seed)
        engine = spec.resolve(file["program"]["engine_class"])(
            cfg, n_pages=dep["n_pages"], page_size=dep["page_size"],
            seed=folded)
        if "check" not in skip:
            keep(f"check_{seed}", lambda: harness_check(
                file, traffic, engine, folded,
                i == 0 and "faults" not in skip))
        if i == 0 and "kernels" not in skip:
            keep("kernels", lambda: kernels(
                file, cfg, engine.params, folded, rows, dev.device_kind,
                (6144, 16384)))
        if i == 0 and "time" not in skip:
            keep("decode_program_ms", lambda: {
                str(n): decode_program_ms(engine, n) for n in (few, most)})
            for kind, n in (("decode", few), ("decode", most),
                            ("prefill", few), ("prefill", most)):
                keep(f"time_{kind}_{n}", lambda: by_scope(engine, kind, n))
        del engine  # the next seed's weights need its room
        gc.collect()
    keep("peak_bytes_in_use", lambda: [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()])
    print(json.dumps(out))
    return 1 if any(isinstance(v, dict) and "error" in v
                    for v in out.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
