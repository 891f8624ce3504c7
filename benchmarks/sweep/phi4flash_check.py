"""Phi-4-mini-flash-reasoning WHOLE at published widths, once, outside any
measured window: what the cell's own check (four rows of logits after all 32
layers) cannot show.

    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/sweep/phi4flash_check.py [--seeds N,N,..]

One process holds the chip. It prints one JSON object a seed and writes it
to ``chiprun_out/phi4flash_check/result_<seed>.json``:

1. ``kernel``: one layer's Mamba-1 operands at ``--rows`` positions (``ops/
   s6.py project_in`` of a random stream through layer 0's weights, the
   convolution and the projections as prefill runs them) through
   ``ops/s6_prefill.py`` against ``ops/s6.py scan`` (the ``lax.scan`` a
   position, the kernel's oracle) and against the REFERENCE's recurrence
   (float32 at 'highest'): outputs and the state, with ``last`` short of the
   end; and the kernel's time ALONE against :func:`s6_ops_bytes`. The MXU's
   peak is no bound of a kernel that multiplies no matrix and
   ``lib/flops.py PEAKS`` has none for the vector or the exponential unit:
   the share reported is of the HBM floor, and the kernel is bound by neither
   published peak.
2. ``check``: the harness's own comparison (prefill of 3 pages less two,
   three decodes across a page boundary; ``lib/serve_cell.py _prepare``) and
   what ``serve_logits_rel_tol`` has to refuse as the same distance: the
   reference with every matrix in 8-bit floats (both formats), the reference
   each wrong way of ``WRONG``, and the engine's stores spoiled between
   prefill and the decodes (``SPOILS``).
3. ``time_*``: device time by scope (``s6.*``, ``gmu.gate``, ``attn.*``,
   ``mem.mlp``; what the prefill runs behind the cut, on ONE position, under
   ``one_position``) over traced prefills at 2, 8 and 16 pages and a decode
   call at 16 pages, the median ``engine.decode_program`` span, the path each
   mixer's prefill took (``prefill_attend_paths()``) and the cut's gauge.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.sweep import commandaplus_check as by_scope_of  # noqa: E402
from benchmarks.sweep.keye_check import timed  # noqa: E402
from benchmarks.sweep.longcat_check import (  # noqa: E402 - the same helpers
    decode_program_ms, dist)

CELL = "serve-phi4miniflash-prefill-open"
# by_scope reads its module's pattern when it is called: this model's scopes;
# an operation behind the cut is keyed by its whole path from the cut on
by_scope_of.SCOPE = re.compile(
    r"(one_position.*|s6\.(?:in_proj|conv|x_proj|scan|step|out_proj)"
    r"|gmu\.gate|attn\.(?:qkv|diff_window|diff_full|cross|diff_norm|out)"
    r"|mem\.mlp)")

# the reference computed another way: its keywords; ``rows``: the rows of the
# four that the wrong way can move (the cut is a prefill's alone)
WRONG = {
    "skip_D_u_dropped": {"skip": False},
    "lam_at_0": {"lam": 0.0},
    "lam0_one_layer_off": {"depth_off": 1},
    "subtractions_norm_left_out": {"sub_norm": False},
    "window_of_511": {"window": 511},
    "window_of_513": {"window": 513},
    "memory_of_the_state_space_layer_in_front": {"memory_back": 1},
    "layer_15s_keys_for_layer_17s": {"cross_reads_window_keys": True},
    "cross_pass_at_last_minus_1": {"shift": 1},
}


def s6_ops_bytes(seq: int, channels: int, states: int, blocks: int):
    """What ONE call of ``s6_prefill`` needs at ``seq`` positions: a position
    and a channel take ``states`` decays (a product and an exponential), the
    update (two products and an add) and the read-out (a product and an add),
    then ``softplus``, ``dt u`` and the skip; it reads ``u`` and ``r W_dt``
    and writes ``y`` (float32), and reads a position's ``B`` and ``C`` once a
    channel block. Returns ``(operations, exponentials, bytes)``."""
    each = seq * channels
    return (each * (6 * states + 6), each * (states + 2),
            12 * each + 8 * seq * states * blocks)


def kernel(file, cfg, params, seed: int, rows: int) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmarks.lib import flops
    from ray_tpu.ops import s6
    from ray_tpu.ops.s6_prefill import CHANNELS, s6_prefill

    ref = importlib.import_module(file["reference"])
    p = {w: a[0] for w, a in params["layers"]["memory_mamba"].items()}
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, rows, cfg.dim),
                          jnp.float32).astype(cfg.dtype)

    def operands():
        u, _ = s6.project_in(x, p["w_in"])
        mixed = s6.convolve(u, p)
        return (mixed, *s6.select(mixed, p, cfg.dtype))

    mixed, r, b_in, c_in = jax.jit(operands)()
    last = rows - 1 - rows // 3
    start = jnp.zeros((1, cfg.s6_inner, cfg.ssm_state), jnp.float32)
    on_chip = jax.default_backend() == "tpu"
    run = jax.jit(lambda last: s6_prefill(mixed, r, b_in, c_in, p, start,
                                          last, interpret=not on_chip))
    y, state = run(jnp.int32(last))
    y_scan, state_scan = jax.jit(lambda last: s6.scan(
        mixed, r, b_in, c_in, p, None, last))(jnp.int32(last))

    def by_reference():
        with jax.default_matmul_precision("highest"):
            n = last + 1
            dt = jax.nn.softplus(r[0, :n] + p["dt_bias"])
            y_, s_ = ref.recurrence(mixed[0, :n], dt, -jnp.exp(p["A_log"]),
                                    b_in[0, :n], c_in[0, :n])
            return y_ + p["D"] * mixed[0, :n], s_

    y_ref, state_ref = jax.jit(by_reference)()
    out = {"rows": rows, "last": last,
           "kernel_against_scan": {
               "y": dist(y[:, :last + 1], y_scan[:, :last + 1]),
               "state": dist(state, state_scan)},
           "kernel_against_reference": {
               "y": dist(y[0, :last + 1], y_ref),
               "state": dist(state[0], state_ref)},
           "largest_state_entry": float(jnp.max(jnp.abs(state_ref))),
           "largest_y": float(jnp.max(jnp.abs(y_ref)))}
    if on_chip:
        seconds = timed(run, jnp.int32(rows - 1))
        ops, exps, nbytes = s6_ops_bytes(rows, cfg.s6_inner, cfg.ssm_state,
                                         cfg.s6_inner // CHANNELS)
        peak = flops.peaks(jax.devices()[0].device_kind)
        out["alone"] = {
            "ms_a_call": 1e3 * seconds, "operations": ops,
            "exponentials": exps, "bytes": nbytes,
            "hbm_floor_ms": 1e3 * nbytes / peak["bytes_per_s"],
            "share_of_the_hbm_floor_pct":
                100.0 * nbytes / peak["bytes_per_s"] / seconds,
            "operations_per_s": ops / seconds,
            "exponentials_per_s": exps / seconds,
            "bound": "neither published peak: no matrix product (the MXU's "
                     "197 TFLOP/s is no bound of it) and a fraction of the "
                     "HBM floor; lib/flops.py PEAKS has no peak of the "
                     "vector or the exponential unit, and none is invented"}
    return out


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

    def at(tag):
        return [i for i, s in enumerate(layout) if s.tag == tag]

    def through_pages(spoil=None, every=False):
        """Prefill ``n`` tokens, decode the rest: a row of logits each.
        ``spoil()`` changes the stores before the first decode (``every``:
        and before every later one)."""
        got = [engine.prefill([int(t) for t in toks[:n]],
                              pages[:pages_for(n, ps)])]
        for j in range(n, len(toks)):
            if spoil and (j == n or every):
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

    def change(tags, fn):
        def spoil():
            stores = list(engine.stores)
            for tag in tags:
                for i in at(tag):
                    stores[i] = jax.jit(fn, donate_argnums=0)(stores[i])
            engine.stores = tuple(stores)
        return spoil

    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    spoils = {
        "state_read_from_zeros": (change(["s6_state"], jnp.zeros_like),
                                  False),
        "convolution_tail_zeroed": (change(["s6_conv"], jnp.zeros_like),
                                    False),
        "window_slots_zeroed": (change(["memory_window"], jnp.zeros_like),
                                False),
        "full_layers_pages_zeroed": (change(["memory_full"], jnp.zeros_like),
                                     False),
        "state_store_in_bfloat16": (change(["s6_state"], bf16), True),
        "state_and_tail_stores_in_8bit_e4m3": (
            change(["s6_state", "s6_conv"],
                   lambda a: jax.lax.reduce_precision(a, 4, 3)), True),
    }
    for name, (spoil, every) in spoils.items():
        out["decode_with_" + name] = rows(through_pages(spoil, every), want)
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
            return rows(got, reference())
        finally:
            ref._mm = mm

    out["reference_8bit_weights_e4m3"] = eight_bit(4, 3)
    out["reference_8bit_weights_e5m2"] = eight_bit(5, 2)
    for name, wrong in WRONG.items():
        out["reference_" + name] = rows(got, reference(**wrong))
    return out


def by_scope(engine, kind: str, pages: int) -> dict:
    """``commandaplus_check.by_scope`` (it knows the window's slots), the
    operations behind the cut summed under ``one_position`` and beside it by
    the scope they end in."""
    out = by_scope_of.by_scope(engine, kind, pages)
    for table in ("ms_a_call_by_scope", "share_by_scope"):
        kept, behind = {}, {}
        for key, value in out[table].items():
            if key.startswith("one_position"):
                inner = by_scope_of.SCOPE.findall(key[len("one_position"):])
                where = inner[-1] if inner else "rest"
                behind[where] = behind.get(where, 0.0) + value
            else:
                kept[key] = value
        kept["one_position"] = sum(behind.values())
        out[table] = kept
        out[table + "_behind_the_cut"] = behind
    return out


def one_seed(args, bundle, file, cfg, seed_arg: int, skip: set) -> dict:
    import jax

    from benchmarks.lib import spec, traffic as traffic_mod
    from ray_tpu.models import llama
    from ray_tpu.util.metrics import registry

    seed = traffic_mod.fold_seed(seed_arg)
    dev = jax.devices()[0]
    out = {"seed": seed_arg, "init": dict(llama.MEMORY_INIT),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    out_dir = os.path.join(ROOT, "chiprun_out", "phi4flash_check")
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
    rows = args.rows if not args.rehearsal else 128
    if "kernel" not in skip:
        keep("kernel", lambda: kernel(file, cfg, engine.params, seed, rows))
    if "check" not in skip:
        out["serve_logits_rel_tol"] = file["correct"]["serve_logits_rel_tol"]
        traffic = bundle["traffic"]
        keep("check", lambda: harness_check(file, traffic, engine, seed,
                                            "faults" not in skip))
    if "time" not in skip:
        few, mid, most = (2, 8, 16) if not args.rehearsal else (3, 4, 5)
        keep("decode_program_ms", lambda: {
            str(n): decode_program_ms(engine, n) for n in (few, most)})
        for kind, n in (("decode", most), ("prefill", few), ("prefill", mid),
                        ("prefill", most)):
            keep(f"time_{kind}_{n}", lambda: by_scope(engine, kind, n))
        keep("prefill_attend_paths", llama.prefill_attend_paths)
        keep("decode_attend_forms", llama.decode_attend_forms)
        keep("gauges", lambda: {
            name: {str(k): v for k, v in registry().local_values(
                "ray_tpu_serve_engine_" + name).items()}
            for name in ("prefill_layers", "traced_layers", "state_bytes",
                         "page_bytes", "prefill_attend")})
    keep("peak_bytes_in_use", lambda: [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()])
    del engine
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="6500000001",
                    help="comma list: one engine and one result a seed")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--skip", default="", help="comma list: kernel,check,"
                    "faults,time")
    ap.add_argument("--wrong", default="", help="comma list: these wrong "
                    "ways alone (default: all)")
    ap.add_argument("--init", default="", help="wq=4,wo=2,dt=0.05:0.5: other "
                    "starting values (models/llama.py MEMORY_INIT)")
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
        if name not in llama.MEMORY_INIT:
            ap.error(f"--init {name}: not one of {sorted(llama.MEMORY_INIT)}")
        llama.MEMORY_INIT[name] = (tuple(float(v) for v in value.split(":"))
                                   if ":" in value else float(value))
    bundle = spec.cell_bundle(CELL, rehearsal=args.rehearsal)
    file = bundle["config"]
    if args.rehearsal:  # the whole pattern at narrow mixers (1,024 channels:
        # a block of the kernel's), a window of 8 and a check that crosses a
        # page
        file = dict(file, num_hidden_layers=32, mamba_expand=16,
                    sliding_window=8, mamba_dt_rank=8, max_model_len=64)
        bundle["traffic"] = dict(bundle["traffic"], prompt_tokens={
            "dist": "log_uniform", "min": 24, "max": 40})
        WRONG["window_of_511"], WRONG["window_of_513"] = (
            {"window": 7}, {"window": 9})
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
