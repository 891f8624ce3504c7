"""granite-4.0-h-micro WHOLE at published widths, once, outside any measured
window: what the cell's own check (four rows of logits after all forty
layers) cannot show.

    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/sweep/granite4h_check.py [--seeds N,N,..]

One process holds the chip. It prints one JSON object a seed and writes it
to ``chiprun_out/granite4h_check/result_<seed>.json``:

1. ``recurrence``: one layer's Mamba-2 operands at ``--rows`` positions
   (``ops/ssm.py project_in`` of a random stream through layer 0's weights)
   through the chunked scan (``scan_positions``, compute type, segmented as
   prefill runs it) against the one-token step run token by token (``step``,
   float32) and against the REFERENCE's recurrence (float32 at 'highest'):
   outputs and the final state, and the scan with ``last`` short of the end
   against the state at ``last``.
2. ``check``: the harness's own comparison (prefill of 4,094 tokens, three
   decodes across a page boundary; ``lib/serve_cell.py _prepare``), and what
   ``serve_logits_rel_tol`` has to refuse as the same distance: the
   reference with every matrix in 8-bit floats (both formats), the reference
   each wrong way of ``WRONG``, and the engine's state stores spoiled
   between prefill and the decodes as a wrong engine would leave them
   (``SPOILS``).
3. ``time_*``: device time by scope (``ssm.*``, ``hyb.*``, ``attn.full``; an
   operation's scope is read from the compiled program's ``op_name``
   metadata) over traced prefills at 2, 4 and 8 pages and a decode call at 8
   pages, the median ``engine.decode_program`` span, and the path each
   mixer's prefill took (``prefill_attend_paths()``).

This PR adds no Pallas kernel and leaves ``ops/flash_prefill.py`` as it was:
there is no kernel's roofline share to count here.
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

from benchmarks.sweep import qwen3next_check as by_scope_of  # noqa: E402
from benchmarks.sweep.longcat_check import (  # noqa: E402 - the same helpers
    decode_program_ms, dist)

CELL = "serve-granite4hmicro-prefill-open"
# by_scope reads its module's pattern when it is called: this model's scopes
by_scope_of.SCOPE = re.compile(
    r"(ssm\.(?:in_proj|conv|scan|step|gate_norm|out_proj)"
    r"|hyb\.(?:qkv|out|mlp)|attn\.full)")

# the reference computed another way: its keywords
WRONG = {
    "convolution_bias_dropped": {"conv_bias": False},
    "residual_multiplier_left_out": {"residual_multiplier": 1.0},
    "attention_multiplier_0.125": {"attention_multiplier": 0.125},
    "embedding_multiplier_left_out": {"embedding_multiplier": 1.0},
    "logits_scaling_left_out": {"logits_scaling": 1.0},
    "attention_layers_rotated": {"rotated": True},
    "skip_D_x_dropped": {"skip": False},
}


def recurrence(file, cfg, params, seed: int, rows: int) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    ref = importlib.import_module(file["reference"])
    dims = dict(heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
                groups=cfg.ssm_groups, state=cfg.ssm_state,
                chunk=cfg.ssm_chunk)
    p = {w: a[0] for w, a in params["layers"]["hybrid_mamba"].items()}
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, rows, cfg.dim),
                          jnp.float32).astype(cfg.dtype)
    _, xbc, dt = jax.jit(lambda x: ssm.project_in(
        x, p["w_in"], cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim))(x)
    scan = jax.jit(lambda last=None: ssm.scan_positions(
        xbc, dt, p, last=last, segment=ssm.SEGMENT, **dims))
    y, state, _ = scan()

    def by_steps():
        with jax.default_matmul_precision("highest"):
            def one(carry, row):
                y_t, s, t = ssm.step(row[0][:, None], row[1][:, None], p,
                                     *carry, groups=cfg.ssm_groups)
                return (s, t), y_t[:, 0]

            K, width = p["conv_w"].shape
            (s, _), ys = jax.lax.scan(
                one, (jnp.zeros(state.shape, jnp.float32),
                      jnp.zeros((1, K - 1, width), jnp.float32)),
                (jnp.moveaxis(xbc, 1, 0), jnp.moveaxis(dt, 1, 0)))
            return jnp.moveaxis(ys, 0, 1), s

    y_step, state_step = jax.jit(by_steps)()

    def by_reference(n):
        """The reference's recurrence on the float32 operands behind the
        convolution, as ``ref.mamba`` makes them."""
        with jax.default_matmul_precision("highest"):
            H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                          cfg.ssm_state)
            rows_ = xbc[0, :n].astype(jnp.float32)
            K = p["conv_w"].shape[0]
            before = jnp.pad(rows_, ((K - 1, 0), (0, 0)))
            mixed = jax.nn.silu(sum(before[j:j + n] * p["conv_w"][j]
                                    for j in range(K)) + p["conv_b"])
            x_ = mixed[:, :H * P].reshape(n, H, P)
            b_, c_ = (jnp.repeat(v.reshape(n, G, N), H // G, axis=1)
                      for v in (mixed[:, H * P:H * P + G * N],
                                mixed[:, H * P + G * N:]))
            y_, s_ = ref.recurrence(
                x_, jax.nn.softplus(dt[0, :n] + p["dt_bias"]),
                -jnp.exp(p["A_log"]), b_, c_)
            return y_ + p["D"][:, None] * x_, s_

    y_ref, state_ref = jax.jit(by_reference, static_argnums=0)(rows)
    last = rows - 1 - rows // 3
    _, state_last, _ = scan(jnp.int32(last))
    _, want_last = jax.jit(by_reference, static_argnums=0)(last + 1)
    return {"rows": rows, "chunk": cfg.ssm_chunk, "segment": ssm.SEGMENT,
            "chunked_against_step": {"y": dist(y, y_step),
                                     "state": dist(state, state_step)},
            "chunked_against_reference": {"y": dist(y[0], y_ref),
                                          "state": dist(state[0], state_ref)},
            "step_against_reference": {"y": dist(y_step[0], y_ref),
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
    state_at = [i for i, s in enumerate(layout) if s.tag == "ssm_state"]
    conv_at = [i for i, s in enumerate(layout) if s.tag == "ssm_conv"]

    def through_pages(spoil=None, before=n, every=False):
        """Prefill ``n`` tokens, decode the rest: a row of logits each.
        ``spoil()`` changes the stores before the decode of position
        ``before`` (``every``: and before every later one)."""
        got = [engine.prefill([int(t) for t in toks[:n]],
                              pages[:pages_for(n, ps)])]
        for j in range(n, len(toks)):
            if spoil and (j == before or (every and j > before)):
                spoil(pages[(j - 1) // ps])
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
    others = engine.pool.alloc(len(pages))
    full = pages_for(n, ps) * ps

    def rows_of(page, which):
        return [engine.stores[i][:, page] for i in which]

    def put(page, which, values):
        stores = list(engine.stores)
        for i, a in zip(which, values):
            stores[i] = stores[i].at[:, page].set(a)
        engine.stores = tuple(stores)

    def change(which, fn):
        return lambda page: put(page, which, [fn(a)
                                              for a in rows_of(page, which)])

    # the same prompt with its pads taken for tokens: the state after the
    # pads and the tail at the page's end lie in its last page
    engine.prefill([int(t) for t in toks[:n]] + [0] * (full - n),
                   others[:pages_for(n, ps)])
    padded = rows_of(others[pages_for(n, ps) - 1], state_at + conv_at)
    engine.prefill([int(t) for t in rng.randint(
        0, file["vocab_size"], size=n)], others[:pages_for(n, ps)])
    strangers = rows_of(others[pages_for(n, ps) - 1], state_at)
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    spoils = {
        "state_read_from_zeros": (change(state_at, jnp.zeros_like), n, False),
        # the third decode opens a page: a state left behind in the old one
        # is a state of zeros in the new one's place
        "state_not_read_from_the_page_before": (
            change(state_at + conv_at, jnp.zeros_like), n + 2, False),
        "convolution_tail_zeroed": (change(conv_at, jnp.zeros_like), n,
                                    False),
        "positions_behind_last_left_live": (
            lambda page: put(page, state_at + conv_at, padded), n, False),
        "state_after_the_pads_alone": (
            lambda page: put(page, state_at, padded[:len(state_at)]), n,
            False),
        "another_sequences_state": (
            lambda page: put(page, state_at, strangers), n, False),
        "state_store_in_bfloat16": (change(state_at, bf16), n, True),
        "state_and_tail_stores_in_8bit_e4m3": (
            change(state_at + conv_at, lambda a: jax.lax.reduce_precision(
                a, 4, 3)), n, True),
    }
    for name, (spoil, before, every) in spoils.items():
        out["decode_with_" + name] = rows(
            through_pages(spoil, before, every), want)
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


def one_seed(args, bundle, file, cfg, seed_arg: int, skip: set) -> dict:
    import jax

    from benchmarks.lib import spec, traffic as traffic_mod
    from ray_tpu.models import llama

    seed = traffic_mod.fold_seed(seed_arg)
    dev = jax.devices()[0]
    out = {"seed": seed_arg, "init": dict(llama.HYBRID_INIT),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    out_dir = os.path.join(ROOT, "chiprun_out", "granite4h_check")
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
        few, mid, most = (2, 4, 8) if not args.rehearsal else (3, 4, 5)
        keep("decode_program_ms", lambda: {
            str(n): decode_program_ms(engine, n) for n in (few, most)})
        for kind, n in (("decode", most), ("prefill", few), ("prefill", mid),
                        ("prefill", most)):
            keep(f"time_{kind}_{n}",
                 lambda: by_scope_of.by_scope(engine, kind, n))
        keep("prefill_attend_paths", llama.prefill_attend_paths)
        keep("decode_attend_forms", llama.decode_attend_forms)
    keep("peak_bytes_in_use", lambda: [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()])
    del engine
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="5800000001",
                    help="comma list: one engine and one result a seed")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--skip", default="", help="comma list: recurrence,"
                    "check,faults,time")
    ap.add_argument("--wrong", default="", help="comma list: these wrong "
                    "ways alone (default: all)")
    ap.add_argument("--init", default="", help="wq=16,wo=12,dt=0.05:0.5: "
                    "other starting values (models/llama.py HYBRID_INIT)")
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
        if name not in llama.HYBRID_INIT:
            ap.error(f"--init {name}: not one of {sorted(llama.HYBRID_INIT)}")
        llama.HYBRID_INIT[name] = (tuple(float(v) for v in value.split(":"))
                                   if ":" in value else float(value))
    bundle = spec.cell_bundle(CELL, rehearsal=args.rehearsal)
    file = bundle["config"]
    if args.rehearsal:  # a whole period at narrow mixers, and a check that
        # crosses a page
        file = dict(file, num_hidden_layers=10, mamba_n_heads=8,
                    mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
                    shared_intermediate_size=96)
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
