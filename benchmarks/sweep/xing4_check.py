"""Xing4.0-29B-A4B's cut at published widths, once, outside any measured
window: what the cell's own check (four rows of logits after all its layers)
cannot show.

    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/sweep/xing4_check.py [--seeds N,N,..]

One process holds the chip. It prints one JSON object a seed and writes it
to ``chiprun_out/xing4_check/result_<seed>.json``:

1. ``parts``: on a stream of ``--rows`` positions whose four rows differ, one
   sublayer's mix (``models/llama.py hyper_mix``) against the reference's,
   the Sinkhorn's error after 1, 5 and 20 iterations, read-out and
   write-back (``hyper_connected``), YaRN's rotation at position 16,000,
   one latent attention and the routed MLP (compute type against the
   float32 reference at 'highest').
2. ``check``: the harness's OWN comparison (``lib/serve_cell.py
   BenchLM._prepare``: prefill of 3,070 tokens, three decodes across a page
   boundary, then ``run``'s rule ``max(rel_err) <= serve_logits_rel_tol``)
   on this one engine, first with the configuration's reference (``sound``:
   has to come out correct), then with the reference computed each wrong way
   of ``--wrong`` (default: all of ``WRONG``; each has to come out NOT
   correct, or the tolerance cannot refuse it): every matrix in 8-bit
   floats (both formats), the hyper-connections' and the rotation's faults,
   the routed MLP's.
3. ``time_*``: device time by scope (``hc.*``, ``mla.*``, ``moe.*``,
   ``ffn.dense``; an operation's scope is read from the compiled program's
   ``op_name`` metadata) over traced prefills at 3, 8 and 16 pages and decode
   calls at 4 and 16 pages with each program's memory account, the median
   ``engine.decode_program`` span, and the path the latent attentions took.
4. ``layouts``: ONE hyper-connected sublayer's passes (mix, read-out, an
   identity sublayer, write-back) over 16,384 positions in the layout the
   program carries (``[1, T, 4 x 3584]``) and as ``[1, T, 4, 3584]``, float32
   and bfloat16: the time of a call and the compiler's temporaries, what
   chose the stream's layout and type.

This PR adds no Pallas kernel: there is no kernel's roofline share to count.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.sweep import qwen3next_check  # noqa: E402 - by_scope
from benchmarks.sweep.longcat_check import (  # noqa: E402 - the same helpers
    decode_program_ms, dist)

CELL = "serve-xing4-prefill-open"
SCOPE = re.compile(r"(hc\.(?:mix|read|write|sum)|mla\.(?:project|attend|out)"
                   r"|ffn\.dense|moe\.(?:route|dispatch|experts|combine"
                   r"|shared))")


def mix_variant(**how):
    """The reference's ``hyper_mix`` with one thing wrong."""
    import jax
    import jax.numpy as jnp

    def hyper_mix(cfg, X, phi, b, alpha):
        T, n, _ = X.shape
        eps = cfg["hc_eps"]
        phi, b, alpha = (a.astype(jnp.float32) for a in (phi, b, alpha))
        flat = X[:, 0] if how.get("one_row") else X.reshape(T, -1)
        m = (flat @ phi[:flat.shape[1]]) * jax.lax.rsqrt(
            jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
        pre = alpha[0] * m[:, :n] + b[:n]
        pre = pre if how.get("no_sigmoid") else jax.nn.sigmoid(pre)
        post = (1.0 if how.get("no_two") else 2.0) * jax.nn.sigmoid(
            alpha[1] * m[:, n:2 * n] + b[n:2 * n])
        R = alpha[2] * m[:, 2 * n:] + b[2 * n:]
        if not how.get("no_clamp"):
            R = jnp.clip(R, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"])
        M = jnp.exp(R).reshape(T, n, n)
        if how.get("identity"):
            return pre, post, jnp.broadcast_to(jnp.eye(n), M.shape)
        for _ in range(how.get("iters", cfg["hc_sinkhorn_iters"])):
            M = M / (M.sum(axis=2, keepdims=True) + eps)
            if not how.get("rows_only"):
                M = M / (M.sum(axis=1, keepdims=True) + eps)
        return pre, post, M
    return hyper_mix


def route_variant(**how):
    """The reference's ``route`` with one thing wrong."""
    import jax
    import jax.numpy as jnp

    def route(cfg, h, p):
        E = cfg["n_routed_experts"]
        k = cfg["num_experts_per_tok"] - how.get("fewer", 0)
        s = jax.nn.sigmoid(h @ p["router"].astype(jnp.float32))
        _, top_e = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), k)
        top_w = jnp.take_along_axis(s, top_e, axis=-1)
        if not how.get("no_renorm"):
            top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
        if not how.get("no_scaling"):
            top_w = top_w * cfg["routed_scaling_factor"]
        chosen = top_e[:, :, None] == jnp.arange(E)[None, None, :]
        return jnp.sum(jnp.where(chosen, top_w[:, :, None], 0.0), axis=1)
    return route


def _nothing(*a):
    import jax.numpy as jnp

    return jnp.zeros_like(a[-2])  # (cfg, h, p) or (h, p): h's shape


def eight_bit(exponent, mantissa):
    """``_w``: every matrix the reference multiplies rounded to an 8-bit
    float where it is widened (vectors: gains, biases, alphas, stay);
    reduce_precision and not a pair of casts, which the compiler may drop as
    excess precision."""
    import jax
    import jax.numpy as jnp

    def rounded(a):
        a = a.astype(jnp.float32)
        return a if a.ndim < 2 else jax.lax.reduce_precision(
            a, exponent, mantissa)
    return rounded


def _plain_frequencies(cfg):
    import jax.numpy as jnp

    D = cfg["qk_rope_head_dim"]
    return cfg["rope_theta"] ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)


def _rounded_write_back(H_res, H_post, X, y):
    import jax.numpy as jnp

    out = jnp.einsum("tij,tjd->tid", H_res, X) \
        + H_post[:, :, None] * y[:, None, :]
    return out.astype(jnp.bfloat16).astype(jnp.float32)


# the reference computed another way: the part functions to put in its place
# (``swapped_phis`` changes the weights it reads, not a function)
WRONG = {
    "matrices_8bit_e5m2": {"_w": eight_bit(5, 2)},
    "matrices_8bit_e4m3": {"_w": eight_bit(4, 3)},
    "h_res_the_identity": {"hyper_mix": mix_variant(identity=True)},
    "one_sinkhorn_iteration": {"hyper_mix": mix_variant(iters=1)},
    "columns_never_normalised": {"hyper_mix": mix_variant(rows_only=True)},
    "h_post_without_its_2": {"hyper_mix": mix_variant(no_two=True)},
    "h_pre_without_the_sigmoid": {"hyper_mix": mix_variant(no_sigmoid=True)},
    "mix_from_one_row_of_the_stream": {
        "hyper_mix": mix_variant(one_row=True)},
    "plain_rotation_for_yarn": {"yarn_frequencies": _plain_frequencies},
    "softmax_factor_left_out": {
        "softmax_scale": lambda cfg: (cfg["qk_nope_head_dim"]
                                      + cfg["qk_rope_head_dim"]) ** -0.5},
    "stream_rounded_to_bfloat16": {"write_back": _rounded_write_back},
    "swapped_phis": {},
    # the routed MLP's
    "routed_scaling_factor_left_out": {"route": route_variant(no_scaling=1)},
    "renormalisation_left_out": {"route": route_variant(no_renorm=1)},
    "top_3_for_top_4": {"route": route_variant(fewer=1)},
    "shared_expert_left_out": {"shared_expert": _nothing},
    "routed_sum_dropped": {"experts": _nothing},
}


@contextlib.contextmanager
def wrong_reference(ref, name):
    """The reference module ``ref`` wrong the way ``name`` says, for the
    time of the block."""
    was = {attr: getattr(ref, attr) for attr in WRONG[name]}
    for attr, fn in WRONG[name].items():
        setattr(ref, attr, fn)
    try:
        yield
    finally:
        for attr, fn in was.items():
            setattr(ref, attr, fn)


def swapped_phis(params):
    """The attention's and the MLP's ``phi`` swapped in every block."""
    return {**params, "layers": {
        kind: {**tree, "hc_phi": tree["hc_phi"][:, ::-1]}
        for kind, tree in params["layers"].items()}}


def _at_highest(fn):
    import jax

    def run(*a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)

    return jax.jit(run)


def parts(file, cfg, params, seed: int, rows: int) -> dict:
    import dataclasses
    import importlib
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops.layers import rotary_embedding

    ref = importlib.import_module(file["reference"])
    n, d = cfg.hc_mult, cfg.dim
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    X = jax.random.normal(k1, (rows, n, d), jnp.float32) \
        * jnp.asarray([1.0, 0.5, 2.0, 0.25])[None, :n, None]
    flat = X.reshape(1, rows, n * d)
    routed = jax.tree.map(lambda a: a[1], params["layers"]["latent"])
    hc = tuple(routed[w][0] for w in ("hc_phi", "hc_b", "hc_alpha"))
    out = {"rows": rows}
    pre, post, res, error = jax.jit(partial(llama.hyper_mix, cfg))(*hc, flat)
    want = _at_highest(partial(ref.hyper_mix, file))(X, *hc)
    out["mix"] = {"h_pre": dist(pre[:, 0].T, want[0]),
                  "h_post": dist(post[:, 0].T, want[1]),
                  "h_res": dist(jnp.moveaxis(res[:, :, 0], -1, 0), want[2]),
                  "h_pre_range": [float(pre.min()), float(pre.max())],
                  "h_post_range": [float(post.min()), float(post.max())]}
    out["sinkhorn_error_after"] = {
        str(it): float(jax.jit(partial(llama.hyper_mix, dataclasses.replace(
            cfg, hc_sinkhorn_iters=it)))(*hc, flat)[3]) for it in (1, 5, 20)}
    y = jax.random.normal(k2, (rows, d), jnp.float32)
    seen = {}

    def sub(h):
        seen["h"] = h
        return y[None], None

    got, _, _ = llama.hyper_connected(cfg, hc, flat, sub)
    out["read_out"] = dist(seen["h"][0], _at_highest(ref.read_out)(
        want[0], X))
    out["write_back"] = dist(got.reshape(rows, n, d), _at_highest(
        ref.write_back)(want[2], want[1], X, y))
    out["collapse"] = dist(llama.collapse_stream(cfg, flat)[0], X.sum(axis=1))
    # the rotation at the far end of the longest prompt
    x = jax.random.normal(k2, (1, 2, 3, cfg.qk_rope_head_dim), jnp.float32)
    at = jnp.asarray([[15999, 16000]], jnp.int32)
    turned, _ = rotary_embedding(x, x, at, cfg.rope_theta, interleaved=True,
                                 inv_freq=llama.yarn_frequencies(cfg))
    far = ref._rope(jnp.zeros((16001, 3, x.shape[-1])).at[15999:].set(x[0]),
                    ref.yarn_frequencies(file))[15999:]
    plain = ref._rope(jnp.zeros((16001, 3, x.shape[-1])).at[15999:].set(x[0]),
                      _plain_frequencies(file))[15999:]
    out["yarn_rotation_at_16000"] = {"rel_err": dist(turned[0], far),
                                     "plain_rotation": dist(turned[0], plain)}
    out["softmax_factor"] = llama.yarn_softmax_factor(cfg)
    # one latent attention and one routed MLP, compute type against float32
    h = jax.random.normal(k1, (rows, d), jnp.float32)
    cd = cfg.dtype
    got = jax.jit(lambda h: llama._latent_half(
        cfg, routed, h[None].astype(cd), llama.positions_of(1, rows),
        partial(llama.attend_latent_expanded, cfg))[0][0])(h)
    out["attention"] = dist(got, _at_highest(partial(ref.attention, file))(
        h.astype(cd).astype(jnp.float32), routed))
    got = jax.jit(lambda h: llama._mlp_half(
        cfg, routed, h[None].astype(cd))[0][0])(h)
    want = _at_highest(partial(ref.moe, file))(
        h.astype(cd).astype(jnp.float32), routed)
    out["routed_mlp"] = dist(got, want)
    out["routed_mlp_without_the_shared_expert"] = dist(
        got.astype(jnp.float32) - _at_highest(ref.shared_expert)(
            h.astype(cd).astype(jnp.float32), routed), want)
    return out


def wrong_module(ref, name):
    """A module for ``spec.resolve`` whose ``logits_one`` is the reference's
    computed the way ``name`` says (``sound``: as it is)."""
    import types

    def logits_one(cfg, params, tokens):
        if name == "sound":
            return ref.logits_one(cfg, params, tokens)
        with wrong_reference(ref, name):  # in force while jit traces
            return ref.logits_one(
                cfg, swapped_phis(params) if name == "swapped_phis"
                else params, tokens)

    module = types.ModuleType(f"{__name__}.{name}")
    module.logits_one = logits_one
    sys.modules[module.__name__] = module
    return module.__name__


def harness_check(file, traffic, engine, seed: int, names) -> dict:
    """``BenchLM._prepare`` itself on ``engine``, a time a name: the traffic
    cut to the check's own prompt (the shapes it warms are then the three
    the check uses), the configuration's ``reference`` the module of that
    name; ``correct`` by ``serve_cell.run``'s rule."""
    import importlib

    from benchmarks.lib.serve_cell import (
        BenchLM, check_prompt_len, shapes_of)

    ref = importlib.import_module(file["reference"])
    ps = engine.page_size
    n = check_prompt_len(shapes_of(traffic, ps), ps)
    only = dict(traffic, prompt_tokens={"dist": "const", "value": n},
                output_tokens={"dist": "const", "value": 4})
    tol = file["correct"]["serve_logits_rel_tol"]
    out = {"serve_logits_rel_tol": tol}
    for name in names:
        lm = BenchLM({"config": dict(file, reference=wrong_module(ref, name)),
                      "traffic": only, "seed": seed})
        lm._engine = engine
        prepared = lm._prepare()
        assert prepared["check_prompt_tokens"] == n, prepared
        out[name] = {"rel_err": prepared["rel_err"],
                     "max_abs_logit": prepared["max_abs_logit"],
                     "correct": bool(max(prepared["rel_err"]) <= tol)}
    return out


def by_scope(engine, kind: str, n_pages: int) -> dict:
    """``qwen3next_check.by_scope`` under this model's scopes."""
    was, qwen3next_check.SCOPE = qwen3next_check.SCOPE, SCOPE
    try:
        return qwen3next_check.by_scope(engine, kind, n_pages)
    finally:
        qwen3next_check.SCOPE = was


def layouts(cfg, positions: int, calls: int = 5) -> dict:
    """One sublayer's hyper-connection passes around an identity sublayer,
    in the program's layout and with the rows as a dimension of their own."""
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    n, d = cfg.hc_mult, cfg.dim
    leaves = llama._init_hyper(cfg, 1, jax.random.PRNGKey(0))
    hc = tuple(leaves[w][0, 0] for w in ("hc_phi", "hc_b", "hc_alpha"))

    def flat(x):
        return llama.hyper_connected(cfg, hc, x, lambda h: (h, None))[0]

    def rowed(x):  # [1, T, n, d]: the same arithmetic, rows a dimension
        pre, post, res, _ = llama.hyper_mix(cfg, *hc, x.reshape(
            *x.shape[:2], -1))
        x32 = x.astype(jnp.float32)
        h = jnp.einsum("jbt,btjd->btd", pre, x32).astype(x.dtype)
        return (jnp.einsum("ijbt,btjd->btid", res, x32) + jnp.einsum(
            "ibt,btd->btid", post, h.astype(jnp.float32))).astype(x.dtype)

    out = {}
    for name, fn, shape in (("flat", flat, (1, positions, n * d)),
                            ("rows", rowed, (1, positions, n, d))):
        for dtype in ("float32", "bfloat16"):
            x = jax.random.normal(jax.random.PRNGKey(1), shape,
                                  jnp.dtype(dtype))
            compiled = jax.jit(fn).lower(x).compile()
            jax.block_until_ready(compiled(x))
            t0 = time.perf_counter()
            for _ in range(calls):
                y = compiled(x)
            jax.block_until_ready(y)
            m = compiled.memory_analysis()
            out[f"{name}_{dtype}"] = {
                "ms_a_call": 1e3 * (time.perf_counter() - t0) / calls,
                "stream_bytes": x.nbytes,
                "temp_bytes": m.temp_size_in_bytes,
                "output_bytes": m.output_size_in_bytes}
    return out


def one_seed(args, bundle, file, cfg, seed_arg: int, skip: set) -> dict:
    import jax

    from benchmarks.lib import spec, traffic as traffic_mod
    from ray_tpu.models import llama

    seed = traffic_mod.fold_seed(seed_arg)
    dev = jax.devices()[0]
    out = {"seed": seed_arg, "seeded_scales": dict(cfg.seeded_scales),
           "hc_init": dict(llama.HC_INIT),
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    out_dir = os.path.join(ROOT, "chiprun_out", "xing4_check")
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
    rows = args.rows if not args.rehearsal else 96
    if "parts" not in skip:
        keep("parts", lambda: parts(file, cfg, engine.params, seed, rows))
    if "check" not in skip:
        keep("check", lambda: harness_check(
            file, bundle["traffic"], engine, seed,
            ["sound"] + ([] if "faults" in skip else args.wrong)))
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
    if "layouts" not in skip:
        keep("layouts", lambda: layouts(
            cfg, 16384 if not args.rehearsal else 64))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="4000000052",
                    help="comma list: one engine and one result a seed")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU: debugs this script only")
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--skip", default="", help="comma list: parts,check,"
                    "faults,time,layouts")
    ap.add_argument("--wrong", default=",".join(WRONG),
                    help="comma list: the wrong ways the check reads")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from ray_tpu.util import compile_cache

    compile_cache.configure(os.environ)
    import jax

    from benchmarks.lib import spec
    from ray_tpu.util import flight_recorder as fr

    fr.configure(enabled=True)
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
