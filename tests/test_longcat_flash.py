"""LongCat-Flash-Chat's layer through the program: the shortcut-connected
double layer, latent attention expanded (the full forward, prefill) and
absorbed over a latent page store (decode), and a router with identity
experts, all at small widths on the CPU against the plain reference
(``benchmarks/reference/longcat_flash_decoder.py``), seeded weights."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import longcat_flash_decoder as ref
from jitted import forward, init_params, reference
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.layers import rotary_embedding
from ray_tpu.ops.moe import routed_mlp

CELL = "serve-longcatflash-prefill-open"
# the file's keys at test widths: 8 real experts (4 held), 4 identity ones
FILE = {
    "vocab_size": 128, "hidden_size": 64, "ffn_hidden_size": 96,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "qk_nope_head_dim": 8, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 4, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "attention_method": "MLA",
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
    "router_experts": 8, "first_expert": 0, "layer_pattern": "SS",
    "norm_topk_prob": False, "tie_word_embeddings": False,
}
FIELDS = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "LongCat-Flash-Chat.json"))["program"]["fields"]


def program_cfg(dtype=jnp.float32, **file_keys):
    """The program's config from the file's keys, as the harness maps them."""
    file = dict(FILE, **file_keys)
    return dataclasses.replace(
        LlamaConfig(**{field: file[key] for field, key in FIELDS.items()}),
        dtype=dtype, remat=False)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with norms and the choice bias off their defaults, so
    that a norm or a bias left out shows."""
    p = init_params(program_cfg(), jax.random.PRNGKey(7))
    k = iter(jax.random.split(jax.random.PRNGKey(8), 8))
    layers = dict(p["layers"]["scmoe"])
    for name in ("attn_norm", "mlp_norm", "q_norm", "kv_norm"):
        layers[name] = 1 + 0.2 * jax.random.normal(next(k), layers[name].shape)
    layers["router_bias"] = 0.003 * jax.random.normal(
        next(k), layers["router_bias"].shape)
    return dict(p, layers={"scmoe": layers})


def hidden(seed, seq=24, batch=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (batch, seq, 64))


def close(got, want, rtol=2e-5):
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=rtol * scale)


def double_layer(cfg, x, p, i):
    """The program's layer ``i`` with expanded attention: one program (the
    reference's side is ``jitted.reference``)."""
    return jax.jit(lambda x, p: llama.shortcut_layer(
        cfg, x, p, i, llama.positions_of(1, x.shape[1]),
        lambda j, *a: llama.attend_latent_expanded(cfg, *a)))(x, p)


def logits_one(params, toks):
    return reference(lambda p, t: ref.logits_one(FILE, p, t), params,
                     jnp.asarray(toks))


# --- (a) the double layer ---------------------------------------------------- #


def one_layer(p, i):
    return jax.tree.map(lambda a: a[i], p["layers"]["scmoe"])


@pytest.mark.parametrize("i", [0, 1])
def test_double_layer_is_the_references(params, i):
    cfg, x = program_cfg(), hidden(1)
    got, stats, latents = double_layer(cfg, x, params["layers"]["scmoe"], i)
    want = reference(lambda x, p: ref.layer(FILE, x, p, i), x[0],
                     params["layers"]["scmoe"])
    close(got[0], want)
    assert latents.shape == (2, 1, 24, 16 + 8)
    assert 0.0 < float(stats["zero_share"]) < 1.0


def test_the_routed_sum_waits_for_the_end_of_the_layer(params):
    """The topology: the program's layer is the reference's, and NOT the
    layer that adds ``m`` before attention 1 (which the second attention
    and feed-forward would then see): that one lies far off."""
    cfg, x, p = program_cfg(), hidden(2), params["layers"]["scmoe"]
    eps = FILE["rms_norm_eps"]

    def early(x):  # m joins the stream with FFN_0's output
        a0 = x + ref.mla(FILE, ref._rms_norm(x, p["attn_norm"][0, 0], eps),
                         p, (0, 0))
        h0 = ref._rms_norm(a0, p["mlp_norm"][0, 0], eps)
        b0 = a0 + ref.ffn(h0, p["ffn_gate"], p["ffn_up"], p["ffn_down"],
                          (0, 0)) + ref.moe(FILE, h0, p, 0)
        a1 = b0 + ref.mla(FILE, ref._rms_norm(b0, p["attn_norm"][0, 1], eps),
                          p, (0, 1))
        return a1 + ref.ffn(ref._rms_norm(a1, p["mlp_norm"][0, 1], eps),
                            p["ffn_gate"], p["ffn_up"], p["ffn_down"], (0, 1))

    got = double_layer(cfg, x, p, 0)[0][0]
    want, wrong = reference(lambda x: (ref.layer(FILE, x, p, 0), early(x)),
                            x[0])
    close(got, want)
    off = float(jnp.max(jnp.abs(wrong - want)) / jnp.max(jnp.abs(want)))
    assert off > 0.01, off


def test_forward_is_the_references_logits(params):
    cfg = program_cfg()
    toks = np.random.RandomState(3).randint(0, 128, size=(2, 20))
    got = forward(cfg, params, toks)
    for row in range(2):
        close(got[row], logits_one(params, toks[row]), 5e-5)


def test_num_params_counts_the_tree_and_the_file():
    cfg = program_cfg()
    tree = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(tree))
    file = spec.cell_bundle(CELL)["config"]
    real = spec.program_config(file)
    assert real.num_params() == 5_172_749_312
    assert (real.kinds, real.latent_row) == ("SSSS", 576)
    # every published width, unchanged, and the cut as the issue states it
    catalog = {"hidden_size": 6144, "ffn_hidden_size": 12288,
               "expert_ffn_hidden_size": 2048, "num_attention_heads": 64,
               "kv_lora_rank": 512, "q_lora_rank": 1536,
               "qk_rope_head_dim": 64, "v_head_dim": 128,
               "qk_nope_head_dim": 128, "routed_scaling_factor": 6,
               "zero_expert_num": 256, "moe_topk": 12, "rope_theta": 10000000,
               "max_position_embeddings": 131072, "rms_norm_eps": 1e-5}
    assert {k: file[k] for k in catalog} == catalog
    assert (file["num_layers"], file["n_routed_experts"],
            file["vocab_size"]) == (4, 16, 16384)
    assert file["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                 "vocab_size": 131072}
    assert file["deployment"]["chips_sharing_a_layer"] == 32


def test_a_double_layer_needs_its_widths():
    with pytest.raises(ValueError, match="latent ranks"):
        LlamaConfig(layer_pattern="S", n_layers=1, num_experts=2)
    with pytest.raises(ValueError, match="identity experts"):
        LlamaConfig(zero_experts=2, num_experts=2, experts_per_token=1)


# --- (b) latent attention: expanded, absorbed, rotated ---------------------- #


def sublayer(params, i, j):
    return {w: params["layers"]["scmoe"][w][i, j] for w in (
        "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")}


def latent_half(cfg, p, h, positions, attend=llama.attend_latent_expanded):
    """``llama._latent_half`` as one program; ``attend`` takes ``cfg``
    first."""
    return jax.jit(lambda p, h, positions: llama._latent_half(
        cfg, p, h, positions, lambda *a: attend(cfg, *a)))(p, h, positions)


@pytest.mark.parametrize("at", [(0, 0), (1, 1)])
def test_latent_half_is_the_references_mla(params, at):
    cfg, h = program_cfg(), hidden(4)
    got, latent = latent_half(cfg, sublayer(params, *at), h,
                              llama.positions_of(1, 24))
    close(got[0], reference(lambda h, p: ref.mla(FILE, h, p, at), h[0],
                            params["layers"]["scmoe"]))
    assert latent.shape == (1, 24, 24)


@pytest.mark.parametrize("block", [4, 24])
def test_queries_in_blocks_change_nothing(params, block, monkeypatch):
    cfg, h = program_cfg(), hidden(5)
    outs = []
    for size in (block, 512):
        monkeypatch.setattr(llama, "LATENT_QUERY_BLOCK", size)
        outs.append(latent_half(cfg, sublayer(params, 0, 0), h,
                                llama.positions_of(1, 24))[0])
    close(outs[0], outs[1], 1e-6)


def test_absorbed_attention_over_latent_rows_is_the_expanded(params):
    """Decode's attend (no key or value expanded: the query absorbs
    ``Wk``, the output ``Wv``) against rows cached by the full pass gives
    the full pass's last position, pad rows masked."""
    cfg, h = program_cfg(), hidden(6, seq=13)
    p = sublayer(params, 1, 0)
    full, latent = latent_half(cfg, p, h, llama.positions_of(1, 13))
    cache = jnp.concatenate([latent[0, :12], jnp.full((4, 24), 1e3)])
    got, row = latent_half(
        cfg, p, h[:, 12:], jnp.full((1, 1), 12, jnp.int32),
        lambda cfg, *a: llama._attend_latent_cached(cfg, cache, 12, *a))
    close(got[0, 0], full[0, 12])
    close(row[0, 0], latent[0, 12], 1e-6)


def test_rotation_takes_the_pairing_it_is_asked_for():
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 7, 3, 8))
    pos = llama.positions_of(1, 7)
    rotate = jax.jit(lambda x, **kw: rotary_embedding(x, x, pos, 100.0,
                                                      **kw)[0],
                     static_argnames="interleaved")
    close(rotate(x, interleaved=True)[0],
          reference(lambda x: ref._rope(x, 100.0), x[0]), 1e-6)
    # the half-split pairing is the interleaved one on permuted channels
    perm = jnp.array([0, 4, 1, 5, 2, 6, 3, 7])
    close(rotate(x)[0][..., perm],
          reference(lambda x: ref._rope(x, 100.0), x[0][..., perm]), 1e-6)


# --- (c) the router: identity experts, choice bias, shares ------------------- #


def routed(cfg, h, p, **kw):
    args = dict(top_k=cfg.experts_per_token, scale=cfg.routed_scale,
                zero_experts=cfg.zero_experts,
                held=(cfg.first_expert, cfg.num_experts))
    args.update(kw)
    return jax.jit(lambda h, p: routed_mlp(
        h, p["router"], p["w_gate"], p["w_up"], p["w_down"],
        choice_bias=p["router_bias"], **args))(h, p)


def test_routed_branch_is_the_references(params):
    cfg, h = program_cfg(), hidden(10, seq=64)
    got, stats = routed(cfg, h, one_layer(params, 0))
    close(got[0], reference(lambda h, p: ref.moe(FILE, h, p, 0), h[0],
                            params["layers"]["scmoe"]))
    shares = float(stats["held_share"]) + float(stats["zero_share"])
    assert 0.0 < shares < 1.0 and float(stats["dropped"]) == 0.0


def test_all_identity_router_returns_scaled_input(params):
    """A choice bias that sends every token to identity experts alone:
    ``m = 6 * sum_j p_j h``, no row, no product, nothing held."""
    cfg, h = program_cfg(), hidden(11, seq=16)
    p = dict(one_layer(params, 0))
    p["router_bias"] = jnp.zeros(12).at[8:].set(10.0)
    got, stats = routed(cfg, h, p)
    probs = jax.nn.softmax(jnp.dot(h[0], p["router"], precision="highest"))
    top3 = jnp.sort(probs[:, 8:], axis=-1)[:, -3:].sum(-1)
    close(got[0], 6.0 * top3[:, None] * h[0])
    assert (float(stats["zero_share"]), float(stats["held_share"])) == (1, 0)
    # and with the experts' weights gone the layer is the identity part
    zeroed = dict(one_layer(params, 0), w_down=jnp.zeros((4, 32, 64)))
    close(routed(cfg, h, zeroed)[0], ref_identity_part(h, zeroed))


@jax.jit
def ref_identity_part(h, p):
    probs = jax.nn.softmax(jnp.dot(h, p["router"], precision="highest"))
    _, chosen = jax.lax.top_k(probs + p["router_bias"], 3)
    w = jnp.take_along_axis(probs, chosen, axis=-1)
    return 6.0 * jnp.sum(jnp.where(chosen >= 8, w, 0.0), -1)[..., None] * h


def test_softmax_choice_bias_picks_biased_and_weighs_unbiased(params):
    cfg, h = program_cfg(), hidden(12, seq=16)
    p = dict(one_layer(params, 0))
    p["router_bias"] = jnp.zeros(12).at[1].set(5.0)  # expert 1: always chosen
    got, stats = routed(cfg, h, p)
    close(got[0], reference(lambda h, p: ref.moe(FILE, h, p, 0), h[0],
                            jax.tree.map(lambda a: a[None], p)))
    unbiased, _ = routed(cfg, h, one_layer(params, 0))
    assert float(jnp.max(jnp.abs(got - unbiased))) > 1e-3
    # weighed by the UNBIASED score: expert 1 alone, chosen by every token
    only = dict(p, w_down=p["w_down"].at[jnp.array([0, 2, 3])].set(0.0))
    probs = jax.nn.softmax(jnp.dot(h[0], p["router"], precision="highest"))
    one = reference(lambda h: ref.ffn(h, p["w_gate"], p["w_up"], p["w_down"],
                                      (1,)), h[0])
    close(routed(cfg, h, only)[0][0] - ref_identity_part(h[0], p),
          6.0 * probs[:, 1:2] * one, 1e-4)


@pytest.mark.parametrize("seq", [1, 64])
def test_the_shares_partial_sums_add_up_to_the_uncut_layer(params, seq):
    """The guide's share test: 4 shares of 2 real experts each (the cell
    is 32 of 16), the identity part (which every chip computes alike)
    counted once, add up to the layer with all 8 real experts here. ``seq``
    1 is a decode call's dozen assignments in one block."""
    cfg, h = program_cfg(), hidden(13, seq=seq)
    k = jax.random.split(jax.random.PRNGKey(14), 3)
    whole = dict(one_layer(params, 0),
                 w_gate=jax.random.normal(k[0], (8, 64, 32)) / 8,
                 w_up=jax.random.normal(k[1], (8, 64, 32)) / 8,
                 w_down=jax.random.normal(k[2], (8, 32, 64)) / 6)
    uncut, stats = routed(cfg, h, whole, held=None)
    identity = ref_identity_part(h, whole)
    total, held_share = identity, 0.0
    for first in range(0, 8, 2):
        part = {w: whole[w][first:first + 2]
                for w in ("w_gate", "w_up", "w_down")}
        y, st = routed(cfg, h, dict(whole, **part), held=(first, 2))
        total = total + (y - identity)
        held_share += float(st["held_share"])
    close(total, uncut, 1e-5)
    assert held_share == pytest.approx(float(stats["held_share"]), abs=1e-6)
    assert held_share + float(stats["zero_share"]) == pytest.approx(1.0)


# --- (c') experts that come as their kind's whole stack ---------------------- #

# LongCat's form (a share of 4 of 24 real experts, 8 identity experts, a
# choice bias) and the plain one (16 experts, all here), both top-12 as the
# cell is; ``rows``: a prefill's tokens, or SIXTEEN decode calls of one
# token's dozen assignments
FORMS = {"longcat": dict(wide=32, count=4, held=(4, 4), zero_experts=8),
         "plain": dict(wide=16, count=16)}


def stack_case(form, rows, depth):
    wide, count = FORMS[form]["wide"], FORMS[form]["count"]
    k = jax.random.split(jax.random.PRNGKey(21), 6)
    weights = (jax.random.normal(k[0], (depth, count, 64, 32)) / 8,
               jax.random.normal(k[1], (depth, count, 64, 32)) / 8,
               jax.random.normal(k[2], (depth, count, 32, 64)) / 6)
    bias = (0.003 * jax.random.normal(k[3], (wide,))
            if form == "longcat" else None)
    h = jax.random.normal(k[5], (16, 1, 64) if rows == "decode" else (48, 64))
    return h, jax.random.normal(k[4], (64, wide)) / 8, bias, weights


def routed_sum(form, h, router, bias, weights, layer):
    """``sum(y * y)`` and the stats of the routed branch: one call on all
    of ``h``'s rows, or a call a row (a decode call each)."""
    kw = {k: v for k, v in FORMS[form].items() if k not in ("wide", "count")}

    def call(rows):
        return routed_mlp(rows, router, *weights, top_k=12, scale=6.0,
                          choice_bias=bias, layer=layer, **kw)

    y, stats = call(h) if h.ndim == 2 else jax.lax.map(call, h)
    return jnp.sum(y * y), (y, stats)


@pytest.mark.parametrize("depth,i", [(3, 0), (3, 1), (3, 2), (1, 0)])
@pytest.mark.parametrize("rows", ["prefill", "decode"])
@pytest.mark.parametrize("form", ["longcat", "plain"])
def test_a_stack_with_its_layers_number_is_the_layer_cut_out(form, rows,
                                                             depth, i):
    """``routed_mlp`` given the kind's stacked experts and ``layer=i``
    (traced, as the engine's scan has it) against given ``stack[i]``: the
    same values and stats for every layer of a stack of three, and for the
    full forward's stack of one the same gradients too."""
    from jitted import value_and_grad

    h, router, bias, stack = stack_case(form, rows, depth)

    def stacked(h, router, stack, i):
        return routed_sum(form, h, router, bias, stack, i)

    def cut_out(h, router, stack, i):
        return routed_sum(form, h, router, bias, [w[i] for w in stack], None)

    at = jnp.int32(i)
    (_, (got, stats)), d_got = value_and_grad(
        stacked, h, router, stack, at, has_aux=True, argnums=(0, 1, 2))
    (_, (want, st)), d_want = value_and_grad(
        cut_out, h, router, stack, at, has_aux=True, argnums=(0, 1, 2))
    assert float(jnp.max(jnp.abs(want))) > 0.1
    close(got, want, 1e-6)
    for name in st:
        np.testing.assert_allclose(stats[name], st[name], rtol=1e-6)
    if form == "longcat" and rows == "decode":
        # calls with NO assignment on a held expert, and calls with some
        assert float(st["held_share"].min()) == 0.0 < float(
            st["held_share"].max())
    if depth == 1:
        for g, w in zip(jax.tree.leaves(d_got), jax.tree.leaves(d_want)):
            close(g, w, 1e-5)


@pytest.mark.parametrize("why", ["another type", "a width filled up"])
def test_a_stack_that_would_be_copied_whole_has_its_layer_cut_out(why):
    """Rows in bfloat16 over a float32 stack, or experts 576 wide (filled
    up to 1024): the grouped product would read a converted or filled COPY
    of the whole stack, so the layer is cut out first, as without
    ``layer``; the values are the same either way."""
    from ray_tpu.ops.moe import expert_groups

    k = jax.random.split(jax.random.PRNGKey(22), 5)
    f = 576 if why == "a width filled up" else 32
    cd = jnp.bfloat16 if why == "another type" else jnp.float32
    stack = (jax.random.normal(k[0], (2, 4, 64, f)) / 8,
             jax.random.normal(k[1], (2, 4, 64, f)) / 8,
             jax.random.normal(k[2], (2, 4, f, 64)) / 24)
    assert expert_groups(stack[1], cd) == 4
    assert expert_groups(stack[1][:, :, :, :32], jnp.float32) == 8
    h = jax.random.normal(k[3], (24, 64)).astype(cd)
    router = jax.random.normal(k[4], (64, 4)) / 8
    got, _ = jax.jit(lambda h, stack, i: routed_mlp(
        h, router, *stack, top_k=2, layer=i))(h, stack, jnp.int32(1))
    want, _ = jax.jit(lambda h, stack: routed_mlp(
        h, router, *(w[1] for w in stack), top_k=2))(h, stack)
    close(got, want, 1e-6)


def test_routed_mlp_takes_a_stack_with_its_layer_and_a_layer_without():
    h, router, _, stack = stack_case("plain", "prefill", 2)
    with pytest.raises(ValueError, match="layer=None"):
        routed_mlp(h, router, *stack, top_k=2)
    with pytest.raises(ValueError, match="layer=1"):
        routed_mlp(h, router, *(w[1] for w in stack), top_k=2, layer=1)


# --- (d) the engine: a latent page store, prefill and decode ---------------- #


@pytest.fixture(scope="module")
def engine(params):
    return llama.LlamaDecodeEngine(program_cfg(), params, n_pages=12,
                                   page_size=8)


def served(engine, toks, n, pages):
    """Prefill ``n`` tokens, then decode the rest: a row of logits each."""
    ps = engine.page_size
    got = [engine.prefill([int(t) for t in toks[:n]], pages[:-(-n // ps)])]
    for j in range(n, len(toks)):
        got.append(engine.decode(j, int(toks[j]), pages[:j // ps + 1]))
    return np.stack(got)


@pytest.mark.parametrize("n", [5, 14, 16])
def test_prefill_then_decode_through_pages_is_the_references(engine, params, n):
    """Across a page boundary (8 positions a page, scattered pages) the
    engine's logits are ``logits_one``'s: prefill expands keys and values,
    decode attends over the latent rows."""
    toks = np.random.RandomState(n).randint(0, 128, size=n + 4)
    got = served(engine, toks, n, [9, 2, 6])
    close(got, logits_one(params, toks)[n - 1:], 5e-5)


def test_the_store_is_one_array_of_latent_rows(engine):
    from ray_tpu.util.metrics import registry
    from test_olmoe import no_page_bytes

    assert llama.page_rows(engine.cfg) == ("latent", [(4, (24,))])
    (store,) = engine.stores
    assert store.shape == (4, 12, 8, 24) and store.dtype == jnp.float32

    def gauge(name):
        return {k[0][1]: v for k, v in registry().local_values(name).items()}

    assert gauge("ray_tpu_serve_engine_page_bytes") == {
        **no_page_bytes(engine.cfg), "latent": 4 * 24 * 4.0}
    # the grouped products run over the stack's 2 x 4 groups
    assert gauge("ray_tpu_serve_engine_expert_groups") == {
        "program": 8.0, "layer": 4.0}
    dense = llama.LlamaDecodeEngine(n_pages=4, page_size=4)
    assert [s.shape for s in dense.stores] == [(2, 4, 4, 2, 16)] * 2
    assert gauge("ray_tpu_serve_engine_page_bytes") == {
        **no_page_bytes(dense.cfg), "kv": 2 * 2 * 2 * 16 * 4.0}
    assert gauge("ray_tpu_serve_engine_expert_groups") == {
        "program": 0.0, "layer": 0.0}


def test_no_program_cuts_a_layers_experts_out_of_the_stack(engine):
    """The engine's prefill and decode programs as LOWERED (before any
    compiler): nothing is sliced or gathered to the shape of one layer's
    experts, ``[4, 64, 32]`` or ``[4, 32, 64]`` here (a copy of 403 MB a
    matrix at the published widths, at every call). Over how many groups
    the grouped products run instead, the engine's gauge says (above)."""
    import re

    pages = np.asarray([1, 2], np.int32)
    calls = {
        "_prefill_fn": (np.zeros((1, 16), np.int32), pages,
                        np.asarray(15, np.int32)),
        "_decode_fn": (np.asarray([1], np.int32), np.asarray(15, np.int32),
                       pages)}
    cut = re.compile(r"stablehlo\.(?:dynamic_slice|slice|gather|"
                     r"dynamic_gather)\b.*->\s*tensor<((?:\d+x)+)f32>")
    for name, args in calls.items():
        text = getattr(engine, name)._fn.lower(
            engine.params, *engine.stores, *args).as_text()
        assert "tensor<8x64x32xf32>" in text  # the stack as 2 x 4 groups
        shapes = {tuple(int(n) for n in m.group(1).split("x") if n)
                  for m in map(cut.search, text.splitlines()) if m}
        assert shapes  # the pattern still reads this lowering
        found = {s for s in shapes
                 if tuple(n for n in s if n != 1) in {(4, 64, 32),
                                                      (4, 32, 64)}}
        assert not found, (name, found)


def test_prefill_reports_where_the_assignments_fell(engine):
    from ray_tpu.util.metrics import registry

    engine.prefill(list(range(20)), [0, 1, 2])
    got = {k[0][1]: v for k, v in registry().local_values(
        "ray_tpu_serve_moe_assignment_share").items()}
    assert set(got) == {"held", "zero", "elsewhere"}
    assert sum(got.values()) == pytest.approx(1.0)
    assert all(0.0 < v < 1.0 for v in got.values())


def test_prefill_says_which_way_its_attention_went(engine):
    """On the CPU every prefill program attends in XLA tiles, and says so
    where it is traced: the gauge by kind and path, the record with why."""
    from ray_tpu.util.metrics import registry

    def gauge():  # the process's: another file's tests may have counted
        return {tuple(v for _, v in sorted(tags)): n for tags, n in
                registry().local_values(
                    "ray_tpu_serve_engine_prefill_attend").items()}

    before = gauge()
    engine.prefill(list(range(30)), [4, 5, 6, 7])  # four pages: a new program
    got = gauge()
    assert got[("latent", "tiles")] >= before.get(
        ("latent", "tiles"), 0.0) + 2.0  # a program's two sublayers
    assert got[("latent", "kernel")] == before.get(("latent", "kernel"), 0.0)
    mine = [r for r in llama.prefill_attend_paths()  # the process's record
            if r["q_shape"] == [1, 32, 4, 16] and r["kind"] == "latent"]
    assert [(r["kind"], r["path"], r["reason"], r["k_shape"])
            for r in mine] == [("latent", "tiles",
                                "backend is 'cpu', not tpu", [1, 32, 4, 8])]


def test_copy_page_copies_latent_rows(engine):
    engine.prefill(list(range(8)), [3])
    engine.copy_page(3, 10)
    store = np.asarray(engine.stores[0])
    assert np.abs(store[:, 3]).max() > 0
    np.testing.assert_array_equal(store[:, 3], store[:, 10])


def test_engine_converts_the_leaves_it_multiplies(params):
    cfg = program_cfg(jnp.bfloat16)
    tree = llama.serving_params(cfg, params)["layers"]["scmoe"]
    f32 = {"attn_norm", "mlp_norm", "q_norm", "kv_norm", "router",
           "router_bias"}
    for name, leaf in tree.items():
        assert leaf.dtype == (jnp.float32 if name in f32 else jnp.bfloat16)
    np.testing.assert_array_equal(
        tree["wkv_b"], params["layers"]["scmoe"]["wkv_b"].astype(jnp.bfloat16))


def test_bfloat16_engine_stays_near_the_reference(params):
    """As the cell runs it: bfloat16 products against the float32
    reference on the engine's own (rounded) weights."""
    engine = llama.LlamaDecodeEngine(program_cfg(jnp.bfloat16), params,
                                     n_pages=8, page_size=8)
    toks = np.random.RandomState(21).randint(0, 128, size=17)
    got = served(engine, toks, 14, [5, 1, 3])
    want = logits_one(engine.params, toks)[13:]
    # at 64 wide a rounded router input that moves one of three choices
    # moves a logit by several percent; a wrong page or an un-rotated key
    # moves it by its own size
    assert float(np.max(np.abs(got - want)) / np.max(np.abs(want))) < 0.15


def test_scheduler_drives_the_latent_engine(engine):
    """``DecodeScheduler`` over the engine, unchanged: greedy tokens are
    the teacher-forced ones, and a repeated prompt is a prefix hit whose
    copied tail page holds latent rows."""
    from ray_tpu.serve.decode import DecodeScheduler
    from test_kv_cache import _run_all

    sched = DecodeScheduler(engine)
    req = {"prompt": [int(t) for t in np.random.RandomState(2).randint(
        0, 128, size=11)], "max_tokens": 6}
    cold = json.loads(_run_all(sched, [("c", req)])["c"][-1][1])
    warm = json.loads(_run_all(sched, [("w", req)])["w"][-1][1])
    assert warm["cached_prefix"] is True
    assert warm["tokens"] == cold["tokens"] and len(cold["tokens"]) == 6
    toks = req["prompt"] + cold["tokens"]
    logits = np.asarray(forward(engine.cfg, engine.params,
                                np.asarray([toks])))[0]
    assert [int(t) for t in logits[10:16].argmax(-1)] == cold["tokens"]


@pytest.mark.parametrize("cfg,why", [
    (dict(layer_pattern="M*", n_layers=2, ssm_heads=2, ssm_head_dim=8,
          ssm_state=8), r"the table lacks \* M; for the 'M' / 'E'"),
    (dict(qk_norm=True), "QK-norm")])
def test_engine_refuses_what_it_does_not_serve(cfg, why):
    with pytest.raises(NotImplementedError, match=why):
        llama.LlamaDecodeEngine(dataclasses.replace(LlamaConfig.debug(),
                                                    **cfg))


# --- (e) the benchmark's files ----------------------------------------------- #


def test_benchmark_files_fit_together_with_the_new_cell():
    from benchmarks.checks import test_yardstick
    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    test_yardstick.test_benchmark_files_fit_together()
    b = spec.cell_bundle(CELL)
    assert (b["cell"]["chips"], b["cell"]["traffic"]) == (
        1, "prefill-open-2048-8192")
    assert sorted(m["name"] for m in b["end_to_end"]) == [
        "setup_s", "ttft_p95_ms"]
    names = {m["name"] for m in b["per_layer"]}
    assert "serve.decode_program_ms" in names and "compile_s" in names
    assert len([n for n in names if n.startswith("serve.")]) == 12
    # the new metric is the new cell's alone: no other cell's line changes
    other = spec.cell_bundle("serve-internlm2-prefill-open")
    assert "serve.decode_program_ms" not in {
        m["name"] for m in other["per_layer"]}
    tr, dep = b["traffic"], b["config"]["deployment"]
    assert (tr["kind"], tr["prompt_tokens"], tr["output_tokens"]) == (
        "open_loop", {"dist": "log_uniform", "min": 2048, "max": 8192},
        {"dist": "const", "value": 16})
    shapes = shapes_of(tr, dep["page_size"])
    assert shapes == {"prefill": list(range(4, 17)),
                      "decode": list(range(5, 18))}
    assert check_prompt_len(shapes, dep["page_size"]) == 2558
    # 8 running sequences of the longest context fit the pool
    assert dep["n_pages"] >= dep["decode_max_batch"] * shapes["decode"][-1]
    assert spec.resolve(b["config"]["reference"] + ":logits_one")
    # the rehearsal's tiny sizes still build the double layer
    tiny = spec.cell_bundle(CELL, rehearsal=True)
    assert spec.program_config(tiny["config"]).kinds == "SSSS"
