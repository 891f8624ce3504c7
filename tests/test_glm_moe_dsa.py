"""GLM-5.2 (``glm_moe_dsa``) through the decode engine at small widths on the
CPU: latent attention UNDER a learned selection that layers share (kinds
``"Y"`` routed + full, ``"Z"`` routed + shared, ``"X"`` dense + full), against
the plain reference (``benchmarks/reference/glm_moe_dsa_decoder.py``):
prefill then decode across a page boundary with ``index_topk`` smaller than
the prompt, the parts one by one, the selection's way from a full layer to
the shared ones behind it (in line, through a scanned run and through a
scanned period), the kernels at the published indexer's shape, the held
ranges' sums, the parameter count, the paths that refuse the kinds by name,
and the benchmark's files. Values are taken under ``jax.jit``."""

import json
import os
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

import jitted  # noqa: E402
from benchmarks.lib import spec  # noqa: E402
from benchmarks.reference import glm_moe_dsa_decoder as ref  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmarks", "configs", "GLM-5.2.json")
CELL = "serve-glm52-prefill-open"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

with open(CONFIG_FILE) as _f:
    PUBLISHED = json.load(_f)

PAGE, TOPK = 8, 8


def small_file(pattern="XXXZZZYZZZY", first=2, layers=5, **over):
    """The published shape, small: the file's own keys at 4 heads of [16 nope
    | 8 rope] that return 24, ranks 48 / 32, an indexer of 4 heads of 16 that
    picks ``TOPK`` = 8, 4 of 32 experts held, top-4 times 2.5 beside a shared
    one. ``pattern`` spells the whole stack in the program's letters; the
    published lists are read off it."""
    keep = ("rms_norm_eps", "norm_topk_prob", "routed_scaling_factor",
            "scoring_func", "n_group", "topk_group", "n_shared_experts",
            "tie_word_embeddings", "router_scoring", "mla_scale_q_lora",
            "mla_scale_kv_lora", "rope_parameters", "rope_theta",
            "seeded_scales", "first_expert")
    file = dict(
        {k: PUBLISHED[k] for k in keep}, hidden_size=64,
        num_hidden_layers=layers, layer_pattern=pattern, first_layer=first,
        indexer_types=["shared" if c == "Z" else "full" for c in pattern],
        mlp_layer_types=["dense" if c == "X" else "sparse" for c in pattern],
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        qk_head_dim=24, v_head_dim=24, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=4, router_experts=32,
        num_experts_per_tok=4, index_n_heads=4, index_head_dim=16,
        index_topk=TOPK, vocab_size=256, max_position_embeddings=512)
    file.update(over)
    return file


FILE = small_file()


def program_cfg(file=FILE, **over):
    kw = {field: file[key]
          for field, key in PUBLISHED["program"]["fields"].items()}
    kw.update({"dtype": jnp.float32, **over})
    cfg = LlamaConfig(**kw)
    for attr, key in PUBLISHED["program"]["check"].items():
        assert getattr(cfg, attr) == file[key], attr
    return cfg


def seeded(cfg):
    """Seeded parameters, the gains, the choice bias and the indexer's
    LayerNorm away from their starting values, so that a misplaced or
    forgotten one shows."""
    p = jitted.init_params(cfg, jax.random.PRNGKey(11))
    rng = np.random.RandomState(5)

    def jiggle(tree, name, lo, hi):
        if name in tree:
            tree[name] = tree[name] + jnp.asarray(
                rng.uniform(lo, hi, tree[name].shape), jnp.float32)

    for tree in p["layers"].values():
        for name in ("attn_norm", "mlp_norm", "q_norm", "kv_norm", "ki_norm",
                     "ki_bias"):
            jiggle(tree, name, -0.5, 0.5)
        jiggle(tree, "router_bias", -0.2, 0.2)
    jiggle(p, "final_norm", -0.5, 0.5)
    return p


@pytest.fixture(scope="module")
def params():
    return seeded(program_cfg())


def worst(got, want):
    """The largest difference over the largest wanted magnitude."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def reference_logits(p, toks, file=FILE, **wrong):
    return np.asarray(jitted.reference(
        partial(ref.logits_one, file, **wrong), p, jnp.asarray(toks)))


def new_engine(p, file=FILE, n_pages=24, **over):
    return llama.LlamaDecodeEngine(program_cfg(file, **over), p,
                                   n_pages=n_pages, page_size=PAGE)


def served(eng, toks, n, pages):
    """Prefill ``n`` tokens into ``pages``, decode the rest: logits rows."""
    rows = [eng.prefill([int(t) for t in toks[:n]], pages[:-(-n // PAGE)])]
    for j in range(n, len(toks)):
        rows.append(eng.decode(j, int(toks[j]), pages[:j // PAGE + 1]))
    return np.stack(rows)


TABLE = [5, 1, 17, 8, 13, 2, 21, 9]  # a page table out of order


# --- (a) the engine against the reference's full forward ------------------- #


@pytest.mark.parametrize("n,more,topk", [
    (23, 9, TOPK),    # the selection, the index store and the gather run
    (7, 14, TOPK),    # a prompt under topk that decodes past it
    (23, 9, 64),      # topk larger than everything: all visible
    (15, 6, 20)])     # the pages' positions pass topk before the real ones do
def test_prefill_then_decode_across_a_page_boundary_is_the_references(
        params, n, more, topk):
    file = small_file(index_topk=topk)
    eng = new_engine(params, file)
    toks = np.random.RandomState(n).randint(0, 256, size=n + more)
    got = served(eng, toks, n, TABLE)
    assert worst(got, reference_logits(params, toks, file)[n - 1:]) < 5e-5
    assert eng.prefill_calls == 1 and eng.decode_calls == more


STACKS = {
    "F S S: a full layer in line, a scanned run of two": ("XZZ", 0, 3),
    "two periods, scanned": ("YZZZYZZZ", 0, 8),
    "a period and a half": ("YZZYZ", 0, 5),
    "all full": ("XYYY", 0, 4),
    "the cell's: one dense, a period behind it": ("XXXZZZYZZZY", 2, 5),
}


@pytest.mark.parametrize("name", list(STACKS))
def test_the_selection_survives_runs_and_periods(name, monkeypatch):
    """Whatever way the walker takes the stack (``_segments``), the engine's
    logits are the reference's, and the same with every layer in line."""
    pattern, first, layers = STACKS[name]
    file = small_file(pattern, first, layers)
    cfg = program_cfg(file)
    p = seeded(cfg)
    toks = np.random.RandomState(2).randint(0, 256, size=29)
    want = reference_logits(p, toks, file)[22:]
    as_walked, in_line = jitted.walked_both_ways(
        lambda: new_engine(p, file), toks, 23, monkeypatch)
    assert worst(as_walked["logits"], want) < 5e-5
    jitted.assert_served_alike(as_walked, in_line)
    assert as_walked["traced"] == llama.traced_layers(cfg) <= layers
    assert in_line["traced"] == layers


def test_the_walker_scans_the_shared_run():
    assert llama._segments("XZZZY") == [("X", 1, False), ("Z", 3, True),
                                        ("Y", 1, False)]
    assert llama._segments("YZZZYZZZ") == [("YZZZ", 2, True)]
    assert llama.traced_layers(program_cfg()) == 3


def test_all_full_and_shared_stacks_differ_and_shared_layers_read_the_full_ones():
    """The same weights for the routed layers' attention and MLPs, once all
    ``full`` and once F S S S: the logits differ, the second's three shared
    layers attend EXACTLY the full layer's S(t), and giving them indexers of
    their own (the wrong way) moves the logits."""
    shared = small_file("YZZZ", 0, 4)
    cfg = program_cfg(shared)
    p = seeded(cfg)
    toks = np.random.RandomState(4).randint(0, 256, size=32)
    _, S = jitted.reference(lambda p, t: ref.hidden_one(shared, p, t), p,
                            jnp.asarray(toks))
    assert all(bool(jnp.all(s == S[0])) for s in S[1:])
    assert [int(n) for n in S[0].sum(-1)] == [min(t + 1, TOPK)
                                              for t in range(32)]
    # all full: layers 1-3 get the shared stack's leaves and indexers
    full = small_file("YYYY", 0, 4)
    q = seeded(program_cfg(full))
    z, y = p["layers"]["dsa_shared"], q["layers"]["dsa_full"]
    tree = dict(q, layers={"dsa_full": {
        w: jnp.concatenate([p["layers"]["dsa_full"][w], z[w]]) if w in z
        else jnp.concatenate([p["layers"]["dsa_full"][w], y[w][1:]])
        for w in y}}, embedding=p["embedding"], lm_head=p["lm_head"],
        final_norm=p["final_norm"])
    want_shared = reference_logits(p, toks, shared)
    want_full = reference_logits(tree, toks, full)
    assert worst(want_full, want_shared) > 0.02
    got = served(new_engine(p, shared), toks, 23, TABLE)
    assert worst(got, want_shared[22:]) < 5e-5
    assert worst(got, want_full[22:]) > 0.02
    got = served(new_engine(tree, full), toks, 23, TABLE)
    assert worst(got, want_full[22:]) < 5e-5
    # the wrong way: shared layers that select for themselves
    wrong = dict(p, layers=dict(p["layers"], dsa_shared=dict(
        z, **{w: y[w][1:] for w in y if w not in z})))
    assert worst(reference_logits(wrong, toks, shared, shared_selects=True),
                 want_shared) > 0.02


@pytest.mark.parametrize("wrong", [
    {"select": False}, {"rotate_index": False}, {"index_from_stream": True},
    {"stale": True}])
def test_the_reference_computed_a_wrong_way_lies_far_off(params, wrong):
    toks = np.random.RandomState(9).randint(0, 256, size=32)
    assert worst(reference_logits(params, toks, **wrong),
                 reference_logits(params, toks)) > 0.01


def test_a_decode_that_gathers_from_another_layers_store_lies_far_off(params):
    toks = np.random.RandomState(6).randint(0, 256, size=32)
    want = reference_logits(params, toks)[22:]
    eng = new_engine(params)
    got = [eng.prefill([int(t) for t in toks[:23]], TABLE[:3])]
    stores = list(eng.stores)
    layout = llama.served_stores(eng.cfg)
    z = [s.kind for s in layout].index("Z")
    stores[z] = stores[z][::-1]  # the shared layers' rows, layers reversed
    eng.stores = tuple(stores)
    for j in range(23, 32):
        got.append(eng.decode(j, int(toks[j]), TABLE[:j // PAGE + 1]))
    assert worst(got[0], want[0]) < 5e-5
    assert worst(np.stack(got[1:]), want[1:]) > 0.01


# --- (b) the parts ---------------------------------------------------------- #


def _normed_rows(T=32, seed=1):
    a = jax.random.normal(jax.random.PRNGKey(seed), (T, 64), jnp.float32)
    return a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True))


def test_the_projections_and_the_indexer_are_the_references(params):
    cfg = program_cfg()
    stack = params["layers"]["dsa_full"]
    p = jax.tree.map(lambda w: w[0], stack)
    a = _normed_rows()
    q, latent, cq = jax.jit(lambda a, p: llama._latent_project(
        cfg, p, a[None], llama.positions_of(1, 32)))(a, p)
    k, v = jax.jit(lambda l, w: llama._latent_heads(cfg, l, w))(
        latent, p["wkv_b"])
    want = jitted.reference(lambda a, s: ref.LAYER["qkv"](FILE, a, s, 0),
                            a, stack)
    for got, w in zip((q[0], k[0], v[0], cq[0]), want):
        assert worst(got, w) < 2e-5
    qi, ki, w = jax.jit(lambda a, cq, p: llama._indexer(
        cfg, p, a[None], llama.positions_of(1, 32), cq=cq))(a, cq, p)
    want = jitted.reference(
        lambda a, cq, s: ref.LAYER["indexer"](FILE, a, cq, s, 0), a, want[3],
        stack)
    for got, w_ in zip((qi[0], ki[0], w[0]), want):
        assert worst(got, w_) < 2e-5
    # the selection: select_top's mask is top_k's, a tie to the lower place
    score = llama.index_scores(qi, ki, w)[0]
    visible = jnp.arange(32)[None, :] <= jnp.arange(32)[:, None]
    mine = llama.select_top(score, visible, TOPK)
    theirs = jitted.reference(lambda *o: ref.LAYER["selection"](FILE, *o),
                              *want)
    assert bool(jnp.all(mine == theirs))


def test_the_attention_under_a_given_selection_is_the_references(params):
    """A SHARED layer's attend: the selection comes in, is used and is
    handed on untouched; no index score is computed."""
    cfg = program_cfg()
    stack = params["layers"]["dsa_shared"]
    p = jax.tree.map(lambda w: w[1], stack)
    assert not {"wqi", "wki", "ww", "ki_norm", "ki_bias"} & set(stack)
    a = _normed_rows(seed=2)
    rng = np.random.RandomState(0)
    S = np.tril(rng.rand(32, 32) < 0.4) | np.eye(32, dtype=bool)
    q, latent, _ = llama._latent_project(cfg, p, a[None],
                                         llama.positions_of(1, 32))
    o, chosen = jax.jit(lambda q, l, w, S: llama.attend_latent_selected(
        cfg, q, l, w, None, S))(q, latent, p["wkv_b"],
                                jnp.asarray(S[None], jnp.int8))
    assert bool(jnp.all(chosen[0] == S))
    rq, rk, rv, _ = ref.qkv(FILE, a, stack, 1)
    want = jitted.reference(lambda *o: ref.LAYER["attention"](
        FILE, *o, stack, 1), rq, rk, rv, jnp.asarray(S))
    assert worst(o.reshape(32, -1) @ p["wo"], want) < 2e-5


def test_the_mlps_are_the_references(params):
    cfg = program_cfg()
    m = _normed_rows(seed=3)
    routed = params["layers"]["dsa_full"]
    p = {w: a if w in ("w_gate", "w_up", "w_down") else a[0]
         for w, a in routed.items()}
    got, stats = jax.jit(lambda m, p: llama._mlp_half(
        cfg, p, m[None], layer=0))(m, p)
    assert worst(got[0], jitted.reference(
        lambda m, s: ref.LAYER["moe"](FILE, m, s, 0), m, routed)) < 2e-5
    assert 0.0 < float(stats["held_share"]) < 1.0
    dense = params["layers"]["dsa_dense"]
    got = jax.jit(lambda m, p: llama._dense_mlp(cfg, p, m))(
        m, jax.tree.map(lambda w: w[0], dense))
    assert worst(got, jitted.reference(
        lambda m, s: ref.LAYER["dense"](FILE, m, s, 0), m, dense)) < 2e-5


def test_the_sixteen_shares_routed_parts_add_up_to_the_uncut_layers():
    """Sixteen chips hold two of 32 experts each: their routed sums, the
    shared expert counted ONCE, are the layer with every expert here."""
    whole = small_file("YZ", 0, 2, n_routed_experts=32)
    p = seeded(program_cfg(whole))
    stack = p["layers"]["dsa_full"]
    m = _normed_rows(seed=4)
    want = jitted.reference(lambda m, s: ref.moe(whole, m, s, 0), m, stack)
    weight = ref.route(whole, m, stack, 0)
    parts = sum(jitted.reference(
        lambda m, w, s, first=first: ref.experts(
            whole, m, w, {k: a[:, first:first + 2] if k in (
                "w_gate", "w_up", "w_down") else a for k, a in s.items()},
            0, first, 2), m, weight, stack) for first in range(0, 32, 2))
    assert worst(parts + ref.shared_expert(m, stack, 0), want) < 2e-5
    # and the program's held range is the reference's share
    held = small_file("YZ", 0, 2, n_routed_experts=2, first_expert=6)
    cut = {k: a[:, 6:8] if k in ("w_gate", "w_up", "w_down") else a
           for k, a in stack.items()}
    cfg = program_cfg(held)
    got, _ = jax.jit(lambda m, p: llama._mlp_half(cfg, p, m[None], layer=0))(
        m, {w: a if w in ("w_gate", "w_up", "w_down") else a[0]
            for w, a in cut.items()})
    assert worst(got[0], jitted.reference(
        lambda m, s: ref.moe(held, m, s, 0), m, cut)) < 2e-5


# --- (c) the kernels at the published indexer's shape ---------------------- #


@pytest.mark.parametrize("seq,topk,ties,a_step", [
    (256, 24, True, 256), (384, 100, False, 128),
    # the step holds up to eight query blocks where a key head has ONE query
    # head: 3 blocks no count divides, 6 in three steps of 2, 8 in one step
    (384, 24, True, 128), (768, 200, False, 256), (1024, 300, True, 1024)])
def test_the_pallas_mask_is_select_tops_at_32_heads_of_128(seq, topk, ties,
                                                           a_step):
    """``index_select`` interpreted at 32 x 128 (4,096 stacked rows a query
    block) against ``select_top`` entry for entry, planted ties included,
    and ``masked_flash`` at head width 256 with a key head a query head, in
    grid steps of ``a_step`` rows, against the XLA path under the same
    mask."""
    from ray_tpu.ops import sparse_prefill as sp

    k = jax.random.split(jax.random.PRNGKey(seq), 6)
    qi = jax.random.normal(k[0], (1, seq, 32, 128))
    ki = jax.random.normal(k[1], (1, seq, 128))
    w = jax.random.normal(k[2], (1, seq, 32))
    if ties:  # every fourth key repeats its neighbour: ties at the last place
        ki = ki.at[:, 1::4].set(ki[:, 0::4])
    mask = jax.jit(lambda *a: sp.index_select(*a, topk, interpret=True))(
        qi, ki, w)
    assert mask.shape == sp.mask_tiles_shape(1, seq)
    rows = sp.mask_rows(mask)[0, :, :seq]
    visible = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    want = llama.select_top(llama.index_scores(qi, ki, w)[0], visible, topk)
    assert bool(jnp.all((rows != 0) == want))
    q = jax.random.normal(k[3], (1, seq, 2, 256))
    kk = jax.random.normal(k[4], (1, seq, 2, 256))
    v = jax.random.normal(k[5], (1, seq, 2, 256))
    step = sp.flash_step(q, kk, v)
    assert (step["rows_a_step"], step["heads_a_step"]) == (a_step, 1)
    got = jax.jit(lambda *a: sp.masked_flash(*a, interpret=True))(
        q, kk, v, mask)
    tiles, _ = jax.jit(lambda *a: llama._latent_selected_tiles(
        *a, None, want[None].astype(jnp.int8), topk, jnp.float32, 64))(
            q, kk, v)
    assert worst(got, tiles) < 1e-5


def test_a_traced_prefill_says_the_step_its_kernel_chose(monkeypatch):
    """A ``Y`` / ``Z`` stack's expanded keys are a key head a query head
    (``rep`` 1): a full and a shared layer's ``masked_flash`` hold the two
    query blocks of 256 positions in ONE grid step, of ONE key head."""
    from ray_tpu.ops import sparse_prefill as sp

    cfg = program_cfg(small_file("YZ", 0, 2))
    rec = jitted.traced_prefill_step(cfg, "latent_selected", monkeypatch)
    assert rec["path"] == "kernel" and rec["calls"] >= 2
    shapes = [jax.ShapeDtypeStruct((1, 256, 4, 24), jnp.float32)] * 3
    assert rec["q_shape"] == list(shapes[0].shape)
    assert {k: rec[k] for k in sp.flash_step(*shapes)} \
        == sp.flash_step(*shapes)
    assert (rec["rows_a_step"], rec["heads_a_step"]) == (256, 1)


# --- (d) what the engine holds ---------------------------------------------- #


def test_num_params_counts_the_tree_and_the_files_table():
    cfg = program_cfg()
    p = jitted.init_params(cfg, jax.random.PRNGKey(0))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(p))
    kw = {field: PUBLISHED[key]
          for field, key in PUBLISHED["program"]["fields"].items()}
    real = LlamaConfig(**kw)
    attention = 12582912 + 33554432 + 3538944 + 14680064 + 100663296 + 2560
    indexer = 8388608 + 786432 + 196608 + 256
    routed = attention + 1573120 + 37748736 + 16 * 37748736 + 2 * 6144
    table = ((attention + indexer + 226492416 + 2 * 6144) + 3 * routed
             + (routed + indexer) + 2 * 19360 * 6144 + 6144)
    assert real.num_params() == table == 3881517056
    assert real.kinds == "XZZZY" and real.head_dim == 256
    for attr, key in PUBLISHED["program"]["check"].items():
        assert getattr(real, attr) == PUBLISHED[key], attr


def test_shared_layers_hold_no_indexer_and_no_index_store(params):
    from ray_tpu.util.metrics import registry

    eng = new_engine(params)
    layout = llama.served_stores(eng.cfg)
    assert [(s.kind, s.tag, s.layers, s.row) for s in layout] == [
        ("Y", "dsa_latent", 1, (40,)), ("Y", "dsa_index", 1, (16,)),
        ("Z", "dsa_latent", 3, (40,)),
        ("X", "dsa_latent", 1, (40,)), ("X", "dsa_index", 1, (16,))]
    assert llama.page_rows(eng.cfg)[0] == "dsa" and eng.n_slots == 0
    assert not {"wqi", "wki", "ww", "ki_norm", "ki_bias"} \
        & set(eng.params["layers"]["dsa_shared"])
    assert {"wqi", "wki", "ww"} <= set(eng.params["layers"]["dsa_full"])

    def gauge(name):
        return {k[0][1]: v for k, v in registry().local_values(name).items()}

    assert gauge("ray_tpu_serve_engine_selecting_layers") == {
        "select": 2.0, "reuse": 3.0}
    held = gauge("ray_tpu_serve_engine_page_bytes")
    assert held["dsa_latent"] == 5 * 40 * 4 and held["dsa_index"] == 2 * 16 * 4
    toks = np.random.RandomState(1).randint(0, 256, size=30)
    served(eng, toks, 23, TABLE)
    share = gauge("ray_tpu_serve_engine_selected_share")
    assert share["decode"] == TOPK / 30 and 0 < share["prefill"] < 1
    # a prefill's trace names a selection in full layers only
    text = jax.jit(partial(llama.prefill_with_cache, eng.cfg)).lower(
        eng.params, *eng.stores, jnp.zeros((1, 24), jnp.int32),
        jnp.arange(3, dtype=jnp.int32), jnp.int32(22)).as_text(
            debug_info=True)
    assert text.count("dsa.attend_shared") > 0 and "dsa.select" in text


def test_copy_page_and_a_bfloat16_engine(params):
    toks = np.random.RandomState(8).randint(0, 256, size=27)
    want = reference_logits(params, toks)[22:]
    eng = new_engine(params)
    first = eng.prefill([int(t) for t in toks[:23]], TABLE[:3])
    eng.copy_page(TABLE[2], 20)
    table = TABLE[:2] + [20]
    rows = [eng.decode(j, int(toks[j]), table + TABLE[3:j // PAGE + 1])
            for j in range(23, 27)]
    assert worst(np.stack([first] + rows), want) < 5e-5
    low = new_engine(params, dtype=jnp.bfloat16)
    assert worst(served(low, toks, 23, TABLE), want) < 0.15


# --- (e) what is refused ---------------------------------------------------- #


@pytest.mark.parametrize("keys", [
    {"layer_pattern": "ZZY", "first_layer": 0, "num_hidden_layers": 3},
    {"index_topk": 0}, {"index_head_dim": 18}, {"q_lora_rank": 0},
    {"layer_pattern": "XZL", "first_layer": 0, "num_hidden_layers": 3},
    {"first_layer": 9}])
def test_config_refuses_what_is_inconsistent(keys):
    with pytest.raises(ValueError):
        program_cfg(dict(FILE, **keys))


def test_the_steps_refuse_the_kinds_by_name():
    from ray_tpu.train import spmd

    cfg = program_cfg()
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("data",))
    for make in (partial(spmd.make_spmd_train_step, cfg, mesh),
                 partial(llama.make_train_step, cfg, mesh)):
        with pytest.raises(NotImplementedError, match="'Y' / 'Z' / 'X'"):
            make()
    pipe = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("pipe",))
    with pytest.raises(NotImplementedError, match="'Y' / 'Z' / 'X'"):
        llama.make_pipeline_train_step(cfg, pipe, 2)
    with pytest.raises(NotImplementedError, match="'Y' / 'Z' / 'X'"):
        llama.held_to(cfg, "the MPMD pipeline")
    with pytest.raises(NotImplementedError, match="selection"):
        jitted.forward(cfg, jitted.init_params(cfg, jax.random.PRNGKey(0)),
                       jnp.zeros((1, 8), jnp.int32))
    llama.held_to(cfg, "LlamaDecodeEngine")


# --- (f) the benchmark's files ---------------------------------------------- #


def test_the_file_holds_the_catalogs_numbers():
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "GLM-5.2"]
    bench = spec.load_benchmark()
    entry, = [c for c in bench["configs"] if c["name"] == "GLM-5.2"]
    assert entry["source"] == row["source_url"] == PUBLISHED["source"]
    assert sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert PUBLISHED["published"][key] == value
            assert PUBLISHED[key] != value
        else:
            assert PUBLISHED[key] == value, key
    assert (PUBLISHED["num_hidden_layers"], PUBLISHED["n_routed_experts"],
            PUBLISHED["vocab_size"]) == (5, 16, 154880 // 8)


def test_benchmark_files_fit_together_with_the_new_cell():
    from benchmarks.checks import test_yardstick
    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    test_yardstick.test_benchmark_files_fit_together()
    b = spec.cell_bundle(CELL)
    assert (b["cell"]["chips"], b["cell"]["traffic"], b["cell"]["config"]) \
        == (1, "prefill-open-4608-16000-glm52", "GLM-5.2")
    assert sorted(m["name"] for m in b["end_to_end"]) == [
        "setup_s", "ttft_p95_ms"]
    names = {m["name"] for m in b["per_layer"]}
    assert {"serve.decode_program_ms", "compile_s"} <= names
    assert "serve.window_slots_ms" not in names
    assert len([n for n in names if n.startswith("serve.")]) == 12
    tr, dep = b["traffic"], b["config"]["deployment"]
    assert (tr["kind"], tr["prompt_tokens"], tr["output_tokens"],
            tr["schedule_seed"], tr["trace_seconds"]) == (
        "open_loop", {"dist": "log_uniform", "min": 4608, "max": 16000},
        {"dist": "const", "value": 16}, 0, 11.0)
    assert (dep["page_size"], dep["decode_max_batch"], dep["n_pages"],
            dep["max_inflight"]) == (2048, 4, 48, 32)
    shapes = shapes_of(tr, dep["page_size"])
    assert shapes == {"prefill": list(range(3, 9)),
                      "decode": list(range(3, 9))}
    # the check's prompt lies beyond topk: `correct` meets the selection
    n = check_prompt_len(shapes, dep["page_size"])
    assert n == 6142 > b["config"]["index_topk"]
    assert tr["prompt_tokens"]["min"] > b["config"]["index_topk"]
    assert dep["n_pages"] >= dep["decode_max_batch"] * (shapes["decode"][-1]
                                                        + 1)
    assert spec.resolve(b["config"]["reference"] + ":logits_one")
    assert spec.resolve(b["config"]["reference"] + ":loss")
    sweep = spec.load_json(os.path.join(
        ROOT, "benchmarks", "sweep",
        "prefill-open-4608-16000-glm52.sweep1.json"))
    assert sweep


@pytest.mark.slow  # one more serve rehearsal behind the one lock: by hand
def test_the_cells_rehearsal_runs_end_to_end():
    """``--rehearsal`` of the new cell on the CPU with NO edit of
    ``rehearsal.json``: 2 layers of the pattern from ``first_layer`` on are
    ``XZ`` (a full layer and a shared one behind it), every latent and
    indexer width as published, ``index_topk`` beyond every context."""
    import rehearse

    tiny = spec.cell_bundle(CELL, rehearsal=True)
    cfg = spec.program_config(tiny["config"])
    assert (cfg.kinds, cfg.index_topk, cfg.dim) == ("XZ", 2048, 64)
    line = rehearse.run_cell(CELL, 6300000063)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
