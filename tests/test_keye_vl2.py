"""Keye-VL-2.0-30B-A3B's layer through the program: whole routed blocks whose
attention reads the keys a learned indexer picks ("I"), per-head QK-norm, a
rotation in three sections, a held range of the router's experts, and the
decode engine's three page stores (keys, values, index keys), all at small
widths on the CPU against the plain reference
(``benchmarks/reference/keye_vl2_decoder.py``), seeded weights. ``topk`` (8)
is smaller than the sequences, and the page size (4) times the pages passes
it, so the selection, the index store and the gather all run."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import keye_vl2_decoder as ref
from jitted import (forward, init_params, loss_fn, reference,
                    traced_prefill_step, value_and_grad)
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.moe import routed_mlp

CELL = "serve-keyevl2-prefill-open"
TOPK, PAGE = 8, 4
SA = {"indexer_head_dim": 16, "indexer_num_heads": 4,
      "indexer_num_kv_heads": 1, "kv_chunk_size": 16, "q_chunk_size": 16,
      "topk": TOPK}
# the file's keys at test widths: 4 of the router's 16 experts held, from 4
FILE = {
    "head_dim": 16, "hidden_size": 64, "max_position_embeddings": 64,
    "moe_intermediate_size": 32, "num_experts_per_tok": 3, "num_experts": 4,
    "num_local_experts": 16, "first_expert": 4, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_hidden_layers": 3,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": SA, "tie_word_embeddings": False, "vocab_size": 128,
    "layer_pattern": "I" * 6, "mrope_section": [2, 3, 3],
    "indexer_num_heads": 4, "indexer_head_dim": 16, "indexer_topk": TOPK,
    "indexer_chunk": 16,
}
REAL = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "Keye-VL-2.0-30B-A3B.json"))
FIELDS = REAL["program"]["fields"]


def file_of(**keys):
    """The file's keys with ``topk`` changed in both places it stands."""
    file = dict(FILE, **keys)
    if "indexer_topk" in keys:
        file["sa_config"] = dict(SA, topk=keys["indexer_topk"])
    return file


def program_cfg(dtype=jnp.float32, **file_keys):
    """The program's config from the file's keys, as the harness maps them;
    no router loss, as the reference's ``loss`` has none."""
    file = file_of(**file_keys)
    return dataclasses.replace(
        LlamaConfig(**{field: file[key] for field, key in FIELDS.items()}),
        dtype=dtype, remat=False, lb_loss_coef=0.0, z_loss_coef=0.0)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with the norms, the LayerNorm's bias and the QK-norm's
    gains off their defaults, so that one left out or swapped shows."""
    p = init_params(program_cfg(), jax.random.PRNGKey(7))
    index = dict(p["layers"]["index"])
    names = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "ki_norm",
             "ki_bias")
    for key, name in zip(jax.random.split(jax.random.PRNGKey(8), 6), names):
        index[name] = index[name] + 0.2 * jax.random.normal(
            key, index[name].shape)
    return dict(p, layers={"index": index})


def off(got, want) -> float:
    """Largest difference over the reference's largest value."""
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want)))
                 / jnp.max(jnp.abs(jnp.asarray(want))))


def close(got, want, rtol=2e-5):
    assert off(got, want) < rtol, off(got, want)


def logits_one(params, toks, **file_keys):
    return reference(lambda p, t: ref.logits_one(file_of(**file_keys), p, t),
                     params, jnp.asarray(toks))


# --- (a) the whole model ----------------------------------------------------- #


@pytest.mark.parametrize("topk", [TOPK, 64])
def test_forward_is_the_references_logits(params, topk):
    toks = np.random.RandomState(3).randint(0, 128, size=(2, 37))
    got = forward(program_cfg(indexer_topk=topk), params, toks)
    for row in range(2):
        close(got[row], logits_one(params, toks[row], indexer_topk=topk),
              5e-5)


def test_loss_is_the_references_and_reaches_the_leaves(params):
    """The selection is a hard choice: the loss's gradient flows through the
    chosen keys' attention and reaches no indexer leaf, as the published
    model trains its indexer by a loss of its own (not built)."""
    cfg = program_cfg()
    toks = jnp.asarray(np.random.RandomState(4).randint(0, 128, size=(2, 30)))
    got, d_got = value_and_grad(lambda p: llama.loss_fn(cfg, p, toks), params)
    want = reference(lambda p: ref.loss(FILE, p, toks), params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(loss_fn(cfg, params, toks)) == pytest.approx(float(want),
                                                              rel=1e-5)
    grads = d_got["layers"]["index"]
    for name in ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "router",
                 "w_gate", "w_up", "w_down"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 0, name
    for name in ("wqi", "wki", "ww", "ki_norm", "ki_bias"):
        assert float(jnp.max(jnp.abs(grads[name]))) == 0, name


def test_num_params_counts_the_tree_and_the_file():
    cfg = program_cfg()
    tree = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(tree))
    assert jax.tree.structure(llama.param_logical_axes(cfg), is_leaf=lambda
                              x: isinstance(x, tuple)) == jax.tree.structure(
                                  tree)
    file = spec.cell_bundle(CELL)["config"]
    real = spec.program_config(file)
    # the file's arithmetic (reduced_why): a layer held, eight, the whole
    layer = (18_874_368 + 256 + 2_261_120 + 262_144 + 16 * 4_718_592 + 4_096)
    assert layer == 96_899_456
    assert real.num_params() == 8 * layer + 2 * 18_992 * 2048 + 2048 \
        == 852_988_928
    whole = dataclasses.replace(real, n_layers=48, num_experts=128,
                                router_experts=0, vocab_size=151_936)
    assert whole.num_params() == 48 * 625_381_760 + 622_331_904
    assert (real.kinds, real.index_topk, real.mrope_section) == (
        "I" * 8, 2048, (16, 24, 24))
    assert (real.num_experts, real.router_experts, real.first_expert) == (
        16, 128, 0)
    # every published key, unchanged but depth, experts held and vocabulary
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: file[k] for k in catalog} == catalog
    assert file["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936}
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (8, 16, 18992)
    # the program's indexer and sections are CHECKED against the nested groups
    with pytest.raises(ValueError, match="sa_config"):
        spec.program_config(dict(file, indexer_topk=1024))
    with pytest.raises(ValueError, match="rope_scaling"):
        spec.program_config(dict(file, mrope_section=[24, 20, 20]))


def test_seeded_weights_start_where_index_init_says():
    index = init_params(program_cfg(hidden_size=128),
                        jax.random.PRNGKey(3))["layers"]["index"]
    assert set(llama.INDEX_INIT) == {"wo", "q_norm"}
    for name, fan_in in (("wo", 64), ("w_down", 32), ("wq", 128),
                         ("wqi", 128), ("wki", 128), ("ww", 128),
                         ("router", 128)):
        std = float(jnp.std(index[name])) * fan_in ** 0.5
        assert std == pytest.approx(llama.INDEX_INIT.get(name, 1.0),
                                    rel=0.06), (name, std)
    assert float(index["q_norm"][0, 0]) == pytest.approx(
        llama.INDEX_INIT["q_norm"])
    assert float(index["k_norm"][0, 0]) == 1.0


@pytest.mark.parametrize("keys,why", [
    (dict(index_topk=0), "index_topk"),
    (dict(layer_pattern="IFI", window=0), "every built layer is 'I'"),
    (dict(qk_norm=True), "no whole-projection"),
    (dict(num_experts=0), "softmax-routed"),
    (dict(mrope_section=(4, 4)), "three sections"),
    (dict(index_head_dim=18), "multiple of 4")])
def test_config_refuses_what_is_inconsistent(keys, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(program_cfg(), **keys)


def test_only_an_indexed_layer_reads_the_sections():
    with pytest.raises(ValueError, match="mrope_section"):
        dataclasses.replace(LlamaConfig.debug(), mrope_section=(2, 3, 3))


# --- (b) each part alone ----------------------------------------------------- #


def hidden(seed, seq=24):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, seq, 64))


def program_layer(cfg, x, index, i, positions=None):
    positions = llama.positions_of(1, x.shape[1]) if positions is None \
        else positions
    return jax.jit(lambda x, p: llama.index_block(
        cfg, x, p, i, positions,
        lambda *a: llama.attend_selected(cfg, *a)))(x, index)


@pytest.mark.parametrize("i", [0, 2])
def test_a_layer_is_the_references(params, i):
    x = hidden(5 + i)
    got, _, (k, v, ki) = program_layer(program_cfg(), x,
                                       params["layers"]["index"], i)
    close(got[0], reference(lambda x, p: ref.layer(FILE, x, p, i), x[0],
                            params["layers"]["index"]), 3e-5)
    assert k.shape == v.shape == (1, 24, 2, 16) and ki.shape == (1, 24, 16)


WRONG = {
    "selection_ignored": {"select": False},
    "score_without_relu": {"relu": False},
    "head_weights_left_out": {"weighted": False},
    "index_keys_not_rotated": {"rotate_index": False},
    "qk_norm_left_out": {"qk_norm": False},
    "router_not_renormalised": {"renormalised": False},
}


@pytest.mark.parametrize("what", sorted(WRONG) + ["topk_4", "topk_16"])
def test_the_reference_computed_a_wrong_way_lies_far_off(params, what):
    """What ``sweep/keye_check.py`` measures at published widths, held here
    in float32: each wrong way moves the layer's output by some hundreds of
    times what the program differs from the reference by (3e-5, above; the
    router's renormalisation least, 1.9%: ``INDEX_INIT`` makes the attention
    most of a layer's output)."""
    x, index = hidden(9, 40), params["layers"]["index"]
    got = program_layer(program_cfg(), x, index, 1)[0][0]
    file = file_of(indexer_topk=int(what[5:])) if what.startswith("topk") \
        else FILE
    wrong = reference(lambda x, p: ref.layer(
        file, x, p, 1, **WRONG.get(what, {})), x[0], index)
    assert off(got, wrong) > 0.01, (what, off(got, wrong))


def test_unequal_position_components_turn_by_their_sections(params):
    """Frequency i takes its angle from the component whose section it
    falls in: temporal, height and width that differ, against the
    reference's rotation; and a whole layer fed such positions."""
    rng = np.random.RandomState(6)
    positions = jnp.asarray(rng.randint(0, 50, size=(3, 1, 24)))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 4, 16))
    for sections in ((2, 3, 3), (16, 24, 24), ()):
        got = llama.mrope_rotate(x, positions, 1e4, sections)
        want = ref.rope(x[0], positions[:, 0], 1e4, list(sections))
        close(got[0], want, 1e-5)
    # the sections matter: component 0 alone is another rotation
    assert off(llama.mrope_rotate(x, positions, 1e4, (2, 3, 3)),
               llama.mrope_rotate(x, positions[0], 1e4, (2, 3, 3))) > 0.1
    # text: three equal components are the 1-D rotation
    text = llama.positions_of(1, 24)
    close(llama.mrope_rotate(x, text, 1e4, (2, 3, 3)),
          llama.rotary_embedding(x, x, text, 1e4)[0], 1e-6)
    h, index = hidden(11), params["layers"]["index"]
    got = program_layer(program_cfg(), h, index, 0, positions)[0]
    close(got[0], reference(lambda x, p: ref.layer(
        FILE, x, p, 0, positions[:, 0]), h[0], index), 3e-5)


def test_the_selection_is_top_k_and_a_tie_goes_to_the_lower_position():
    score = jax.random.normal(jax.random.PRNGKey(2), (6, 40))
    # planted ties: equal scores at 3 and 7 in every row; in row 5 they
    # stand alone at the k-th place (seven scores above them, the rest
    # under); and a row of equal scores
    score = score.at[:, 7].set(score[:, 3])
    score = score.at[5].set(-1.0).at[5, 10:17].set(1.0).at[
        5, jnp.array([3, 7])].set(0.5)
    score = score.at[4].set(0.5)
    rows = jnp.array([3, 10, 20, 39, 39, 39])
    visible = jnp.arange(40)[None, :] <= rows[:, None]
    got = jax.jit(lambda s, v: llama.select_top(s, v, TOPK))(score, visible)
    want = ref.selection(FILE, score, rows)
    assert bool(jnp.all(got == want))
    assert [int(n) for n in got.sum(-1)] == [4, 8, 8, 8, 8, 8]
    assert bool(got[5, 3]) and not bool(got[5, 7])
    assert [int(i) for i in jnp.nonzero(got[4])[0]] == list(range(8))
    # signed zeros tie, negative scores order, everything visible under k
    zeros = jnp.array([[-0.0, 0.0, -1.0, -2.0, 0.0, -0.0]])
    seen = jnp.ones((1, 6), bool)
    assert [int(i) for i in jnp.nonzero(llama.select_top(
        jnp.where(zeros == 0, 0.0, zeros), seen, 3)[0])[0]] == [0, 1, 4]
    assert bool(jnp.all(llama.select_top(zeros, seen, 6)))


def test_the_eight_held_ranges_routed_sums_add_up_to_the_uncut_layers(params):
    """The shares test: the router is the same on every chip, each holds a
    range of its experts, and the partial sums of all ranges are the layer
    with every expert here."""
    cfg = program_cfg()
    index = params["layers"]["index"]
    key = jax.random.split(jax.random.PRNGKey(12), 3)
    whole = {w: jax.random.normal(k, (3, 16, *index[w].shape[2:])) / 8
             for k, w in zip(key, ("w_gate", "w_up", "w_down"))}
    m = hidden(13)

    def routed(held, weights):
        return routed_mlp(
            m, index["router"][1], *weights, top_k=cfg.experts_per_token,
            norm_topk_prob=True, held=held, layer=1, router_input=m)

    full, _ = jax.jit(lambda: routed(None, [
        whole[w] for w in ("w_gate", "w_up", "w_down")]))()
    parts, shares = [], []
    for first in range(0, 16, 2):
        y, stats = jax.jit(lambda: routed((first, 2), [
            whole[w][:, first:first + 2]
            for w in ("w_gate", "w_up", "w_down")]))()
        parts.append(y)
        shares.append(float(stats["held_share"]))
    close(sum(parts), full, 1e-5)
    assert sum(shares) == pytest.approx(1.0, abs=1e-6)
    # and the reference's held range is the program's
    got, _ = jax.jit(lambda: routed((4, 4), [
        index[w] for w in ("w_gate", "w_up", "w_down")]))()
    want = reference(lambda m, p: ref.experts(
        FILE, m, ref.route(FILE, m, p, 1), p, 1), m[0], index)
    close(got[0], want, 2e-5)


def selection_operands(seq, planted="ties", heads=4):
    """``(qi, ki, w)`` of one call, float32, seeded by ``seq``: random
    normal, with what a search by counts can get wrong planted in them. A
    score is ``sum_j w_j relu(qi_j . ki)``: a key scaled scales its scores,
    a key repeated repeats them, a key of zeros scores zero; and with four
    heads a key in sixteen scores exactly 0 unplanted (no head's product
    is positive): a run of equal keys in the middle of every long row."""
    k = jax.random.split(jax.random.PRNGKey(seq), 6)
    qi = jax.random.normal(k[0], (1, seq, heads, 16))
    ki = jax.random.normal(k[1], (1, seq, 16))
    w = jax.random.normal(k[2], (1, seq, heads)) / 8
    at = jnp.arange(seq)
    if planted == "ties":  # two pairs of equal keys
        ki = ki.at[0, 5].set(ki[0, 3]).at[0, 100].set(ki[0, 90])
    elif planted == "all_equal":  # every score 0: the lowest positions
        w = jnp.zeros_like(w)
    elif planted == "forty_binades":  # of both signs (w's)
        ki = ki * jnp.exp2((at * 41 % 40 - 20.0))[None, :, None]
    elif planted == "outlier":
        ki = ki.at[0, 7].multiply(1e30)
    elif planted == "half_repeated":  # many ties AT the last place
        ki = ki.at[0, seq // 2:].set(ki[0, :seq - seq // 2])
    elif planted == "signed_zeros":  # 0 * a negative weight is -0.0
        ki = jnp.where((at % 3 == 0)[None, :, None], 0.0, ki)
        w = jnp.where((at % 2 == 0)[None, :, None], -jnp.abs(w), w)
    else:
        assert planted == "plain", planted
    return qi, ki, w


@pytest.mark.parametrize("seq,topk,planted", [
    (256, 24, "ties"), (384, 500, "ties"), (128, 1, "ties"),
    (256, 128, "plain"),   # row 127 sees exactly topk keys, row 128 one more
    (1024, 300, "plain"),  # two key blocks; two query blocks under topk
    (256, 24, "all_equal"), (384, 100, "forty_binades"),
    (256, 24, "outlier"), (384, 100, "half_repeated"),
    (256, 24, "signed_zeros")])
def test_the_kernels_are_the_xla_path(seq, topk, planted):
    """``ops/sparse_prefill.py`` interpreted against ``_selected_tiles``:
    the mask entry for entry (planted ties included) and the attention."""
    from ray_tpu.ops import sparse_prefill as sp

    qi, ki, w = selection_operands(seq, planted)
    k = jax.random.split(jax.random.PRNGKey(seq + 1), 3)
    q = jax.random.normal(k[0], (1, seq, 4, 16))
    kk = jax.random.normal(k[1], (1, seq, 2, 16))
    v = jax.random.normal(k[2], (1, seq, 2, 16))
    mask = jax.jit(lambda *a: sp.index_select(*a, topk, interpret=True))(
        qi, ki, w)
    rows = sp.mask_rows(mask)[0, :, :seq]
    visible = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    want = llama.select_top(llama.index_scores(qi, ki, w)[0], visible, topk)
    assert bool(jnp.all((rows != 0) == want))
    assert [int(n) for n in rows.sum(-1)] == [
        min(t + 1, topk) for t in range(seq)]
    got = jax.jit(lambda *a: sp.masked_flash(*a, interpret=True))(
        q, kk, v, mask)
    close(got, jax.jit(lambda *a: llama._selected_tiles(
        *a, topk, jnp.float32, 64))(q, kk, v, qi, ki, w), 1e-5)


# sha256 of the bytes of ``masked_flash`` interpreted on the CPU at 16 query
# heads on 2 (``rep`` 8), 384 positions, ``topk`` 100, planted ties, taken on
# 04073f6: before a grid step was chosen from ``rep``
REP_8_OUTPUT = "3cb2771b95deb0a23f9bf9943bb2f213b16e3ea18cf9d69050d3df46f2294441"


def test_a_group_of_eight_heads_keeps_its_step_and_its_bits():
    """``flash_step`` gives Keye's shape (32 query heads on 4 key heads of
    128: ``rep`` 8) ONE query block of 1,024 stacked rows a grid step at
    every page count of the cell, what a step was before it was chosen from
    ``rep``, and the kernel's output at ``rep`` 8 is the one it gave then,
    to the last bit."""
    import hashlib

    from ray_tpu.ops import sparse_prefill as sp

    for pages in range(4, 17):
        q, kk, v = (jax.ShapeDtypeStruct((1, pages * 2048, h, 128),
                                         jnp.bfloat16) for h in (32, 4, 4))
        step = sp.flash_step(q, kk, v)
        assert (step["rows_a_step"], step["heads_a_step"]) == (1024, 1)
    seq, topk = 384, 100
    k = jax.random.split(jax.random.PRNGKey(seq + 1), 3)
    q = jax.random.normal(k[0], (1, seq, 16, 16))
    kk = jax.random.normal(k[1], (1, seq, 2, 16))
    v = jax.random.normal(k[2], (1, seq, 2, 16))
    assert sp.flash_step(q, kk, v)["rows_a_step"] == 1024
    mask = jax.jit(lambda *a: sp.index_select(*a, topk, interpret=True))(
        *selection_operands(seq, "ties"))
    got = jax.jit(lambda *a: sp.masked_flash(*a, interpret=True))(
        q, kk, v, mask)
    assert hashlib.sha256(np.ascontiguousarray(got).tobytes()).hexdigest() \
        == REP_8_OUTPUT


def test_a_traced_prefill_says_the_step_its_kernel_chose(monkeypatch):
    """An indexed stack of 16 query heads on 2 (``rep`` 8): ONE query block
    of 8 x 128 stacked rows a grid step of ``masked_flash``, ONE key head."""
    from ray_tpu.ops import sparse_prefill as sp

    cfg = program_cfg(num_attention_heads=16, max_position_embeddings=256)
    rec = traced_prefill_step(cfg, "selected", monkeypatch)
    assert rec["path"] == "kernel" and rec["q_shape"] == [1, 256, 16, 16]
    shapes = [jax.ShapeDtypeStruct((1, 256, h, 16), jnp.float32)
              for h in (16, 2, 2)]
    assert {k: rec[k] for k in sp.flash_step(*shapes)} \
        == sp.flash_step(*shapes)
    assert (rec["rows_a_step"], rec["heads_a_step"]) == (1024, 1)


@pytest.mark.parametrize("seq,topk,planted,heads,most", [
    (1024, 300, "plain", 16, 20),   # bell-shaped scores stop early
    (1024, 300, "plain", 4, None),  # runs of zeros at the last place
    (256, 24, "all_equal", 4, None), (384, 100, "forty_binades", 4, None),
    (256, 24, "outlier", 4, None), (384, 100, "half_repeated", 4, None),
    (256, 24, "signed_zeros", 4, None)])
def test_the_selection_stops_when_its_rows_have(seq, topk, planted, heads,
                                                most):
    """The passes a query block's search ran, which the call writes beside
    the mask: none where no row sees more than ``topk`` keys, no more than
    ``most`` on plain scores, and never more than the stated cap, whatever
    the scores: the bound on the search is this test."""
    from ray_tpu.ops import sparse_prefill as sp

    ops = selection_operands(seq, planted, heads)
    mask, passes = jax.jit(lambda *a: sp.index_select_passes(
        *a, topk, interpret=True))(*ops)
    assert bool(jnp.all(mask == jax.jit(lambda *a: sp.index_select(
        *a, topk, interpret=True))(*ops)))
    passes = [int(n) for n in passes[0]]
    under = topk // sp.SELECT_BLOCK_Q  # blocks whose rows all take all
    assert passes[:under] == [0] * under and min(passes[under:]) > 0
    cap = sp.select_pass_cap(mask.shape[2] * sp.SELECT_BLOCK_K)
    assert max(passes) <= (most or cap) <= cap, passes


# --- (c) the decode engine --------------------------------------------------- #


def new_engine(params, dtype=jnp.float32, n_pages=24, **file_keys):
    return llama.LlamaDecodeEngine(program_cfg(dtype, **file_keys), params,
                                   n_pages=n_pages, page_size=PAGE)


def served(eng, toks, n, pages):
    """Prefill ``n`` tokens into ``pages``, decode the rest: logits rows."""
    rows = [eng.prefill([int(t) for t in toks[:n]], pages[:-(-n // PAGE)])]
    for j in range(n, len(toks)):
        rows.append(eng.decode(j, int(toks[j]), pages[:j // PAGE + 1]))
    return np.stack(rows)


@pytest.mark.parametrize("n,more,topk", [
    (23, 9, TOPK),    # the selection, the index store and the gather run
    (7, 14, TOPK),    # a prompt under topk that decodes past it
    (23, 9, 64),      # topk larger than everything: all visible
    (15, 6, 20)])     # the pages' positions pass topk before the real ones do
def test_prefill_then_decode_through_the_three_stores_is_the_references(
        params, n, more, topk):
    eng = new_engine(params, indexer_topk=topk)
    toks = np.random.RandomState(n).randint(0, 128, size=n + more)
    pages = [5, 1, 17, 8, 13, 2, 21, 9]  # a table out of order
    got = served(eng, toks, n, pages)
    close(got, logits_one(params, toks, indexer_topk=topk)[n - 1:], 5e-5)
    assert eng.prefill_calls == 1 and eng.decode_calls == more


def test_the_stores_are_three_and_the_gauges_say_so(params):
    from ray_tpu.util.metrics import registry

    eng = new_engine(params)
    assert [s.shape for s in eng.stores] == [
        (3, 24, PAGE, 2, 16), (3, 24, PAGE, 2, 16), (3, 24, PAGE, 16)]
    assert llama.page_rows(eng.cfg)[0] == "index" and eng.n_slots == 0
    toks = np.random.RandomState(1).randint(0, 128, size=19)
    served(eng, toks, 15, [0, 1, 2, 3, 4])

    def gauge(name, **tags):
        values = {dict(key).get(next(iter(tags))): v
                  for key, v in registry().local_values(name).items()
                  if all(dict(key).get(k) == v for k, v in tags.items())}
        assert len(values) == 1, (name, tags, values)
        return float(next(iter(values.values())))

    page = "ray_tpu_serve_engine_page_bytes"
    assert gauge(page, kind="kv") == 2 * 3 * 2 * 16 * 4
    assert gauge(page, kind="index") == 3 * 16 * 4
    assert gauge(page, kind="window") == gauge(page, kind="latent") == 0
    share = "ray_tpu_serve_engine_selected_share"
    few = TOPK * (TOPK + 1) // 2
    assert gauge(share, program="prefill") == pytest.approx(
        (few + (15 - TOPK) * TOPK) / (15 * 16 // 2))
    assert gauge(share, program="decode") == pytest.approx(TOPK / 19)
    groups = "ray_tpu_serve_engine_expert_groups"
    assert (gauge(groups, part="program"), gauge(groups, part="layer")) == (
        12, 4)
    held = gauge("ray_tpu_serve_moe_assignment_share", part="held")
    assert 0 < held < 1 and gauge(
        "ray_tpu_serve_moe_assignment_share", part="elsewhere") \
        == pytest.approx(1 - held)
    taken = [r for r in llama.prefill_attend_paths()
             if r["kind"] == "selected"]
    assert taken and all(r["path"] == "tiles" and "cpu" in r["reason"]
                         for r in taken)
    assert gauge("ray_tpu_serve_engine_prefill_attend", kind="selected",
                 path="tiles") >= 1


def test_a_decode_that_reads_other_index_rows_or_pages_lies_far_off(params):
    """The decode faults ``sweep/keye_check.py`` measures on the chip, in
    float32: another sequence's index rows under this one's pages, and key
    and value rows in swapped pages."""
    eng = new_engine(params)
    rng = np.random.RandomState(5)
    toks = rng.randint(0, 128, size=27)
    mine, theirs = [3, 4, 5, 6, 7, 8, 9], [10, 11, 12, 13, 14, 15, 16]
    want = logits_one(params, toks)[22:]
    close(served(eng, toks, 23, mine), want, 5e-5)
    eng.prefill([int(t) for t in rng.randint(0, 128, size=23)], theirs[:6])
    k, v, ki = eng.stores
    eng.prefill([int(t) for t in toks[:23]], mine[:6])
    k, v, ki = eng.stores
    eng.stores = (k, v, ki.at[:, jnp.array(mine)].set(
        ki[:, jnp.array(theirs)]))
    spoiled = np.stack([eng.decode(j, int(toks[j]), mine[:j // PAGE + 1])
                        for j in range(23, 27)])
    assert off(spoiled, want[1:]) > 0.02
    eng.prefill([int(t) for t in toks[:23]], mine[:6])
    k, v, ki = eng.stores
    swap, back = jnp.array([3, 4]), jnp.array([4, 3])
    eng.stores = (k.at[:, swap].set(k[:, back]),
                  v.at[:, swap].set(v[:, back]), ki)
    spoiled = np.stack([eng.decode(j, int(toks[j]), mine[:j // PAGE + 1])
                        for j in range(23, 27)])
    assert off(spoiled, want[1:]) > 0.02


def test_copy_page_carries_all_three_stores(params):
    eng = new_engine(params)
    toks = np.random.RandomState(2).randint(0, 128, size=14)
    eng.prefill([int(t) for t in toks], [1, 2, 3, 4])
    eng.copy_page(4, 9)
    for store in eng.stores:
        assert bool(jnp.all(store[:, 9] == store[:, 4]))
        assert float(jnp.max(jnp.abs(store[:, 4]))) > 0


def test_a_whole_prompt_hit_decodes_to_the_first_times_logits(params):
    """Prefix sharing: a page id addresses all three stores, and the copied
    tail page brings its index keys."""
    from ray_tpu.serve.decode import DecodeScheduler
    from test_kv_cache import _run_all

    eng = new_engine(params)
    sched = DecodeScheduler(eng)
    req = {"prompt": [int(t) for t in np.random.RandomState(2).randint(
        0, 128, size=23)], "max_tokens": 9}
    cold = json.loads(_run_all(sched, [("c", req)])["c"][-1][1])
    warm = json.loads(_run_all(sched, [("w", req)])["w"][-1][1])
    assert warm["cached_prefix"] is True and cold["cached_prefix"] is False
    assert warm["tokens"] == cold["tokens"] and len(cold["tokens"]) == 9
    toks = req["prompt"] + cold["tokens"]
    logits = np.asarray(forward(eng.cfg, eng.params, np.asarray([toks])))[0]
    assert [int(t) for t in logits[22:31].argmax(-1)] == cold["tokens"]


def test_engine_converts_the_leaves_it_multiplies(params):
    tree = llama.serving_params(program_cfg(jnp.bfloat16),
                                params)["layers"]["index"]
    f32 = {"attn_norm", "mlp_norm", "router", "q_norm", "k_norm", "ki_norm",
           "ki_bias", "ww"}
    for name, leaf in tree.items():
        assert leaf.dtype == (jnp.float32 if name in f32 else jnp.bfloat16), \
            name
    assert tree["w_up"].ndim == 4


def test_bfloat16_engine_stays_near_the_reference(params):
    """As the cell runs it: bfloat16 products against the float32 reference
    on the engine's own (rounded) weights."""
    eng = new_engine(params, dtype=jnp.bfloat16, indexer_topk=64)
    toks = np.random.RandomState(21).randint(0, 128, size=30)
    got = served(eng, toks, 23, [5, 1, 3, 8, 13, 2, 7, 11])
    # topk beyond the context: with 8 keys a query at these widths ONE key
    # that rounding moves across the last place is an eighth of a softmax
    assert off(got, logits_one(eng.params, toks, indexer_topk=64)[22:]) < 0.15


def test_the_steps_that_cannot_select_refuse_the_kind():
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("pipe",))
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        llama.make_pipeline_train_step(program_cfg(), mesh, 2)


# --- (d) the benchmark's files ---------------------------------------------- #


def test_benchmark_files_fit_together_with_the_new_cell():
    from benchmarks.checks import test_yardstick
    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    test_yardstick.test_benchmark_files_fit_together()
    bench = spec.load_benchmark()
    b = spec.cell_bundle(CELL)
    assert (b["cell"]["chips"], b["cell"]["traffic"], b["cell"]["config"]) \
        == (1, "prefill-open-6400-32000", "Keye-VL-2.0-30B-A3B")
    assert sorted(m["name"] for m in b["end_to_end"]) == [
        "setup_s", "ttft_p95_ms"]
    names = {m["name"] for m in b["per_layer"]}
    assert {"serve.decode_program_ms", "compile_s"} <= names
    assert "serve.window_slots_ms" not in names
    assert len([n for n in names if n.startswith("serve.")]) == 12
    tr, dep = b["traffic"], b["config"]["deployment"]
    assert (tr["kind"], tr["prompt_tokens"], tr["output_tokens"],
            tr["schedule_seed"], tr["trace_seconds"]) == (
        "open_loop", {"dist": "log_uniform", "min": 6400, "max": 32000},
        {"dist": "const", "value": 16}, 0, 11.0)
    assert (dep["page_size"], dep["decode_max_batch"], dep["n_pages"],
            dep["max_inflight"]) == (2048, 4, 80, 32)
    shapes = shapes_of(tr, dep["page_size"])
    assert shapes == {"prefill": list(range(4, 17)),
                      "decode": list(range(4, 17))}
    # the check's prompt lies beyond topk: `correct` meets the selection
    n = check_prompt_len(shapes, dep["page_size"])
    assert n == 8190 > b["config"]["sa_config"]["topk"]
    assert tr["prompt_tokens"]["min"] > b["config"]["sa_config"]["topk"]
    assert dep["n_pages"] >= dep["decode_max_batch"] * (shapes["decode"][-1]
                                                        + 1)
    assert spec.resolve(b["config"]["reference"] + ":logits_one")
    entry, = [c for c in bench["configs"]
              if c["name"] == b["cell"]["config"]]
    assert sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]


@pytest.mark.deadline(170)
def test_the_cells_rehearsal_runs_end_to_end():
    """``--rehearsal`` of the new cell on the CPU: a head of 16 under
    sections that sum to 64, ``topk`` beyond every context, a held range,
    through ``serve.run``, the scheduler and the harness's check."""
    import rehearse

    tiny = spec.cell_bundle(CELL, rehearsal=True)
    cfg = spec.program_config(tiny["config"])
    assert (cfg.kinds, cfg.index_topk, cfg.mrope_section, cfg.head_dim) == (
        "II", 2048, (16, 24, 24), 16)
    line = rehearse.run_cell(CELL, 4000000040)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
