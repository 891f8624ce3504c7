"""command-a-plus-05-2026 (``cohere2_moe``) through the program at small
widths on the CPU: the PARALLEL block (one mean-subtracting LayerNorm feeds
the attention and the routed MLP, both added to the stream), window ("R":
rotated in pairs (2i, 2i + 1)) and full ("P": unrotated) layers in one stack
with 4 query heads a KV head, a sigmoid router whose chosen scores are
renormalised over a HELD range, four shared experts averaged, a tied head,
and the decode engine's two kinds of store read by a decode attend that
repeats no key; against the plain reference
(``benchmarks/reference/cohere2_moe_decoder.py``), seeded weights. The window
(11) is shorter than the sequences and the page size (5) does not divide it.
Values are taken under ``jax.jit`` (``jitted``)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import cohere2_moe_decoder as ref
from jitted import assert_served_alike, forward, init_params, loss_fn, \
    reference, walked_both_ways
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.layers import layer_norm, rms_norm, rotary_embedding, \
    rotate_pairs

CELL = "serve-commandaplus-prefill-open"
NAME = "command-a-plus-05-2026"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REAL = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", f"{NAME}.json"))
FIELDS = REAL["program"]["fields"]
WINDOW, PAGE = 11, 5
# the file's keys at test widths: two periods, 8 query heads on 2 KV heads
# (4 a group), a router 16 wide of which experts 4-7 are held, top-3, four
# shared experts
FILE = dict(
    {k: REAL[k] for k in (
        "layer_norm_eps", "norm_topk_prob", "expert_selection_fn",
        "num_shared_experts", "shared_expert_combination_strategy",
        "tie_word_embeddings", "use_parallel_block", "norm_kind",
        "rope_interleaved")},
    hidden_size=64, num_hidden_layers=8, layer_pattern="RRRP" * 2,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    intermediate_size=32, num_experts=4, router_experts=16, first_expert=4,
    num_experts_per_tok=3, vocab_size=128, sliding_window=WINDOW,
    max_model_len=60, rope_theta=10000.0, logit_scale=0.5)


def program_cfg(dtype=jnp.float32, **file_keys):
    """The program's config from the file's keys, as the harness maps them."""
    file = dict(FILE, **file_keys)
    return dataclasses.replace(
        LlamaConfig(**{field: file[key] for field, key in FIELDS.items()}),
        dtype=dtype, remat=False)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with the norms off one, so that a norm left out
    shows."""
    p = init_params(program_cfg(), jax.random.PRNGKey(7))
    k = jax.random.split(jax.random.PRNGKey(8), 2)
    block = dict(p["layers"]["parallel"])
    block["norm"] = 1 + 0.2 * jax.random.normal(k[0], block["norm"].shape)
    return dict(p, layers={"parallel": block},
                final_norm=1 + 0.2 * jax.random.normal(k[1], (64,)))


def hidden(seed, seq=29):
    """A stream whose MEAN IS NOT ZERO (seeded weights alone give a mean
    near zero, and cannot tell LayerNorm from RMSNorm)."""
    return 0.7 + jax.random.normal(jax.random.PRNGKey(seed), (1, seq, 64))


def off(got, want) -> float:
    """Largest difference over the reference's largest value."""
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want)))
                 / jnp.max(jnp.abs(jnp.asarray(want))))


def close(got, want, rtol=2e-5):
    assert off(got, want) < rtol, off(got, want)


def logits_one(params, toks, **file_keys):
    return reference(lambda p, t: ref.logits_one(dict(FILE, **file_keys), p,
                                                 t), params,
                     jnp.asarray(toks))


# --- (a) the whole model ----------------------------------------------------- #


def test_forward_is_the_references_logits(params):
    toks = np.random.RandomState(3).randint(0, 128, size=(2, 37))
    got = forward(program_cfg(), params, toks)
    for row in range(2):
        close(got[row], logits_one(params, toks[row]), 5e-5)
    # logit_scale is in both: at 1 the logits are twice these
    close(2 * got[0], logits_one(params, toks[0], logit_scale=1), 5e-5)


def test_loss_is_the_references(params):
    cfg = program_cfg()
    toks = jnp.asarray(np.random.RandomState(4).randint(0, 128, size=(2, 30)))
    want = reference(lambda p, t: ref.loss(FILE, p, t), params, toks)
    assert float(loss_fn(cfg, params, toks)) == pytest.approx(float(want),
                                                              rel=1e-5)


def test_num_params_counts_the_tree_and_the_file():
    cfg = program_cfg()
    tree = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(tree))
    assert jax.tree.structure(llama.param_logical_axes(cfg), is_leaf=lambda
                              x: isinstance(x, tuple)) == jax.tree.structure(
                                  tree)
    real = spec.program_config(spec.cell_bundle(CELL)["config"])
    # a layer held: attention, four shared experts, router, norm, 16 experts
    attention = 2 * 4096 * 128 * 128 + 2 * 4096 * 8 * 128
    shared, expert = 4 * 3 * 4096 * 4096, 3 * 4096 * 4096
    assert (attention, shared, expert) == (142_606_336, 201_326_592,
                                           50_331_648)
    layer = attention + shared + 4096 * 128 + 4096 + 16 * expert
    assert layer == 1_149_767_680
    assert real.num_params() == 4 * layer + 32768 * 4096 + 4096 \
        == 4_733_292_544
    whole = dataclasses.replace(real, n_layers=32, num_experts=128,
                                router_experts=0, vocab_size=262144)
    assert whole.num_params() == 32 * 6_786_912_256 + 1_073_745_920 \
        == 218_254_938_112
    assert (real.kinds, real.window, real.window_pages(1024)) == (
        "RRRP", 4096, 5)
    assert (real.n_heads, real.n_kv_heads, real.head_dim, real.mlp_dim,
            real.shared_mlp_dim, real.shared_experts, real.router_experts,
            real.experts_per_token, real.rope_theta) == (
                128, 8, 128, 4096, 4096, 4, 128, 8, 50000)


def test_seeded_weights_start_where_parallel_init_says():
    block = init_params(program_cfg(hidden_size=128),
                        jax.random.PRNGKey(3))["layers"]["parallel"]
    assert set(llama.PARALLEL_INIT) == {"wq", "wo"}
    for name, fan_in in (("wo", 128), ("w_down", 32), ("wq", 128),
                         ("w_up", 128), ("router", 128), ("wk", 128),
                         ("shared_down", 32), ("shared_gate", 128)):
        std = float(jnp.std(block[name])) * fan_in ** 0.5
        assert std == pytest.approx(llama.PARALLEL_INIT.get(name, 1.0),
                                    rel=0.05), (name, std)


@pytest.mark.parametrize("keys,why", [
    (dict(window=0), "needs a window"),
    (dict(norm_kind="rms"), "ONE LayerNorm"),
    (dict(num_experts=0), "routed SwiGLU MLP"),
    (dict(mlp_act="reglu"), "routed SwiGLU MLP"),
    (dict(shared_mlp_dim=0), "shared_experts ungated shared experts"),
    (dict(shared_combine="sum"), "shared_combine 'average'"),
    (dict(qk_norm=True), "no QK-norm"),
    (dict(layer_pattern="RRRF" * 2), "every built layer is one of the two")])
def test_config_refuses_what_is_inconsistent(keys, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(program_cfg(), **keys)


@pytest.mark.parametrize("keys", [
    dict(norm_kind="layer"), dict(rope_interleaved=True),
    dict(shared_experts=4), dict(logit_scale=0.5)])
def test_only_a_parallel_stack_reads_its_fields(keys):
    """No test holds another kind to a reference with any of them."""
    with pytest.raises(ValueError, match="only a stack of 'P' / 'R' layers"):
        dataclasses.replace(LlamaConfig.debug(), **keys)


def test_the_train_steps_refuse_the_kinds_by_name():
    from ray_tpu.train.spmd import build_train_mesh, make_spmd_train_step

    cfg = program_cfg()
    with pytest.raises(NotImplementedError, match="no 'P' / 'R' layer"):
        make_spmd_train_step(cfg, build_train_mesh(""))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with pytest.raises(NotImplementedError, match="no 'P' / 'R' layer"):
        llama.make_train_step(cfg, mesh)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("pipe",))
    with pytest.raises(NotImplementedError, match="no 'P' / 'R' layer"):
        llama.make_pipeline_train_step(cfg, mesh, 2)


# --- (b) each part alone ----------------------------------------------------- #


def block_layer(cfg, x, block, i):
    """The program's layer ``i``, attended in tiles."""
    kind = cfg.kinds[i]
    return jax.jit(lambda x, p: llama.parallel_block(
        cfg, kind, x, p, i, llama.positions_of(1, x.shape[1]),
        lambda *a: llama.attend_parallel_tiles(cfg, kind, *a)))(x, block)


@pytest.mark.parametrize("i", [0, 2, 3, 7])
def test_a_layer_is_the_references(params, i):
    cfg, x, block = program_cfg(), hidden(1), params["layers"]["parallel"]
    got, stats, (k, v) = block_layer(cfg, x, block, i)
    close(got[0], reference(lambda x, p: ref.layer(FILE, x, p, i), x[0],
                            block))
    assert k.shape == v.shape == (1, 29, 2, 16)
    assert 0.0 <= float(stats["held_share"]) <= 1.0


def _plain_attention(file, a, p, l, spread):
    """Layer ``l``'s attention with explicit ``[heads, T, T]`` scores, a KV
    head's keys brought to the query heads by ``spread`` (``jnp.repeat``:
    head h reads KV head h // rep, the model's; ``jnp.tile``: h mod n_kv)."""
    T = a.shape[0]
    nq, nkv, hd = 8, 2, 16
    q = (a @ p["wq"][l]).reshape(T, nq, hd)
    k = (a @ p["wk"][l]).reshape(T, nkv, hd)
    v = (a @ p["wv"][l]).reshape(T, nkv, hd)
    windowed = file["layer_types"][l] == "sliding_attention"
    if windowed:
        q, k = ref._rope(q, file["rope_theta"]), ref._rope(k,
                                                           file["rope_theta"])
    n = nq // nkv
    k, v = (spread(y, (1, n, 1)) if spread is jnp.tile
            else spread(y, n, axis=1) for y in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / 4.0
    probs = jax.nn.softmax(jnp.where(ref.band(file, T, windowed)[None], s,
                                     -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(T, -1) @ p["wo"][l]


def _half_split(x, theta):
    q, _ = rotary_embedding(x[None], x[None], jnp.arange(x.shape[0]), theta)
    return q[0]


def _sequential(file, x, p, l):
    """The block as a SEQUENTIAL one would have it: the MLP fed a norm of
    ``x + A`` (the same norm's gain: the block has one)."""
    eps = file["layer_norm_eps"]
    h = x + ref.attention(file, ref.layer_norm(x, p["norm"][l], eps), p, l)
    m = ref.layer_norm(h, p["norm"][l], eps)
    return (h + ref.experts(file, m, ref.route(file, m, p, l), p, l)
            + ref.shared(file, m, p, l))


def _wrong_layer(what, x, p, l):
    """The reference's layer ``l`` with ONE thing turned wrong."""
    file = dict(FILE)
    if what == "sequential for parallel":
        return _sequential(file, x, p, l)
    keys = {
        "the window one longer": dict(sliding_window=WINDOW + 1),
        "the window one shorter": dict(sliding_window=WINDOW - 1),
        "shared experts summed": dict(
            shared_expert_combination_strategy="sum"),
        "softmax for sigmoid": dict(expert_selection_fn="softmax"),
        "no renormalisation": dict(norm_topk_prob=False),
        "a full layer windowed and rotated": dict(
            layer_types=["sliding_attention"] * 8),
    }.get(what)
    if keys is not None:
        return ref.layer(dict(file, **keys), x, p, l)
    saved = {n: getattr(ref, n) for n in ("layer_norm", "_rope", "band",
                                          "attention")}
    try:  # traced once, under the swap
        if what == "no mean subtraction":
            ref.layer_norm = lambda x, w, eps: rms_norm(x, w, eps)
        elif what == "half-split rotation":
            ref._rope = _half_split
        elif what == "a window layer not rotated":
            ref._rope = lambda x, theta: x
        elif what == "a full layer rotated":  # the band off, the rotation on
            file["layer_types"] = ["sliding_attention"] * 8
            causal = saved["band"]
            ref.band = lambda cfg, T, windowed: causal(cfg, T, False)
        elif what == "head h reads KV head h mod n_kv":
            ref.attention = lambda cfg, a, p, l: _plain_attention(
                cfg, a, p, l, jnp.tile)
        else:
            raise KeyError(what)
        return ref.layer(file, x, p, l)
    finally:
        for n, fn in saved.items():
            setattr(ref, n, fn)


# what a comparison must refuse, each ALONE: the layer (a window layer, 0,
# or a full one, 3) computed that way lies far from the program's, whose own
# reference lies within 2e-5
WRONG = {
    "sequential for parallel": 0, "no mean subtraction": 0,
    "half-split rotation": 0, "a window layer not rotated": 0,
    "a full layer rotated": 3, "a full layer windowed and rotated": 3,
    "the window one longer": 0, "the window one shorter": 0,
    "shared experts summed": 0, "softmax for sigmoid": 0,
    "no renormalisation": 0, "head h reads KV head h mod n_kv": 0,
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_comparison_refuses(params, what):
    i = WRONG[what]
    cfg, x, block = program_cfg(), hidden(2), params["layers"]["parallel"]
    got = block_layer(cfg, x, block, i)[0][0]
    wrong = reference(lambda x, p: _wrong_layer(what, x, p, i), x[0], block)
    # the program's own reference lies within 2e-5. A window moved by ONE
    # position changes one key of eleven in some rows; softmax scores
    # renormalised over the chosen three differ from sigmoid ones by a third
    # of ONE held expert's weight, beside an attention 24 times fan-in
    floor = (2e-3 if "window one" in what else
             1e-3 if what == "softmax for sigmoid" else 1e-2)
    assert off(got, wrong) > floor, off(got, wrong)


def test_the_plain_attention_of_the_fault_test_is_the_references(params):
    """``_plain_attention`` with ``jnp.repeat`` IS the reference's grouped
    attention: only its ``jnp.tile`` is a fault."""
    block = params["layers"]["parallel"]
    a = hidden(3)[0]
    for l in (0, 3):
        close(reference(lambda a, p: _plain_attention(FILE, a, p, l,
                                                      jnp.repeat), a, block),
              reference(lambda a, p: ref.attention(FILE, a, p, l), a, block))


def test_layer_norm_subtracts_the_mean_and_rms_norm_does_not():
    x, w = hidden(4)[0], 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                                     (64,))
    got = jax.jit(layer_norm)(x, w)
    close(got, reference(lambda x, w: ref.layer_norm(x, w, 1e-5), x, w),
          1e-6)
    np.testing.assert_allclose(np.asarray(got / w).mean(-1), 0, atol=1e-6)
    assert off(jax.jit(rms_norm)(x, w), got) > 0.1
    # bfloat16 in, bfloat16 out, float32 statistics
    assert jax.jit(layer_norm)(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rotate_pairs_is_the_interleaved_rotation(dtype):
    """The rotation by ONE product with a signed permutation is the sliced
    and stacked one (in bfloat16 to the bit)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16)).astype(dtype)
    pos = jnp.arange(9)[None].repeat(2, 0) + 5
    want, _ = jax.jit(lambda x: rotary_embedding(x, x, pos, 50000.0,
                                                 interleaved=True))(x)
    got = jax.jit(lambda x: rotate_pairs(x, pos, 50000.0))(x)
    assert got.dtype == dtype
    # the same products and sums (float32: in another order, an ulp apart)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0 if dtype == jnp.bfloat16 else 1e-5,
                               atol=0 if dtype == jnp.bfloat16 else 1e-6)


def test_the_references_rotation_turns_pairs():
    x = jax.random.normal(jax.random.PRNGKey(2), (9, 3, 16))
    want, _ = rotary_embedding(x[None], x[None], jnp.arange(9), 50000.0,
                               interleaved=True)
    close(ref._rope(x, 50000.0), want[0], 1e-6)


def test_one_wide_expert_is_four_averaged_ones(params):
    """The program keeps the four shared experts side by side as ONE expert
    four times as wide whose down product is divided by four; the reference
    computes four and averages."""
    block = params["layers"]["parallel"]
    a = hidden(5)[0]

    def wide(a, p):
        g = jax.nn.silu(a @ p["shared_gate"][1]) * (a @ p["shared_up"][1])
        return (g @ p["shared_down"][1]) / 4

    assert block["shared_gate"].shape == (8, 64, 4 * 32)
    close(reference(wide, a, block),
          reference(lambda a, p: ref.shared(FILE, a, p, 1), a, block), 1e-6)
    assert off(reference(wide, a, block), reference(
        lambda a, p: ref.shared(dict(
            FILE, shared_expert_combination_strategy="sum"), a, p, 1),
        a, block)) > 0.5


def test_the_shares_add_up_to_the_uncut_layer():
    """Over all four ranges of 4 experts of a router 16 wide, the attention
    and the shared experts (what every chip computes alike) counted once,
    the layer's sum is the uncut reference's."""
    whole_file = dict(FILE, num_experts=16, first_expert=0)
    whole = init_params(program_cfg(num_experts=16, first_expert=0),
                        jax.random.PRNGKey(9))["layers"]["parallel"]
    x, l = hidden(6), 1
    want = reference(lambda x, p: ref.layer(whole_file, x, p, l), x[0], whole)
    # what every share has: x + A + S, the routed sum of NO held expert
    alike = reference(lambda x, p: ref.layer(
        dict(whole_file, num_experts=0), x, p, l), x[0], whole)
    total = alike
    for first in range(0, 16, 4):
        held = dict(whole, **{w: whole[w][:, first:first + 4]
                              for w in ("w_gate", "w_up", "w_down")})
        got = block_layer(program_cfg(first_expert=first), x, held, l)[0][0]
        close(got, reference(lambda x, p: ref.layer(
            dict(FILE, first_expert=first), x, p, l), x[0], held))
        total = total + (got - alike)
    close(total, want)
    assert off(alike, want) > 1e-2  # the routed sum is not nothing


@pytest.mark.parametrize("lowest", [None, 4])
def test_the_grouped_decode_attend_is_attend_cached(lowest):
    """One product a KV head, no key repeated or concatenated: the same
    numbers as ``_attend_cached`` on the same views in float32, with a
    window's lower edge and without."""
    cfg = program_cfg()
    k = jax.random.split(jax.random.PRNGKey(lowest or 0), 5)
    q = jax.random.normal(k[0], (1, 1, 8, 16))
    kk, vv = (jax.random.normal(a, (1, 1, 2, 16)) for a in k[1:3])
    views = [jax.random.normal(a, (20, 2, 16)) for a in k[3:]]
    want = jax.jit(lambda *a: llama._attend_cached(
        cfg, *a, lowest=lowest))(*views, 13, q, kk, vv)
    got = jax.jit(lambda *a: llama._attend_grouped(
        cfg, "parallel_full", *a, lowest=lowest))(*views, 13, q, kk, vv)
    close(got, want, 2e-6)
    # head h reads KV head h // 4: the other grouping lies far off
    other = jax.jit(lambda *a: llama._attend_grouped(
        cfg, "parallel_full", *a, lowest=lowest))(
            *views, 13, q.reshape(1, 1, 4, 2, 16).swapaxes(2, 3).reshape(
                q.shape), kk, vv)
    assert off(other.reshape(1, 1, 2, 4, 16).swapaxes(2, 3).reshape(q.shape),
               want) > 0.1
    forms = {(r["form"], tuple(r["view_shape"]))
             for r in llama.decode_attend_forms()}
    assert {("grouped", (20, 2, 16)), ("repeated", (20, 2, 16))} <= forms


# --- (c) the engine: two kinds of store, slots, the grouped decode ---------- #


def new_engine(params, n_layers=8, n_pages=24, dtype=jnp.float32, **kw):
    cfg = program_cfg(dtype, num_hidden_layers=n_layers, **kw)
    block = jax.tree.map(lambda a: a[:n_layers],
                         params["layers"]["parallel"])
    return llama.LlamaDecodeEngine(
        cfg, dict(params, layers={"parallel": block}), n_pages=n_pages,
        page_size=PAGE)


@pytest.fixture(scope="module")
def engine(params):
    return new_engine(params)


def served(engine, toks, n, pages):
    """Prefill ``n`` tokens, then decode the rest: a row of logits each."""
    ps = engine.page_size
    got = [engine.prefill([int(t) for t in toks[:n]], pages[:-(-n // ps)])]
    for j in range(n, len(toks)):
        got.append(engine.decode(j, int(toks[j]), pages[:j // ps + 1]))
    return np.stack(got)


def gauge(name):
    from ray_tpu.util.metrics import registry

    return {tuple(v for _, v in sorted(tags)): n
            for tags, n in registry().local_values(name).items()}


@pytest.mark.parametrize("n,more", [(23, 14), (7, 17), (15, 3)])
def test_prefill_then_decode_through_pages_and_slots_is_the_references(
        engine, params, n, more):
    """A prompt longer than the window and one shorter, decoded across at
    least two page boundaries and PAST the window, over scattered pages: the
    engine's logits are ``logits_one``'s of the whole sequence."""
    toks = np.random.RandomState(n).randint(0, 128, size=n + more)
    pages = [9, 2, 17, 6, 11, 4, 20, 1]
    got = served(engine, toks, n, pages)
    close(got, logits_one(params, toks)[n - 1:], 5e-5)
    assert len(engine._slot_of) <= len(pages)


@pytest.mark.parametrize("n_layers,kinds", [(4, "RRRP"), (2, "RR"),
                                            (6, "RRRPRR")])
def test_a_cut_stack_serves_the_references_logits(params, n_layers, kinds):
    """One period in line, a period and a rest, and the REHEARSAL's cut: two
    layers of a pattern that starts with three window layers are window
    layers alone, which this family's rows allow (``Served.alone``)."""
    eng = new_engine(params, n_layers)
    assert eng.cfg.kinds == kinds
    toks = np.random.RandomState(n_layers).randint(0, 128, size=22)
    got = served(eng, toks, 18, [3, 1, 4, 7, 5])
    close(got, logits_one(params, toks, num_hidden_layers=n_layers)[17:],
          5e-5)


@pytest.mark.parametrize("n_layers,segments,traced", [
    # the cell's ONE period: a run scanned
    (4, [("R", 3, True), ("P", 1, False)], 2),
    (6, [("R", 3, True), ("P", 1, False), ("R", 2, True)], 3),
    # two periods: scanned as a period's body
    (8, [("RRRP", 2, True)], 4)])
def test_a_scanned_run_of_window_layers_is_those_layers_in_line(
        params, n_layers, segments, traced, monkeypatch):
    """A stack of fewer than two periods has its runs of ``R`` scanned, ONE
    body a run, and one of two periods is scanned by periods as it always
    was (no run is looked for inside a period's body): either way the logits
    of a prefill past the window and of decode calls across page boundaries,
    the rows of the four stores (pages and SLOTS) and the assignment shares
    are those of the same layers each in line."""
    cfg = program_cfg(num_hidden_layers=n_layers)
    assert llama._segments(llama.served_kinds(cfg)) == segments
    toks = np.random.RandomState(n_layers).randint(0, 128, size=24)
    scanned, in_line = walked_both_ways(
        lambda: new_engine(params, n_layers), toks, 18, monkeypatch)
    assert (scanned["traced"], in_line["traced"]) == (traced, n_layers)
    assert_served_alike(scanned, in_line)


def test_a_decode_that_reads_a_wrong_slot_lies_far_off(engine, params):
    toks = np.random.RandomState(31).randint(0, 128, size=26)
    pages = [5, 6, 7, 8, 9, 10]
    want = logits_one(params, toks)[22:]
    close(served(engine, toks, 23, pages), want, 5e-5)
    engine._slot_of[8], keep = engine._slot_of[7], engine._slot_of[8]
    wrong = np.stack([engine.decode(j, int(toks[j]), pages[:j // PAGE + 1])
                      for j in range(23, 26)])
    engine._slot_of[8] = keep
    assert off(wrong, want[1:]) > 1e-2


def test_the_stores_the_gauges_and_the_decode_attends_form(params):
    engine = new_engine(params)  # its gauges are the last engine's
    cfg = engine.cfg
    assert llama.page_rows(cfg) == ("parallel", [(2, (2, 16))] * 2
                                    + [(6, (2, 16))] * 2)
    # k = ceil(11 / 5) + 1 = 4 pages of window; the longest sequence is 12
    # pages: ceil(24 / 12) * (4 + 2) slots
    assert (engine.window_pages, engine.n_slots) == (4, 12)
    assert [s.shape for s in engine.stores] == [(2, 24, 5, 2, 16)] * 2 \
        + [(6, 12, 5, 2, 16)] * 2
    bytes_ = gauge("ray_tpu_serve_engine_page_bytes")
    assert bytes_[("parallel_full",)] == 2 * 2 * 2 * 16 * 4.0
    assert bytes_[("parallel_window",)] == 2 * 6 * 2 * 16 * 4.0
    assert {k for k, v in bytes_.items() if v} == {("parallel_full",),
                                                   ("parallel_window",)}
    assert gauge("ray_tpu_serve_engine_expert_groups") == {
        ("program",): 8 * 4.0, ("layer",): 4.0}
    assert gauge("ray_tpu_serve_engine_window_slots")[("total",)] == 12.0
    before = gauge("ray_tpu_serve_engine_decode_attend")
    attends = gauge("ray_tpu_serve_engine_prefill_attend")
    pages = engine.pool.alloc(7)  # seven pages: programs of their own
    engine.prefill(list(range(33)), pages)
    engine.decode(33, 1, pages)
    engine.pool.release(pages)
    after = gauge("ray_tpu_serve_engine_decode_attend")
    # eight layers are two periods scanned: the period's four attends are
    # traced once
    assert after[("grouped",)] >= before.get(("grouped",), 0.0) + 4.0
    assert after[("repeated",)] == before.get(("repeated",), 0.0)
    now = gauge("ray_tpu_serve_engine_prefill_attend")
    for kind in ("parallel_full", "parallel_window"):
        assert now[(kind, "tiles")] >= attends.get((kind, "tiles"), 0.0) + 1.0
        assert now[(kind, "kernel")] == attends.get((kind, "kernel"), 0.0)
    mine = {(r["kind"], r["window"]): (r["path"], r["reason"])
            for r in llama.prefill_attend_paths()
            if r["q_shape"] == [1, 35, 8, 16]}
    why = ("tiles", "backend is 'cpu', not tpu")
    assert mine == {("parallel_full", 0): why,
                    ("parallel_window", WINDOW): why}
    shares = gauge("ray_tpu_serve_moe_assignment_share")
    assert 0.0 < shares[("held",)] < 1.0 and shares[("zero",)] == 0.0
    assert sum(shares.values()) == pytest.approx(1.0)


def test_a_long_prompt_holds_k_slots_and_release_frees_them(params):
    eng = new_engine(params)
    pages = eng.pool.alloc(8)
    eng.prefill(list(range(37)), pages)  # 8 pages: 4 slots, the last pages'
    assert set(eng._slot_of) == set(pages[-4:])
    more = eng.pool.alloc(1)
    eng.decode(40, 1, pages + more)  # a ninth page: first touch, a slot
    assert set(eng._slot_of) == set(pages[-4:] + more)
    eng.copy_page(pages[-1], 20)
    assert 20 in eng._slot_of
    eng.pool.release(pages + more)
    assert set(eng._slot_of) == {20}


def test_the_scheduler_serves_it_and_a_whole_prompt_hit_decodes_the_same(
        params):
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
    tree = llama.serving_params(program_cfg(jnp.bfloat16), params)
    f32 = {"norm", "router"}
    for name, leaf in tree["layers"]["parallel"].items():
        assert leaf.dtype == (jnp.float32 if name in f32 else jnp.bfloat16)
    # the tied head is the embedding: ONE leaf, in the compute type
    assert tree["embedding"].dtype == jnp.bfloat16 and "lm_head" not in tree
    assert tree["final_norm"].dtype == jnp.float32


def test_bfloat16_engine_stays_near_the_reference(params):
    """As the cell runs it: bfloat16 products against the float32 reference
    on the engine's own (rounded) weights."""
    eng = new_engine(params, dtype=jnp.bfloat16)
    toks = np.random.RandomState(21).randint(0, 128, size=30)
    got = served(eng, toks, 23, [5, 1, 3, 8, 13, 2])
    assert off(got, logits_one(eng.params, toks)[22:]) < 0.15


def test_engine_refuses_a_mix_of_families(params):
    with pytest.raises(ValueError, match="every built layer is one of"):
        program_cfg(layer_pattern="RRRW" * 2)
    # "F" / "W" still want both of theirs: only these rows say ``alone``
    # (and, since PR 58, the state-space layers' "H"; since PR 63 the
    # layers under a shared selection, any part whose first layer is full;
    # since PR 65 the decoder-hybrid-decoder's, any part LlamaConfig takes)
    assert llama.SERVED["P"].alone and llama.SERVED["R"].alone
    assert not any(kind.alone for c, kind in llama.SERVED.items()
                   if c not in "PRH" + llama.DSA_KINDS + llama.MEMORY_KINDS)


# --- (d) the benchmark's files ---------------------------------------------- #


@pytest.fixture(scope="module")
def cell():
    return spec.cell_bundle(CELL)


def test_the_file_holds_the_published_row_but_its_three_cuts(cell):
    file = cell["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of published rows is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == NAME)
    assert file["source"] == row["source_url"]
    cut = {"num_hidden_layers": (32, 4), "num_experts": (128, 16),
           "vocab_size": (262144, 32768)}
    for key, value in row["config"].items():
        if key in cut:
            assert (file["published"][key], file[key]) == cut[key]
            assert value == cut[key][0]
        else:
            assert file[key] == value, key
    assert set(file["published"]) == set(cut)
    entry = next(c for c in cell["bench"]["configs"] if c["name"] == NAME)
    assert entry["reduced"] == list(cut)
    assert file["router_experts"] == 128 and file["first_expert"] == 0
    for key in ("deployment", "reduced_why", "assumed", "reference",
                "program", "correct"):
        assert file[key], key
    assert file["deployment"]["chips_sharing_a_layer"] == 8
    # the program's pattern is CHECKED against the published list
    cfg = spec.program_config(file)
    assert cfg.layer_types == file["layer_types"]
    with pytest.raises(ValueError, match="layer_types"):
        spec.program_config(dict(file, layer_pattern="RRPP" * 8))


def test_benchmark_files_fit_together_with_the_new_cell(cell):
    from benchmarks.checks import test_yardstick
    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    test_yardstick.test_benchmark_files_fit_together()
    assert (cell["cell"]["chips"], cell["cell"]["traffic"]) == (
        1, "prefill-open-4608-16000-cmdaplus")
    assert sorted(m["name"] for m in cell["end_to_end"]) == [
        "setup_s", "ttft_p95_ms"]
    names = {m["name"] for m in cell["per_layer"]}
    small = {m["name"] for m in spec.cell_bundle(
        "serve-smallthinker-prefill-open")["per_layer"]}
    assert names == small and "serve.window_slots_ms" in names
    tr, dep = cell["traffic"], cell["config"]["deployment"]
    assert (tr["kind"], tr["prompt_tokens"], tr["output_tokens"]) == (
        "open_loop", {"dist": "log_uniform", "min": 4608, "max": 16000},
        {"dist": "const", "value": 16})
    assert tr["schedule_seed"] == 0
    shapes = shapes_of(tr, dep["page_size"])
    assert shapes == {"prefill": list(range(5, 17)),
                      "decode": list(range(5, 17))}
    # the check's prompt lies beyond the window: `correct` meets the band
    n = check_prompt_len(shapes, dep["page_size"])
    assert n == 5118 > cell["config"]["sliding_window"]
    assert dep["n_pages"] >= dep["decode_max_batch"] * (shapes["decode"][-1]
                                                        + 1)
    assert spec.resolve(cell["config"]["reference"] + ":logits_one")
    # the sweep's record is there, and the rate is half its knee
    record = spec.load_json(os.path.join(
        spec.BENCH_DIR, "sweep",
        "prefill-open-4608-16000-cmdaplus.sweep1.json"))
    assert record
    # the rehearsal cuts the pattern to two window layers whose window never
    # bites at 512 positions
    tiny = spec.program_config(spec.cell_bundle(CELL,
                                                rehearsal=True)["config"])
    assert (tiny.kinds, tiny.window, tiny.dim, tiny.shared_experts) == (
        "RR", 4096, 64, 4)


@pytest.mark.slow  # a FIFTH serve rehearsal behind the one lock: by hand
@pytest.mark.deadline(170)
def test_the_cells_rehearsal_runs_end_to_end():
    """``--rehearsal`` of the new cell on the CPU with NO edit of
    ``rehearsal.json``: 2 layers make the pattern ``RR`` (both window
    layers: the full layer is the period's fourth) and the 4,096 window
    never bites at 512 positions, so what this run holds is the harness, the
    slots' bookkeeping (a slot a page) and the grouped decode, not the band.
    ``slow`` for the reason ``tests/test_xing4.py``'s is: the serve
    rehearsals queue behind ONE lock; run it with ``-m slow -k rehearsal``."""
    import rehearse

    line = rehearse.run_cell(CELL, 5400000054)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
