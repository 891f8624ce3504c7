"""Xing4.0-29B-A4B (``xing4_0``) through the decode engine at small widths on
the CPU: the residual stream as ``hc_mult`` rows a token mixed around every
sublayer (manifold-constrained hyper-connections), the latent blocks ``"G"``
/ ``"L"`` SERVED (prefill expanded, decode absorbed, latent rows through the
page store) under a YaRN rotation, against the plain reference
(``benchmarks/reference/xing4_decoder.py``): ``forward``'s logits, prefill
then decode across a page boundary, the parts one by one, the faults a
comparison of logits must refuse, the paths that refuse the kind by name, and
the benchmark's files. Values are taken under ``jax.jit`` (``jitted``)."""

import dataclasses
import json
import math
import os
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

import jitted  # noqa: E402
from benchmarks.reference import glm4_moe_lite_decoder as glm_ref  # noqa: E402
from benchmarks.reference import xing4_decoder as ref  # noqa: E402
from benchmarks.sweep import xing4_check as check  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmarks", "configs",
                           "Xing4.0-29B-A4B.json")
CELL = "serve-xing4-prefill-open"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

with open(CONFIG_FILE) as _f:
    PUBLISHED = json.load(_f)

# the published shape, small: a leading dense block and two routed ones, four
# rows a token, 4 heads of [16 nope | 8 rope] that return 16 (the score is
# wider than the value, as published), ranks 24 / 32, 8 experts top-3 times 2
# beside a shared one; YaRN from 16 original positions by 8, so that the 40
# positions here lie past the original context
FILE = dict(
    {k: PUBLISHED[k] for k in (
        "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
        "mhc_h_res_clamp_max", "rms_norm_eps", "rope_theta", "norm_topk_prob",
        "routed_scaling_factor", "scoring_func", "n_group", "topk_group",
        "n_shared_experts", "tie_word_embeddings", "router_scoring",
        "mla_scale_q_lora", "mla_scale_kv_lora", "first_k_dense_replace",
        "seeded_scales")},
    hidden_size=64, num_hidden_layers=3, layer_pattern="GLLLL",
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=160, moe_intermediate_size=48, n_routed_experts=8,
    num_experts_per_tok=3, vocab_size=256, max_position_embeddings=256,
    rope_scaling=dict(PUBLISHED["rope_scaling"], factor=8,
                      original_max_position_embeddings=16))
SEQ = 40


def program_cfg(file=FILE, **over):
    kw = {field: file[key]
          for field, key in PUBLISHED["program"]["fields"].items()}
    kw.update({"dtype": jnp.float32, **over})
    return LlamaConfig(**kw)


def seeded(cfg=None):
    """Seeded parameters, the gains, the choice bias and the mixes' biases
    and alphas away from their starting values, so that a misplaced or
    forgotten one shows."""
    p = jitted.init_params(cfg or program_cfg(), jax.random.PRNGKey(11))
    rng = np.random.RandomState(5)

    def jiggle(tree, name, lo, hi):
        tree[name] = tree[name] + jnp.asarray(
            rng.uniform(lo, hi, tree[name].shape), jnp.float32)

    for tree in p["layers"].values():
        for name in ("attn_norm", "mlp_norm", "q_norm", "kv_norm"):
            jiggle(tree, name, -0.5, 0.5)
        if "hc_b" in tree:
            jiggle(tree, "hc_b", -0.5, 0.5)
            jiggle(tree, "hc_alpha", -0.3, 0.3)
    jiggle(p["layers"]["latent"], "router_bias", -0.2, 0.2)
    jiggle(p, "final_norm", -0.5, 0.5)
    return p


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(
        0, FILE["vocab_size"], (2, SEQ)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return seeded()


def worst(got, want):
    """The largest difference over the largest wanted magnitude."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def reference_logits(p, toks, file=FILE):
    return np.asarray(jitted.reference(partial(ref.logits_one, file), p,
                                       toks))


# --- the whole model ------------------------------------------------------- #


@pytest.fixture(scope="module")
def program_logits(params, tokens):
    return np.asarray(jitted.forward(program_cfg(), params, tokens))


def test_forward_is_the_references_logits(program_logits, params, tokens):
    for row in range(2):
        assert worst(program_logits[row],
                     reference_logits(params, tokens[row])) < 2e-5


def test_loss_fn_runs_the_stream(params, tokens):
    """``loss_fn`` goes through ``_backbone``: its cross-entropy is the
    reference logits'."""
    got = float(jitted.loss_fn(program_cfg(), params, tokens))
    want = 0.0
    for row in range(2):
        logp = jax.nn.log_softmax(
            reference_logits(params, tokens[row, :-1]), axis=-1)
        want -= float(np.take_along_axis(
            np.asarray(logp), tokens[row, 1:, None], axis=-1).mean()) / 2
    # the routed layers add no router loss (sigmoid scores)
    assert got == pytest.approx(want, rel=2e-5)


# --- the engine: prefill, then decode through the store --------------------- #


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
def test_prefill_then_decode_through_pages_is_the_references(
        engine, params, tokens, n):
    """Across a page boundary (8 positions a page, scattered pages): prefill
    expands keys and values, decode attends absorbed over the latent rows of
    the store, the stream is four rows a token in both."""
    toks = tokens[0, :n + 4]
    got = served(engine, toks, n, [9, 2, 6])
    assert worst(got, reference_logits(params, toks)[n - 1:]) < 2e-5


@pytest.mark.parametrize("layers,segments", [
    (3, [("G", 1, False), ("L", 2, True)]),    # this file's stack
    (5, [("G", 1, False), ("L", 4, True)])])   # the cell's
def test_a_scanned_run_of_routed_layers_is_those_layers_in_line(
        layers, segments, tokens, monkeypatch):
    """``G`` in line and ONE body of ``L`` scanned over its run
    (``llama._segments``) against the same layers each in line: the logits
    of a prefill and of decode calls across a page boundary, the rows of both
    stores, the assignment shares and ``hc_sinkhorn_error`` of the stream of
    four rows."""
    cfg = program_cfg(dict(FILE, num_hidden_layers=layers))
    assert llama._segments(llama.served_kinds(cfg)) == segments
    p = seeded(cfg)
    scanned, in_line = jitted.walked_both_ways(
        lambda: llama.LlamaDecodeEngine(cfg, p, n_pages=12, page_size=8),
        tokens[0, :19], 14, monkeypatch)
    assert (scanned["traced"], in_line["traced"]) == (2, layers)
    # the layers' LARGEST, out of a scan's ys as out of a stack
    assert scanned["stats"]["ray_tpu_serve_hc_sinkhorn_error"][()] > 0.0
    jitted.assert_served_alike(scanned, in_line)


def test_bfloat16_engine_stays_near_the_reference(params, tokens):
    """As the cell runs it: bfloat16 products, a float32 stream, against the
    float32 reference on the engine's own (rounded) weights."""
    eng = llama.LlamaDecodeEngine(program_cfg(dtype=jnp.bfloat16), params,
                                  n_pages=8, page_size=8)
    toks = tokens[1, :17]
    got = served(eng, toks, 15, [5, 1, 3])
    assert worst(got, reference_logits(eng.params, toks)[14:]) < 0.08


def test_the_stores_gauges_and_leaves(engine, params):
    from ray_tpu.util.metrics import registry

    cfg = engine.cfg
    layout = llama.served_stores(cfg)
    # a store a kind, the routed one first; ONE latent row a layer
    assert [(s.kind, s.tag, s.layers, s.row, s.table) for s in layout] == [
        ("L", "latent_block", 2, (40,), "page"),
        ("G", "latent_block", 1, (40,), "page")]
    assert llama.page_rows(cfg)[0] == "latent_block"
    assert [a.shape for a in engine.stores] == [(2, 12, 8, 40), (1, 12, 8, 40)]

    def gauge(name):
        return registry().local_values(name)

    assert gauge("ray_tpu_serve_engine_page_bytes")[
        (("kind", "latent_block"),)] == 4.0 * 3 * 40
    # four rows of 64 in float32
    assert gauge("ray_tpu_serve_engine_stream_bytes")[()] == 4 * 64 * 4.0
    pages = engine.pool.alloc(2)
    engine.prefill(list(range(11)), pages)
    engine.pool.release(pages)
    assert 0.0 < gauge("ray_tpu_serve_hc_sinkhorn_error")[()] < 5e-3
    taken = [r for r in llama.prefill_attend_paths() if r["kind"] == "latent"
             and r["q_shape"] == [1, 16, 4, 24]]
    assert taken and taken[0]["path"] == "tiles"
    # what serving converts: phi, b and alpha stay float32 with the norms
    tree = llama.serving_params(program_cfg(dtype=jnp.bfloat16), params)
    f32 = {"attn_norm", "mlp_norm", "q_norm", "kv_norm", "router",
           "router_bias", "hc_phi", "hc_b", "hc_alpha"}
    for kind in ("latent", "latent_dense"):
        for name, leaf in tree["layers"][kind].items():
            assert leaf.dtype == (jnp.float32 if name in f32
                                  else jnp.bfloat16), name


def test_scheduler_drives_the_engine(engine):
    """``DecodeScheduler`` over the engine, unchanged (nothing in ``serve/``
    knows the kind): greedy tokens are the teacher-forced ones, and a
    repeated prompt is a prefix hit whose copied tail page holds latent
    rows."""
    from ray_tpu.serve.decode import DecodeScheduler
    from test_kv_cache import _run_all

    sched = DecodeScheduler(engine)
    req = {"prompt": [int(t) for t in np.random.RandomState(2).randint(
        0, 256, size=11)], "max_tokens": 6}
    cold = json.loads(_run_all(sched, [("c", req)])["c"][-1][1])
    warm = json.loads(_run_all(sched, [("w", req)])["w"][-1][1])
    assert warm["cached_prefix"] is True
    assert warm["tokens"] == cold["tokens"] and len(cold["tokens"]) == 6
    toks = req["prompt"] + cold["tokens"]
    logits = np.asarray(jitted.forward(engine.cfg, engine.params,
                                       np.asarray([toks])))[0]
    assert [int(t) for t in logits[10:16].argmax(-1)] == cold["tokens"]


# --- the parts, one by one --------------------------------------------------- #


@pytest.fixture(scope="module")
def stream(params, tokens):
    """A stream whose four rows differ: the embedding, scaled a row."""
    x = params["embedding"][tokens[0]]
    X = x[:, None, :] * jnp.asarray([1.0, -0.5, 2.0, 0.25])[None, :, None]
    return X + 0.1 * jnp.asarray(np.random.RandomState(1).randn(*X.shape),
                                 jnp.float32)


def _hc(params, kind="latent", layer=0, sub=0):
    tree = params["layers"][kind]
    return tuple(tree[w][layer, sub] for w in ("hc_phi", "hc_b", "hc_alpha"))


# the two forms a program carries the stream in (``llama.widen_stream``): the
# full forward's rows side by side, the serving programs' tuple of rows
FORMS = {"side by side": lambda X: X.reshape(1, X.shape[0], -1),
         "apart": lambda X: tuple(X[None, :, j] for j in range(X.shape[1]))}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("sub", [0, 1])
def test_the_mix_is_the_references_and_lies_on_its_manifold(params, stream,
                                                            sub, form):
    cfg, hc = program_cfg(), _hc(params, sub=sub)
    pre, post, res, error = jax.jit(partial(llama.hyper_mix, cfg))(
        *hc, FORMS[form](stream))
    want = jitted.reference(partial(ref.hyper_mix, FILE), stream, *hc)
    # the program keeps the positions last
    for got, w in zip((pre[:, 0].T, post[:, 0].T,
                       jnp.moveaxis(res[:, :, 0], -1, 0)), want):
        assert worst(got, w) < 1e-5
    assert float(pre.min()) > 0.0 and float(pre.max()) < 1.0
    assert float(post.min()) > 0.0 and float(post.max()) < 2.0
    rows = np.abs(np.asarray(res.sum(axis=1)) - 1.0).max()
    cols = np.abs(np.asarray(res.sum(axis=0)) - 1.0).max()
    # the last step normalises the columns; the rows are off by what twenty
    # iterations leave
    assert cols < 1e-5 and rows < 5e-3
    assert float(error) == pytest.approx(max(rows, cols), rel=1e-3)
    assert float(res.min()) > 0.0


@pytest.mark.parametrize("iters,limit", [(1, 0.8), (5, 0.1), (20, 5e-3)])
def test_sinkhorns_error_falls_with_its_iterations(params, stream, iters,
                                                   limit):
    cfg = program_cfg(hc_sinkhorn_iters=iters)
    T = stream.shape[0]
    *_, error = jax.jit(partial(llama.hyper_mix, cfg))(
        *_hc(params), stream.reshape(1, T, -1))
    assert float(error) < limit
    if iters == 1:
        assert float(error) > 5e-3  # and one is not enough


@pytest.mark.parametrize("form", list(FORMS))
def test_read_out_and_write_back_are_the_references(params, stream, form):
    """In either form, and the stream leaves in the form it came in."""
    cfg, hc = program_cfg(), _hc(params)
    T, n, d = stream.shape
    y = stream[:, 1] * 0.5 + 1.0
    seen = {}

    def sub(h):
        seen["h"] = h
        return y[None], None

    got, _, _ = llama.hyper_connected(cfg, hc, FORMS[form](stream), sub)
    pre, post, res = jitted.reference(partial(ref.hyper_mix, FILE), stream,
                                      *hc)
    assert worst(seen["h"][0], ref.read_out(pre, stream)) < 1e-5
    if form == "apart":
        assert isinstance(got, tuple) and len(got) == n
        got = jnp.stack(got, axis=2)
    assert worst(got.reshape(T, n, d),
                 ref.write_back(res, post, stream, y)) < 1e-5


def test_without_rows_a_sublayer_is_the_plain_residual(stream):
    cfg = program_cfg(hc_mult=1)
    x = stream[None, :, 0]
    got, aux, error = llama.hyper_connected(cfg, None, x,
                                            lambda h: (2.0 * h, "aux"))
    np.testing.assert_array_equal(got, x + 2.0 * x)
    assert aux == "aux" and error is None
    assert llama.widen_stream(cfg, x) is x
    assert llama.widen_stream(cfg, x, apart=True) is x
    assert llama.collapse_stream(cfg, x) is x


def test_the_stream_starts_as_copies_and_ends_as_the_sum(stream):
    cfg = program_cfg()
    T, n, d = stream.shape
    x = stream[None, :, 0]
    wide = llama.widen_stream(cfg, x)
    np.testing.assert_array_equal(wide.reshape(T, n, d),
                                  jnp.broadcast_to(x[0, :, None], (T, n, d)))
    apart = llama.widen_stream(cfg, x, apart=True)
    assert len(apart) == n and all(row is x for row in apart)
    for form in FORMS.values():
        got = llama.collapse_stream(cfg, form(stream))[0]
        assert worst(got, stream.sum(axis=1)) < 1e-6
        # not the mean (which a final norm would hide from the logits)
        assert worst(got, stream.mean(axis=1)) > 0.5


def test_yarn_keeps_the_fast_frequencies_and_divides_the_slow():
    cfg = spec_cfg()
    got = llama.yarn_frequencies(cfg)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert got.shape == (32,) and got.dtype == np.float32
    # low = 10, high = 23 at the published numbers
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    ratio = got[11:23] / plain[11:23]
    assert np.all(np.diff(ratio) < 0) and ratio[0] < 1 and ratio[-1] > 1 / 64
    np.testing.assert_allclose(got, ref.yarn_frequencies(PUBLISHED),
                               rtol=1e-6)
    assert llama.yarn_softmax_factor(cfg) == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2)
    assert ref.softmax_scale(PUBLISHED) == pytest.approx(
        192 ** -0.5 * llama.yarn_softmax_factor(cfg))
    assert llama.yarn_softmax_factor(program_cfg(rope_yarn=None)) == 1.0


def spec_cfg():
    from benchmarks.lib import spec

    return spec.program_config(dict(PUBLISHED))


def test_rotation_at_a_far_position_is_the_references():
    """YaRN's rotation of the rope slice at position 16,000, published
    numbers, against the reference's."""
    cfg = spec_cfg()
    x = jnp.asarray(np.random.RandomState(0).randn(1, 2, 3, 64), jnp.float32)
    at = jnp.asarray([[15999, 16000]], jnp.int32)
    from ray_tpu.ops.layers import rotary_embedding

    got, _ = rotary_embedding(x, x, at, cfg.rope_theta, interleaved=True,
                              inv_freq=llama.yarn_frequencies(cfg))
    want = ref._rope(jnp.zeros((16001, 3, 64)).at[15999:].set(x[0]),
                     ref.yarn_frequencies(PUBLISHED))[15999:]
    assert worst(got[0], want) < 2e-3  # float32 angles near 16,000 radians


def _layer_leaves(params, kind, row):
    return jax.tree.map(lambda a: a[row], params["layers"][kind])


@pytest.mark.parametrize("kind", ["latent_dense", "latent"])
def test_attention_is_the_references(params, tokens, kind):
    cfg, p = program_cfg(), _layer_leaves(params, kind, 0)
    h = params["embedding"][tokens[0]] * 3.0
    got, latent = jax.jit(lambda h: llama._latent_half(
        cfg, p, h[None], llama.positions_of(1, SEQ),
        partial(llama.attend_latent_expanded, cfg)))(h)
    want = jitted.reference(partial(ref.attention, FILE), h, p)
    assert worst(got[0], want) < 1e-5
    assert latent.shape == (1, SEQ, 40)


def test_absorbed_decode_is_the_expanded_prefill(params, tokens):
    """The last position attended absorbed over the others' latent rows is
    what the expanded attention gives it."""
    cfg, p = program_cfg(), _layer_leaves(params, "latent", 1)
    h = (params["embedding"][tokens[1]] * 3.0)[None]

    @jax.jit
    def both(h):
        whole, latent = llama._latent_half(
            cfg, p, h, llama.positions_of(1, SEQ),
            partial(llama.attend_latent_expanded, cfg))
        last, _ = llama._latent_half(
            cfg, p, h[:, -1:], jnp.full((1, 1), SEQ - 1, jnp.int32),
            partial(llama._attend_latent_cached, cfg,
                    jnp.pad(latent[0, :-1], ((0, 9), (0, 0))), SEQ - 1))
        return last[0, 0], whole[0, -1]

    assert worst(*both(h)) < 1e-5


def test_router_and_shared_expert_are_the_references(params, tokens):
    cfg, p = program_cfg(), _layer_leaves(params, "latent", 0)
    h = params["embedding"][tokens[0]] * 3.0
    got, _ = jax.jit(lambda h: llama._mlp_half(cfg, p, h[None]))(h)
    want = jitted.reference(partial(ref.moe, FILE), h, p)
    assert worst(got[0], want) < 1e-5
    alone = jitted.reference(ref.shared_expert, h, p)
    assert 0.05 < worst(want - alone, want) < 1.0  # both parts weigh
    weight = jitted.reference(partial(ref.route, FILE), h, p)
    assert np.all((np.asarray(weight) > 0).sum(-1) == 3)
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.0, rtol=1e-5)


# --- the faults a comparison of logits must refuse --------------------------- #

LIMIT = 2e-4  # ten times what the sound program reads


@pytest.mark.parametrize("fault", [n for n in check.WRONG
                                   if n != "swapped_phis"])
def test_a_faulty_reference_is_refused(fault, program_logits, params,
                                       tokens):
    """The reference with ONE thing wrong (``xing4_check.WRONG``, what the
    chip's check plants too) differs from the program's logits by more than
    ``LIMIT``, which the sound one stays ten times under."""
    with check.wrong_reference(ref, fault):
        assert worst(program_logits[0],
                     reference_logits(params, tokens[0])) > LIMIT


def test_swapped_phis_are_refused(program_logits, params, tokens):
    assert worst(program_logits[0], reference_logits(
        check.swapped_phis(params), tokens[0])) > LIMIT


def test_the_clamp_holds_where_the_exponential_would_not(params, tokens,
                                                         monkeypatch):
    """``alpha_res`` driven so that ``R`` passes 30 (and 88, where float32's
    exponential ends): the program clamps in front of the exponential and is
    the reference; a reference without the clamp is not finite."""
    driven = {**params, "layers": {
        kind: {**tree, "hc_alpha": tree["hc_alpha"].at[..., 2].set(60.0)}
        for kind, tree in params["layers"].items()}}
    got = np.asarray(jitted.forward(program_cfg(), driven, tokens[:1]))[0]
    assert np.all(np.isfinite(got))
    assert worst(got, reference_logits(driven, tokens[0])) < 2e-4
    monkeypatch.setattr(ref, "hyper_mix", check.mix_variant(no_clamp=True))
    wrong = reference_logits(driven, tokens[0])
    assert not np.all(np.isfinite(wrong)) or worst(got, wrong) > LIMIT


def test_the_streams_sum_is_not_their_mean(params, stream):
    """The final norm hides a mean from the logits (it is the sum over
    four): the collapse itself is held to the sum."""
    cfg = program_cfg()
    T = stream.shape[0]
    got = llama.collapse_stream(cfg, stream.reshape(1, T, -1))[0]
    assert worst(got, stream.mean(axis=1)) > LIMIT
    assert worst(got, stream.sum(axis=1)) < 1e-6


# --- one row a token and a plain rotation: latent_block's logits ------------- #

GLM_FILE = {
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "layer_pattern": "GLL", "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 32, "intermediate_size": 160, "moe_intermediate_size": 48,
    "n_routed_experts": 4, "router_experts": 16, "first_expert": 4,
    "num_experts_per_tok": 3, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "rope_theta": 1000000, "rms_norm_eps": 1e-5, "vocab_size": 256,
    "num_nextn_predict_layers": 0,
}


def glm_cfg(**over):
    """A configuration of GLM-4.7-Flash's kind (one row a token, a plain
    rotation, a held range of experts), with no prediction module."""
    f = GLM_FILE
    kw = dict(
        vocab_size=f["vocab_size"], dim=f["hidden_size"],
        n_layers=f["num_hidden_layers"], n_heads=f["num_attention_heads"],
        n_kv_heads=f["num_attention_heads"], attn_head_dim=32,
        mlp_dim=f["moe_intermediate_size"],
        dense_mlp_dim=f["intermediate_size"],
        shared_mlp_dim=f["moe_intermediate_size"], max_seq_len=128,
        rope_theta=f["rope_theta"], norm_eps=f["rms_norm_eps"],
        layer_pattern=f["layer_pattern"], q_lora_rank=f["q_lora_rank"],
        kv_lora_rank=f["kv_lora_rank"],
        qk_nope_head_dim=f["qk_nope_head_dim"],
        qk_rope_head_dim=f["qk_rope_head_dim"], v_head_dim=f["v_head_dim"],
        mla_scale_q_lora=False, mla_scale_kv_lora=False,
        num_experts=f["n_routed_experts"], router_experts=f["router_experts"],
        first_expert=f["first_expert"],
        experts_per_token=f["num_experts_per_tok"], norm_topk_prob=True,
        routed_scale=f["routed_scaling_factor"], router_scoring="sigmoid",
        dtype=jnp.float32)
    kw.update(over)
    return LlamaConfig(**kw)


def test_an_engine_of_glms_kind_is_its_reference_and_latent_blocks(tokens):
    """With ``hc_mult`` 1 and a plain rotation the served blocks trace no
    mix: prefill and decode through the store are
    ``glm4_moe_lite_decoder.logits_one``'s logits, and ``forward``'s (the
    trainer's :func:`latent_block` over plain attention)."""
    cfg = glm_cfg()
    p = seeded(cfg)
    eng = llama.LlamaDecodeEngine(cfg, p, n_pages=8, page_size=8)
    toks = tokens[0, :18]
    got = served(eng, toks, 14, [5, 1, 3])
    want = np.asarray(jitted.reference(
        partial(glm_ref.logits_one, GLM_FILE), p, toks))
    assert worst(got, want[13:]) < 2e-5
    assert worst(got, jitted.forward(cfg, p, toks[None])[0, 13:]) < 2e-5


# --- who refuses what --------------------------------------------------------- #


def _mesh(spec_str, n):
    from ray_tpu.train.spmd import build_train_mesh

    return build_train_mesh(spec_str, jax.devices()[:n])


def _spmd(cfg):
    from ray_tpu.train.spmd import make_spmd_train_step

    return make_spmd_train_step(cfg, _mesh("", 1))


def _gspmd(cfg):
    from ray_tpu.parallel.mesh import make_mesh

    return llama.make_train_step(cfg, make_mesh(devices=jax.devices()[:1]))


def _pipeline(cfg):
    from ray_tpu.parallel.mesh import make_mesh

    return llama.make_pipeline_train_step(
        cfg, make_mesh(axis_sizes={"pipe": 2}), 2)


@pytest.mark.parametrize("step", [_spmd, _gspmd, _pipeline])
@pytest.mark.parametrize("what", ["four rows a token",
                                  "a score wider than its value"])
def test_the_train_steps_refuse_it_by_name(step, what):
    cfg = program_cfg() if what == "four rows a token" else program_cfg(
        hc_mult=1)
    with pytest.raises(NotImplementedError) as e:
        step(cfg)
    msg = str(e.value)
    assert "hyper-connections" in msg and f"hc_mult={cfg.hc_mult}" in msg
    assert "v_head_dim=16" in msg


def test_the_engine_still_refuses_a_prediction_module():
    with pytest.raises(NotImplementedError) as e:
        llama.LlamaDecodeEngine(glm_cfg(mtp_layers=1))
    msg = str(e.value)
    assert "prediction module" in msg and "mtp_layers=1" in msg
    assert "'L' / 'G'" not in msg
    assert {"L", "G"} <= set(llama.SERVED)
    with pytest.raises(NotImplementedError, match="ONE family"):
        llama.LlamaDecodeEngine(glm_cfg(layer_pattern="LLL"))  # a part


@pytest.mark.parametrize("over,says", [
    ({"hc_mult": 0}, "hc_mult=0"),
    ({"hc_sinkhorn_iters": 0}, "hc_sinkhorn_iters > 0"),
    ({"hc_res_clamp_min": 40.0}, "hc_res_clamp_min"),
    ({"rope_yarn": {"type": "linear", "factor": 4}}, "type 'yarn'"),
    ({"rope_yarn": dict(FILE["rope_scaling"], mscale=0.7)}, "amplitude"),
    ({"seeded_scales": {"w_up": 2.0}}, "seeded_scales"),
])
def test_a_config_that_is_not_the_kind_is_refused(over, says):
    with pytest.raises(ValueError, match=says):
        program_cfg(**over)


def test_rows_and_yarn_belong_to_the_latent_blocks():
    with pytest.raises(ValueError, match="'L' / 'G' blocks alone"):
        dataclasses.replace(LlamaConfig.debug(), hc_mult=4)
    with pytest.raises(ValueError, match="latent half"):
        dataclasses.replace(LlamaConfig.debug(),
                            rope_yarn=FILE["rope_scaling"])
    # a config is hashed: the group is kept as pairs, and given back nested
    cfg = program_cfg()
    assert hash(cfg) == hash(program_cfg())
    assert cfg.rope_scaling == FILE["rope_scaling"]


def test_seeded_scales_are_the_files_and_touch_two_leaves():
    """The starting scales are the configuration file's data, not the
    stream's width's: they multiply the attention's ``wo`` and the routed
    experts' ``w_down`` and nothing else, and a config without them (GLM's
    kind, the same blocks) starts every matrix at its fan-in."""
    with pytest.raises(ValueError, match="seeded_scales"):
        dataclasses.replace(LlamaConfig.debug(), seeded_scales={"wo": 2.0})
    cfg = program_cfg()
    assert dict(cfg.seeded_scales) == PUBLISHED["seeded_scales"] \
        == {"wo": 16.0, "expert_down": 0.25}
    key = jax.random.PRNGKey(2)
    scaled = jitted.init_params(cfg, key)["layers"]
    plain = jitted.init_params(
        dataclasses.replace(cfg, seeded_scales=()), key)["layers"]
    for kind, tree in scaled.items():
        for name, leaf in tree.items():
            by = {"wo": 16.0, "w_down": 0.25 if kind == "latent" else 1.0
                  }.get(name, 1.0)
            np.testing.assert_array_equal(
                np.asarray(leaf), by * np.asarray(plain[kind][name]),
                err_msg=f"{kind}.{name}")


# --- the configuration file and the cell ------------------------------------ #


@pytest.fixture(scope="module")
def cell():
    from benchmarks.lib import spec

    return spec.cell_bundle(CELL)


def test_the_file_holds_the_published_row_but_its_depth(cell):
    file = cell["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of published rows is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert file["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert file["published"][key] == value == 40 and file[key] == 5
        else:
            assert file[key] == value, key
    entry = next(c for c in cell["bench"]["configs"]
                 if c["name"] == "Xing4.0-29B-A4B")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert ref.kinds_of(file) == ["G", "L", "L", "L", "L"]
    for key in ("deployment", "reduced_why", "assumed", "reference",
                "program", "correct"):
        assert file[key], key
    assert file["deployment"]["chips_sharing_a_layer"] == 1


def test_the_programs_count_is_the_files_arithmetic(cell):
    from benchmarks.lib import spec

    cfg = spec.program_config(cell["config"])
    assert (cfg.kinds, cfg.hc_mult, cfg.hc_sinkhorn_iters) == ("GLLLL", 4, 20)
    assert not cfg.mla_scale_q_lora and not cfg.mla_scale_kv_lora
    assert cfg.rope_scaling == cell["config"]["rope_scaling"]
    d = 3584
    mla = (d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256
           + 32 * 128 * d + 768 + 512)
    assert mla == 28_411_136
    hc = 2 * (4 * d * 24 + 24 + 3)
    assert hc == 688_182
    dense = mla + 3 * d * 9216 + 2 * d + hc
    routed = (mla + 64 * 3 * d * 1024 + 3 * d * 1024 + d * 64 + 64 + 2 * d
              + hc)
    assert (dense, routed) == (128_196_918, 744_989_046)
    total = dense + 4 * routed + 2 * 131072 * d + d
    assert cfg.num_params() == total == 4_047_680_782
    # and the tree's leaves are that count, at the test's size
    small = program_cfg()
    assert small.num_params() == sum(
        a.size for a in jax.tree.leaves(jax.eval_shape(
            partial(llama.init_params, small), jax.random.PRNGKey(0))))


def test_benchmark_files_fit_together_with_the_new_cell(cell):
    from benchmarks.checks import test_yardstick
    from benchmarks.lib import spec
    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    test_yardstick.test_benchmark_files_fit_together()
    assert (cell["cell"]["chips"], cell["cell"]["traffic"]) == (
        1, "prefill-open-2048-16000")
    assert sorted(m["name"] for m in cell["end_to_end"]) == [
        "setup_s", "ttft_p95_ms"]
    names = {m["name"] for m in cell["per_layer"]}
    longcat = {m["name"] for m in spec.cell_bundle(
        "serve-longcatflash-prefill-open")["per_layer"]}
    assert names == longcat and "serve.window_slots_ms" not in names
    tr, dep = cell["traffic"], cell["config"]["deployment"]
    assert (tr["kind"], tr["prompt_tokens"], tr["output_tokens"]) == (
        "open_loop", {"dist": "log_uniform", "min": 2048, "max": 16000},
        {"dist": "const", "value": 16})
    assert tr["schedule_seed"] == 0
    shapes = shapes_of(tr, dep["page_size"])
    assert shapes == {"prefill": list(range(2, 17)),
                      "decode": list(range(3, 17))}
    assert check_prompt_len(shapes, dep["page_size"]) == 3070
    # 4 running sequences of the longest context and a copied tail page each
    assert dep["n_pages"] >= dep["decode_max_batch"] * (
        shapes["decode"][-1] + 1)
    assert spec.resolve(cell["config"]["reference"] + ":logits_one")
    # the rehearsal's tiny sizes still build the dense and a routed block
    tiny = spec.cell_bundle(CELL, rehearsal=True)
    assert spec.program_config(tiny["config"]).kinds == "GL"


@pytest.mark.slow  # a FOURTH serve rehearsal behind the one lock: by hand
@pytest.mark.deadline(170)
def test_the_cells_rehearsal_runs_end_to_end():
    """``--rehearsal`` of the new cell on the CPU with NO edit of
    ``rehearsal.json``: the pattern follows the depth it is given (``GL``),
    four rows of 64 through ``serve.run``, the scheduler and the harness's
    check. ``slow``: three serve rehearsals already queue behind the ONE
    lock at 117-170 s of their 170 s in a whole run (ROADMAP, Reach); run it
    with ``-m slow -k rehearsal`` (36 s alone)."""
    import rehearse
    from benchmarks.lib import spec

    tiny = spec.cell_bundle(CELL, rehearsal=True)
    cfg = spec.program_config(tiny["config"])
    assert (cfg.kinds, cfg.hc_mult, cfg.dim) == ("GL", 4, 64)
    line = rehearse.run_cell(CELL, 5200000052)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
