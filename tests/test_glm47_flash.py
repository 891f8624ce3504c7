"""GLM-4.7-Flash (``glm4_moe_lite``) through the trainer at small widths on
the CPU: the latent blocks outside ``"S"`` (kinds ``"G"`` and ``"L"``), the
ungated shared expert and the multi-token-prediction module against the plain
reference (``benchmarks/reference/glm4_moe_lite_decoder.py``): logits, the
loss with the module, every gradient leaf, the parts one by one, the faults
the loss must refuse, the shares adding up to the uncut layer, the steps that
run it and the paths that refuse it by name. Values and gradients are taken
under ``jax.jit`` (``jitted``)."""

import dataclasses
import json
import os
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

import jitted  # noqa: E402
from benchmarks.reference import glm4_moe_lite_decoder as ref  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmarks", "configs", "GLM-4.7-Flash.json")
CELL = "train-glm47flash-1chip"
# the published rows the driver draws from: outside the checkout
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the published shape, small: a leading dense block and two routed ones, 4
# heads of [24 nope | 8 rope] that return 32, ranks 24 / 32; a router over 16
# of which this share holds 4 (from the 4th on), top-3, times 1.8; one module
FILE = {
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "layer_pattern": "G" + "L" * 46, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 32, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 32, "mla_scale_q_lora": False, "mla_scale_kv_lora": False,
    "intermediate_size": 160, "moe_intermediate_size": 48,
    "n_routed_experts": 4, "router_experts": 16, "first_expert": 4,
    "num_experts_per_tok": 3, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "router_scoring": "sigmoid", "rope_theta": 1000000, "rms_norm_eps": 1e-5,
    "vocab_size": 256, "max_position_embeddings": 128,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
    "mtp_loss_weight": 0.3,
}
UNCUT = dict(FILE, n_routed_experts=16, first_expert=0)
SEQ = 40


def program_cfg(file=FILE, **over):
    with open(CONFIG_FILE) as f:
        fields = json.load(f)["program"]["fields"]
    kw = {field: file[key] for field, key in fields.items()}
    kw.update({"dtype": jnp.float32, **over})
    return LlamaConfig(**kw)


def seeded(file):
    """Seeded parameters for ``file``, the gains and the choice bias away
    from their starting values, so that a misplaced or forgotten one shows."""
    p = jitted.init_params(program_cfg(file), jax.random.PRNGKey(11))
    rng = np.random.RandomState(5)

    def jiggle(tree, name, lo, hi):
        tree[name] = tree[name] + jnp.asarray(
            rng.uniform(lo, hi, tree[name].shape), jnp.float32)

    for tree in (p["layers"]["latent"], p["layers"]["latent_dense"],
                 p["mtp"]["layers"]["latent"]):
        for name in ("attn_norm", "mlp_norm", "q_norm", "kv_norm"):
            jiggle(tree, name, -0.5, 0.5)
    for tree in (p["layers"]["latent"], p["mtp"]["layers"]["latent"]):
        jiggle(tree, "router_bias", -0.2, 0.2)
    for name in ("enorm", "hnorm", "final_norm"):
        jiggle(p["mtp"], name, -0.5, 0.5)
    jiggle(p, "final_norm", -0.5, 0.5)
    return p


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(
        0, FILE["vocab_size"], (2, SEQ + 1)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    return {"cut": seeded(FILE), "uncut": seeded(UNCUT)}


FILES = {"cut": FILE, "uncut": UNCUT}


def rel(got, want):
    return float(abs(got - want) / abs(want))


def worst(got, want):
    """The largest difference over the largest wanted magnitude."""
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want))
                                                  + 1e-30))


# --- the whole model ------------------------------------------------------- #


@pytest.mark.parametrize("share", list(FILES))
def test_logits_against_the_reference(share, params, tokens):
    file, p = FILES[share], params[share]
    got = jitted.forward(program_cfg(file), p, tokens[:, :-1])
    for row in range(2):
        want = jitted.reference(partial(ref.logits_one, file), p,
                                tokens[row, :-1])
        assert worst(got[row], want) < 2e-5


@pytest.mark.parametrize("share", list(FILES))
@pytest.mark.parametrize("dtype,limit", [("float32", 1e-5),
                                         ("bfloat16", 6e-3)])
def test_the_loss_with_the_module_against_the_reference(
        share, dtype, limit, params, tokens):
    file, p = FILES[share], params[share]
    got = jitted.loss_fn(program_cfg(file, dtype=jnp.dtype(dtype)), p, tokens)
    want = jitted.reference(partial(ref.loss, file), p, tokens)
    assert rel(float(got), float(want)) < limit


def test_loss_parts_reports_both_losses_apart(params, tokens):
    cfg = program_cfg()
    total, report = jax.jit(partial(llama.loss_parts, cfg))(params["cut"],
                                                            tokens)
    main, ahead = jitted.reference(partial(ref.losses, FILE), params["cut"],
                                   tokens)
    assert rel(float(report["main_loss"]), float(main)) < 1e-5
    assert rel(float(report["mtp_loss"]), float(ahead)) < 1e-5
    assert float(total) == pytest.approx(
        float(main) + 0.3 * float(ahead), rel=1e-5)
    # six scalars: the router's four of a held sigmoid router, and the two
    assert set(report) == {"max_load_ratio", "dropped", "held_share",
                           "held_chunks", "main_loss", "mtp_loss"}
    assert float(report["dropped"]) == 0.0


@pytest.fixture(scope="module")
def gradients(params, tokens):
    cfg = program_cfg()
    _, got = jitted.value_and_grad(partial(llama.loss_fn, cfg),
                                   params["cut"], tokens)
    want = jitted.reference(jax.grad(partial(ref.loss, FILE)), params["cut"],
                            tokens)
    return got, want


def test_every_gradient_leaf_against_the_reference(gradients):
    got, want = gradients
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert len(flat) == 53
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # for the choice alone: no gradient
            assert float(jnp.abs(g).max()) == 0.0 == float(jnp.abs(w).max())
            continue
        assert float(jnp.abs(w).max()) > 0, name
        assert worst(g, w) < 5e-5, name


# --- part by part ---------------------------------------------------------- #


def _layer(p, kind="latent", i=0):
    return jax.tree.map(lambda a: a[i], p["layers"][kind])


@pytest.fixture(scope="module")
def stream():
    return jnp.asarray(np.random.RandomState(7).normal(
        size=(1, SEQ, FILE["hidden_size"])), jnp.float32)


def _plain(q, k, v):
    from ray_tpu.parallel.ring_attention import plain_attention

    return plain_attention(q, k, v, causal=True)


def test_latent_attention_hands_the_kernel_what_the_reference_attends(
        params, stream):
    """q, k, v as ``attend`` gets them: a head's ``[nope | rope]``, the ONE
    rotated slice (pairs (2i, 2i + 1), theta 1e6) the same for every head,
    no ``sqrt(dim / rank)`` factor; all three of the value's width."""
    cfg, p = program_cfg(), _layer(params["cut"])

    def handed(h):
        seen = {}

        def attend(q, k, v):
            seen.update(q=q, k=k, v=v)
            return _plain(q, k, v)

        llama._latent_half(cfg, p, h, llama.positions_of(1, SEQ),
                           partial(llama.attend_latent_heads, cfg, attend))
        return seen

    seen = jax.jit(handed)(stream)
    q, k, v = jitted.reference(partial(ref.qkv, FILE), stream[0], p)
    assert seen["q"].shape == seen["k"].shape == seen["v"].shape \
        == (1, SEQ, 4, 32)
    for name, want in (("q", q), ("k", k), ("v", v)):
        assert worst(seen[name][0], want) < 1e-5, name
    # the shared slice: one row a position, not one a head
    rope = np.asarray(seen["k"][0, :, :, 24:])
    assert np.array_equal(rope[:, 0], rope[:, 3])
    assert not np.allclose(rope[1], rope[2])  # and rotated by position


@pytest.mark.parametrize("fault,limit", [
    (None, 1e-5),
    ("scale_q", 1e-2), ("scale_kv", 1e-2), ("half_split_pairs", 1e-2),
    ("scale_by_nope", 1e-2)])
def test_latent_attention_against_the_reference(fault, limit, params, stream):
    """The sound half within 1e-5; each fault more than a hundredth away:
    LongCat's ``sqrt(dim / rank)`` on either side, the rotation's other
    pairing, a softmax scale of ``1 / sqrt(qk_nope_head_dim)``."""
    p = _layer(params["cut"])
    cfg = program_cfg(**({"mla_scale_q_lora": True} if fault == "scale_q"
                         else {"mla_scale_kv_lora": True}
                         if fault == "scale_kv" else {}))
    attend = _plain
    if fault == "scale_by_nope":
        attend = lambda q, k, v: _plain(q * (32 / 24) ** 0.5, k, v)  # noqa: E731
    rotate = llama.rotary_embedding
    if fault == "half_split_pairs":
        llama.rotary_embedding = lambda q, k, pos, theta, interleaved: \
            rotate(q, k, pos, theta, interleaved=False)
    try:
        got = jax.jit(lambda h: llama._latent_half(
            cfg, p, h, llama.positions_of(1, SEQ),
            partial(llama.attend_latent_heads, cfg, attend))[0])(stream)
    finally:
        llama.rotary_embedding = rotate
    want = jitted.reference(partial(ref.attention, FILE), stream[0], p)
    err = worst(got[0], want)
    assert (err < limit) if fault is None else (err > limit), err


def _mlp(file, p, h, **over):
    cfg = program_cfg(file, **over)
    return jax.jit(lambda h: llama._mlp_half(cfg, p, h)[0])(h)


def test_router_sigmoid_bias_in_the_choice_only_renormalise_then_scale(
        params, stream):
    """``ref.route``: weights are the sigmoid scores of the chosen,
    renormalised to 1 THEN times 1.8; the bias moves the choice and no
    weight. And the program's routed sum is the reference's for that rule."""
    p = _layer(params["uncut"])
    h = stream[0]
    w = np.asarray(jitted.reference(partial(ref.route, UNCUT), h, p))
    assert ((w > 0).sum(-1) == 3).all()
    np.testing.assert_allclose(w.sum(-1), 1.8, rtol=1e-5)
    s = np.asarray(jax.nn.sigmoid(h @ p["router"]))
    chosen = np.argsort(-(s + np.asarray(p["router_bias"])), axis=-1)[:, :3]
    assert all(set(np.nonzero(w[t])[0]) == set(chosen[t])
               for t in range(SEQ))
    unbiased = np.argsort(-s, axis=-1)[:, :3]
    assert any(set(chosen[t]) != set(unbiased[t]) for t in range(SEQ))
    picked = np.take_along_axis(s, chosen, -1)
    np.testing.assert_allclose(
        np.take_along_axis(w, chosen, -1),
        picked / picked.sum(-1, keepdims=True) * 1.8, rtol=1e-5)
    got = _mlp(UNCUT, p, stream)
    want = jitted.reference(partial(ref.moe, UNCUT), h, p)
    assert worst(got[0], want) < 1e-5


@pytest.mark.parametrize("fault,limit", [
    (None, 1e-5), ("unscaled", 5e-2), ("no_shared", 5e-2),
    ("gated_shared", 5e-2), ("softmax", 5e-2)])
def test_the_routed_mlp_against_the_reference(fault, limit, params, stream):
    """This share's routed MLP (4 of 16 experts, the shared one ungated)
    within 1e-5 of the reference; each fault away from it: weights not times
    1.8, the shared expert dropped or put behind a sigmoid gate, softmax
    scores."""
    p = dict(_layer(params["cut"]))
    over = {}
    if fault == "unscaled":
        over["routed_scale"] = 1.0
    if fault == "softmax":
        over["router_scoring"] = "softmax"
    if fault == "no_shared":
        p["shared_down"] = jnp.zeros_like(p["shared_down"])
    if fault == "gated_shared":  # sigmoid(h . 0) = 1 / 2 in front of it
        p["shared_down"] = 0.5 * p["shared_down"]
    got = _mlp(FILE, p, stream, **over)
    want = jitted.reference(partial(ref.moe, FILE), stream[0],
                            _layer(params["cut"]))
    err = worst(got[0], want)
    assert (err < limit) if fault is None else (err > limit), err


def test_the_shares_add_up_to_the_uncut_layer(params, stream):
    """The four shares' routed MLPs (experts 0-3, 4-7, 8-11, 12-15 of the
    uncut tree), each with the shared expert, less three shared experts, are
    the uncut REFERENCE's layer: the shared expert counts once."""
    p = _layer(params["uncut"])
    total = 0.0
    for first in range(0, 16, 4):
        mine = dict(p, **{w: p[w][first:first + 4]
                          for w in ("w_gate", "w_up", "w_down")})
        total = total + _mlp(dict(FILE, first_expert=first), mine, stream)[0]
    shared = jitted.reference(ref.shared_expert, stream[0], p)
    want = jitted.reference(partial(ref.moe, UNCUT), stream[0], p)
    assert worst(total - 3 * shared, want) < 1e-5


@pytest.mark.parametrize("kind,name", [("G", "dense"), ("L", "routed")])
def test_a_whole_block_against_the_reference(kind, name, params, stream):
    p = _layer(params["cut"], ref.STACK[name])
    cfg = program_cfg()
    got = jax.jit(lambda x: llama.latent_block(cfg, kind, _plain, x, p)[0])(
        stream)
    want = jitted.reference(lambda x, q: ref.block(FILE, x, q, name),
                            stream[0], p)
    assert worst(got[0], want) < 1e-5
    if kind == "G":  # the dense half alone too, at its own width
        assert p["w_gate"].shape == (64, 160)
        h = stream.astype(jnp.float32)
        assert worst(jax.jit(partial(llama._dense_mlp, cfg, p))(h)[0],
                     jitted.reference(partial(ref.dense, FILE), h[0], p)) \
            < 1e-5


# --- the module, and the faults a loss must refuse -------------------------- #


def module_losses(file, params, tokens, fault=None):
    """``(L_main, L_mtp)`` built from the reference's PARTS, with one fault
    switched on: what the program's losses must NOT be."""
    eps, d = file["rms_norm_eps"], file["hidden_size"]
    m, emb = params["mtp"], params["embedding"]
    head = params["lm_head"]
    block = jax.tree.map(lambda a: a[0], m["layers"]["latent"])

    def nll(states, targets):
        return ref._nll_sum(states, head, targets)

    def row_losses(row):
        hidden = ref.hidden_one(file, params, row[:-1])
        ids = row[:-1] if fault == "Emb(t_i)" else row[1:]
        e = ref._rms_norm(emb[ids], m["enorm"], eps)
        h = hidden if fault == "no RMSNorm_h" else ref._rms_norm(
            hidden, m["hnorm"], eps)
        both = [h, e] if fault == "halves swapped" else [e, h]
        y = ref.block(file, jnp.concatenate(both, axis=-1) @ m["eh_proj"],
                      block, "routed")
        y = ref._rms_norm(y, m["final_norm"], eps)
        if fault == "target t_{i+1}":
            return nll(hidden, row[1:]), nll(y[:-1], row[1:-1])
        if fault == "T-1 counted":  # against the id the program fills in
            return nll(hidden, row[1:]), nll(
                y, jnp.concatenate([row[2:], jnp.zeros(1, row.dtype)]))
        return nll(hidden, row[1:]), nll(y[:-1], row[2:])

    B, T1 = tokens.shape
    main, ahead = jax.lax.map(row_losses, tokens)
    count = T1 - 1 if fault == "T-1 counted" else T1 - 2
    return main.sum() / (B * (T1 - 1)), ahead.sum() / (B * count)


MODULE_FAULTS = ["Emb(t_i)", "target t_{i+1}", "T-1 counted",
                 "halves swapped", "no RMSNorm_h"]


@pytest.mark.parametrize("fault", [None] + MODULE_FAULTS)
def test_the_module_loss_refuses_each_fault(fault, params, tokens):
    """The program's module loss is the sound module's within 1e-5 and more
    than 1e-4 from each faulty one (the least, one position of 40 counted
    that should not be, reads 4.2e-4; the others 1.8e-3 to 2.0e-2)."""
    _, report = jax.jit(partial(llama.loss_parts, program_cfg()))(
        params["cut"], tokens)
    _, want = jitted.reference(
        lambda p, t: module_losses(FILE, p, t, fault), params["cut"], tokens)
    err = rel(float(report["mtp_loss"]), float(want))
    assert (err < 1e-5) if fault is None else (err > 1e-4), err


@pytest.mark.parametrize("fault,limit", [
    ("lambda 0", 0.05), ("weights not scaled by 1.8", 1e-3),
    ("shared expert dropped", 1e-3)])
def test_the_whole_loss_refuses_each_fault(fault, limit, params, tokens):
    """The sound program's loss is within 1e-5 of the reference
    (test_the_loss_with_the_module_against_the_reference); the reference
    WITH the fault is further than ``limit`` from it."""
    p, file = params["cut"], FILE
    if fault == "lambda 0":
        file = dict(FILE, mtp_loss_weight=0.0)
    if fault == "weights not scaled by 1.8":
        file = dict(FILE, routed_scaling_factor=1.0)
    if fault == "shared expert dropped":
        p = jax.tree.map(lambda a: a, p)
        for tree in (p["layers"]["latent"], p["mtp"]["layers"]["latent"]):
            tree["shared_down"] = jnp.zeros_like(tree["shared_down"])
    got = jitted.loss_fn(program_cfg(), params["cut"], tokens)
    faulty = jitted.reference(partial(ref.loss, file), p, tokens)
    assert rel(float(got), float(faulty)) > limit


def test_the_head_and_the_table_are_shared_not_copied(gradients, params,
                                                      tokens):
    """The module has no head and no table of its own, and the ONE head
    leaf's gradient is the main loss's plus 0.3 times the module's: a copied
    head would leave the main leaf the first term alone."""
    got, _ = gradients
    assert set(params["cut"]["mtp"]) == {"enorm", "hnorm", "eh_proj",
                                         "layers", "final_norm"}
    assert set(params["cut"]["mtp"]["layers"]) == {"latent"}

    def part(which):
        return jitted.reference(jax.grad(
            lambda p, t: ref.losses(FILE, p, t)[which]), params["cut"],
            tokens)

    main, ahead = part(0), part(1)
    for leaf in ("lm_head", "embedding"):
        assert worst(got[leaf], main[leaf] + 0.3 * ahead[leaf]) < 5e-5
        assert worst(got[leaf], main[leaf]) > 1e-2  # once, SUMMED


@pytest.mark.parametrize("chunk", [0, 16, 12])
def test_a_position_left_out_of_the_chunked_mean(chunk):
    """``chunked_nll_mean(live=)`` unchunked, in whole chunks and with a
    rest: the mean over the first ``live`` positions alone."""
    cfg = dataclasses.replace(LlamaConfig.debug(), loss_chunk=chunk,
                              dtype=jnp.float32)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(size=(2, 40, 64)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    t = jnp.asarray(rng.randint(0, 256, (2, 40)), jnp.int32)
    nll = llama._plain_chunk_nll(cfg, head)
    got = jax.jit(lambda x: llama.chunked_nll_mean(cfg, x, t, nll, live=39))(x)
    assert float(got) == pytest.approx(float(nll(x, t)[:, :39].mean()),
                                       rel=1e-6)
    everything = jax.jit(lambda x: llama.chunked_nll_mean(cfg, x, t, nll))(x)
    assert float(everything) == pytest.approx(float(nll(x, t).mean()),
                                              rel=1e-6)
    assert abs(float(got) - float(everything)) > 1e-4


# --- the steps that run it, and the paths that refuse it -------------------- #


def _mesh(spec_str, n):
    from ray_tpu.train.spmd import build_train_mesh

    return build_train_mesh(spec_str, jax.devices()[:n])


def test_spmd_step_one_device_against_data2_and_loss_fn(tokens):
    from ray_tpu.train.spmd import make_spmd_train_step

    cfg = program_cfg()
    runs = []
    for spec_str, n in (("", 1), ("data=2", 2)):
        init, step, sharding, _ = make_spmd_train_step(cfg, _mesh(spec_str, n))
        state = init(jax.random.PRNGKey(0))
        out = []
        for _ in range(2):
            state, loss, scalars = step(state, jax.device_put(tokens,
                                                              sharding))
            out.append((float(loss), {k: float(v)
                                      for k, v in scalars.items()}))
        runs.append(out)
    one, two = runs
    want = float(jitted.loss_fn(
        cfg, jitted.init_params(cfg, jax.random.PRNGKey(0)), tokens))
    assert one[0][0] == pytest.approx(want, rel=1e-5)
    for (l1, s1), (l2, s2) in zip(one, two):
        assert l1 == pytest.approx(l2, rel=2e-5)
        assert set(s1) == {"max_load_ratio", "dropped", "held_share",
                           "held_chunks", "main_loss", "mtp_loss"}
        assert l1 == pytest.approx(s1["main_loss"] + 0.3 * s1["mtp_loss"],
                                   rel=1e-6)
        assert s1["mtp_loss"] == pytest.approx(s2["mtp_loss"], rel=2e-5)
    assert one[1][0] < one[0][0]  # adamw learns


def test_gspmd_step_runs_it(tokens):
    from ray_tpu.parallel.mesh import make_mesh

    cfg = program_cfg()
    init, step, sharding, _ = llama.make_train_step(
        cfg, make_mesh(devices=jax.devices()[:1]))
    _, loss = step(init(jax.random.PRNGKey(0)),
                   jax.device_put(tokens, sharding))
    want = float(jitted.loss_fn(
        cfg, jitted.init_params(cfg, jax.random.PRNGKey(0)), tokens))
    assert float(loss) == pytest.approx(want, rel=1e-5)


def test_the_engine_refuses_the_kinds_by_name():
    with pytest.raises(NotImplementedError) as e:
        llama.LlamaDecodeEngine(program_cfg())
    msg = str(e.value)
    # the blocks are served since PR 52 (tests/test_xing4.py holds an engine
    # of this kind to this file's reference); the module still is not
    assert "prediction module" in msg and "mtp_layers=1" in msg
    assert "more than one token a call" in msg
    assert "L" in llama.SERVED and "G" in llama.SERVED
    assert llama.served_kinds(LlamaConfig.debug()) == "bb"


def test_the_pipeline_step_refuses_the_kinds_by_name():
    from ray_tpu.parallel.mesh import make_mesh

    with pytest.raises(NotImplementedError) as e:
        llama.make_pipeline_train_step(
            program_cfg(), make_mesh(axis_sizes={"pipe": 2}), 2)
    assert "'L' / 'G'" in str(e.value) and "second loss" in str(e.value)


@pytest.mark.parametrize("spec_str,n", [("fsdp=2", 2), ("tensor=2", 2)])
def test_the_spmd_step_refuses_it_on_fsdp_or_tensor(spec_str, n):
    from ray_tpu.train.spmd import make_spmd_train_step

    with pytest.raises(ValueError, match="batch axes only"):
        make_spmd_train_step(program_cfg(), _mesh(spec_str, n))


@pytest.mark.parametrize("over,says", [
    ({"v_head_dim": 0}, "v_head_dim"),
    ({"layer_pattern": "GLE"}, "every built layer is one of the two"),
    ({"mtp_layers": 2}, "one multi-token-prediction module or none"),
    ({"layer_pattern": "GGG"}, "its block is an 'L' layer's"),
    ({"dense_mlp_dim": 0}, "dense_mlp_dim"),
    ({"qk_norm": True}, "no QK-norm"),
])
def test_a_config_that_is_not_the_kind_is_refused(over, says):
    with pytest.raises(ValueError, match=says):
        program_cfg(**over)


def test_loop_reports_the_module_loss_and_sets_the_stack_gauge():
    from ray_tpu.train.session import TrainContext, set_context
    from ray_tpu.train.spmd import spmd_train_loop
    from ray_tpu.util import flight_recorder as fr
    from ray_tpu.util.metrics import registry

    fr.reset_for_tests()
    fr.configure(enabled=True)
    ctx = TrainContext(1, 0, 0, 1, 0)
    set_context(ctx)
    try:
        spmd_train_loop({"llama_config": program_cfg(), "steps": 2,
                         "seq": SEQ, "batch_per_device": 1,
                         "mesh": "data=1"})
        reports = [r.metrics for r in ctx._drain()]
        payload = fr.snapshot_payload()
    finally:
        set_context(None)
        fr.reset_for_tests()
    last = reports[-1]
    assert last["loss"] == pytest.approx(
        last["main_loss"] + 0.3 * last["mtp_loss"], rel=1e-6)
    assert 0.0 < last["moe_held_share"] < 1.0
    assert "moe_mtp_loss" not in last and "moe_lb_loss" not in last
    payload.update(source="test", node_hex="", offset_s=0.0)
    rep = fr.attribute_trace(fr.build_span_events([payload]))
    assert set(rep["router"]) == {
        "moe.max_load_ratio", "moe.dropped", "moe.held_share",
        "moe.held_chunks", "mtp.loss", "mtp.main_loss"}
    assert rep["router"]["mtp.loss"]["last"] == pytest.approx(
        last["mtp_loss"])
    gauge = registry().local_values("ray_tpu_train_stack")
    assert {k[0][1]: v for k, v in gauge.items()} == {
        "block_layers": 0.0, "mamba_layers": 0.0, "moe_layers": 0.0,
        "attn_layers": 0.0, "latent_layers": 2.0,
        "latent_dense_layers": 1.0, "mtp_layers": 1.0, "experts_held": 4.0,
        "router_experts": 16.0}


# --- the configuration file and the cell ------------------------------------ #


@pytest.fixture(scope="module")
def cell():
    from benchmarks.lib import spec

    return spec.cell_bundle(CELL)


def test_the_file_holds_the_published_row_but_the_three_cuts(cell):
    file = cell["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of published rows is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert file["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in reduced:
            assert file["published"][key] == value and file[key] < value
        else:
            assert file[key] == value, key
    assert (file["num_hidden_layers"], file["n_routed_experts"],
            file["vocab_size"]) == (5, 8, 19360)
    entry = next(c for c in cell["bench"]["configs"]
                 if c["name"] == "GLM-4.7-Flash")
    assert set(entry["reduced"]) == reduced
    # what the program reads beside the row follows from the row
    assert file["layer_pattern"] == (
        "G" * row["config"]["first_k_dense_replace"]
        + "L" * (row["config"]["num_hidden_layers"]
                 - row["config"]["first_k_dense_replace"]))
    assert file["head_dim"] == file["qk_nope_head_dim"] \
        + file["qk_rope_head_dim"] == file["v_head_dim"]
    assert ref.kinds_of(file) == ["dense"] + ["routed"] * 4
    for key in ("deployment", "reduced_why", "assumed", "reference",
                "program", "correct"):
        assert file[key], key


def test_the_programs_count_is_the_files_arithmetic(cell):
    from benchmarks.lib import spec

    cfg = spec.program_config(cell["config"])
    assert (cfg.kinds, cfg.head_dim, cfg.mtp_layers) == ("GLLLL", 256, 1)
    assert not cfg.mla_scale_q_lora and not cfg.mla_scale_kv_lora
    mla = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
           + 20 * 256 * 2048 + 768 + 512)
    assert mla == 21_759_232
    dense = mla + 3 * 2048 * 10240 + 2 * 2048
    routed = (mla + 8 * 3 * 2048 * 1536 + 3 * 2048 * 1536 + 2048 * 64 + 64
              + 2 * 2048)
    assert (dense, routed) == (84_677_888, 106_829_120)
    module = routed + 2 * 2048 * 2048 + 3 * 2048
    total = dense + 4 * routed + 2 * 19360 * 2048 + 2048 + module
    assert cfg.num_params() == total == 706_518_848
    assert "706,518,848" in cell["config"]["reduced_why"]
    shapes = jax.eval_shape(lambda: llama.init_params(
        cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes)) == total


def test_what_the_step_may_keep_counts_the_latent_blocks_and_the_module(
        cell):
    from benchmarks.lib import spec
    from ray_tpu.train.spmd import kept_group_bytes, loss_phase_bytes

    cfg = spec.program_config(cell["config"])
    groups = kept_group_bytes(cfg, 2, 8192)
    rows = 2 * 8192
    assert groups["attn"] == 6 * rows * ((4 * 5120 + 2048) * 2 + 4 * 20)
    assert groups["mlp"] == rows * 2 * 10240 * 2
    assert groups["head"] == 2 * rows * 19360 * 2  # two head passes
    plain = dataclasses.replace(cfg, mtp_layers=0)
    assert loss_phase_bytes(cfg, 2, 8192) - loss_phase_bytes(
        plain, 2, 8192) == 3 * rows * 2048 * 2
    # a dense and a Nemotron-shaped config count what they counted
    dense = LlamaConfig.debug()
    assert kept_group_bytes(dataclasses.replace(dense, remat=True), 2, 64) \
        == {"attn": 2 * 128 * ((2 * 64 + 2 * 32 + 64) * 2 + 4 * 4),
            "mlp": 2 * 128 * 2 * 128 * 2}


def test_the_default_of_the_scale_setting_is_longcats():
    """LongCat-Flash-Chat's file (which no PR but a benchmark one may edit)
    maps no ``mla_scale_*`` field, publishes both as true, and builds a
    config whose latent half applies both: the default."""
    from benchmarks.lib import spec

    file = spec.load_config(spec.load_benchmark(), "LongCat-Flash-Chat")
    assert file["mla_scale_q_lora"] is True is file["mla_scale_kv_lora"]
    assert not {"mla_scale_q_lora", "mla_scale_kv_lora"} & set(
        file["program"]["fields"])
    cfg = spec.program_config(file)
    assert cfg.mla_scale_q_lora and cfg.mla_scale_kv_lora
    assert LlamaConfig().mla_scale_q_lora and LlamaConfig().mla_scale_kv_lora


@pytest.mark.deadline(170)
def test_the_cells_rehearsal_runs_end_to_end():
    """``--rehearsal`` of the new cell on the CPU: the harness's check of the
    program against the reference (the module's loss in both), the loop's
    first loss, the loss falling, through ``JaxTrainer``."""
    import rehearse
    from benchmarks.lib import spec

    tiny = spec.cell_bundle(CELL, rehearsal=True)
    cfg = spec.program_config(tiny["config"])
    assert (cfg.kinds, cfg.mtp_layers, cfg.dense_mlp_dim) == ("GL", 1, 128)
    line = rehearse.run_cell(CELL, 4800000048)
    # the loss falls for good once the loop meets its 8 distinct batches a
    # second time; before that a step's loss against the first's is one
    # batch's against another's (7.817 against 7.813 at steps 3 and 7 of this
    # seed), and a loaded machine's window holds two to six steps
    why = [w for w in line["why_not_correct"]
           if line["attempted"] >= 16 or not w.startswith("loss did not fall")]
    assert not why, line
    assert line["correct"] is not bool(line["why_not_correct"])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["detail"]["reference"]["rel_err"] < 1e-3
