"""OLMoE through the program's own block (``models/llama.py`` with experts
and QK-norm switched on by the config) against the plain float32 reference
the benchmark keeps (``benchmarks/reference/olmoe_decoder.py``), at small
widths on the CPU: the routed MLP half and its gradients, the case where
every token picks the same experts, QK-norm attention, the loss with its
router losses APART, the controls a tolerance has to refuse, the trainer's
step on one device against ``fsdp=4`` under both gather schedules, and the
lowered text of every program that runs the block, which must be what it
was."""

import dataclasses
import hashlib
import json
import os
import re

import numpy as np
import pytest

from benchmarks.reference import olmoe_decoder as ref

jax = pytest.importorskip("jax")
jnp = jax.numpy

from jitted import (init_params, loss_fn, reference,  # noqa: E402
                    value_and_grad)
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.ops.moe import routed_mlp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmarks", "configs",
                           "OLMoE-1B-7B-0125-Instruct.json")

# the published shape, small: 2 layers, 4 heads on 4 (MHA), 16 experts top-4
FILE = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 32,
        "vocab_size": 256, "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False, "num_experts": 16,
        "num_experts_per_tok": 4, "norm_topk_prob": False}
SEQ = 32


def program_cfg(file=FILE, **over):
    kw = dict(vocab_size=file["vocab_size"], dim=file["hidden_size"],
              n_layers=file["num_hidden_layers"],
              n_heads=file["num_attention_heads"],
              n_kv_heads=file["num_key_value_heads"],
              mlp_dim=file["intermediate_size"], max_seq_len=64,
              rope_theta=file["rope_theta"], norm_eps=file["rms_norm_eps"],
              num_experts=file["num_experts"],
              experts_per_token=file["num_experts_per_tok"],
              norm_topk_prob=file["norm_topk_prob"], qk_norm=True,
              dtype=jnp.float32)
    kw.update(over)
    return LlamaConfig(**kw)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(
        0, FILE["vocab_size"], (4, SEQ + 1)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    p = init_params(program_cfg(), jax.random.PRNGKey(11))
    # norm scales away from one, so that a misplaced norm shows
    rng = np.random.RandomState(5)
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        leaf = p["layers"][name]
        p["layers"][name] = leaf * jnp.asarray(
            rng.uniform(0.5, 1.5, leaf.shape), jnp.float32)
    return p


def layer0(params):
    return jax.tree.map(lambda a: a[0], params["layers"])


def mlp_inputs(n=2 * SEQ, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (2, n // 2, FILE["hidden_size"]), jnp.float32)


def tolerance():
    with open(CONFIG_FILE) as f:
        return json.load(f)["correct"]["train_loss_rel_tol"]


# --- (a) the routed MLP half against the per-token form ------------------- #


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_routed_mlp_matches_reference_outputs_and_gradients(params,
                                                            norm_topk_prob):
    p = layer0(params)
    file = dict(FILE, norm_topk_prob=norm_topk_prob)
    h = mlp_inputs()
    target = jax.random.normal(jax.random.PRNGKey(1), h.shape)
    names = ("router", "w_gate", "w_up", "w_down")

    def program(h, *w):
        y, stats = routed_mlp(h, *w, top_k=FILE["num_experts_per_tok"],
                              norm_topk_prob=norm_topk_prob)
        return jnp.sum(y * target) + stats["lb_loss"] + stats["z_loss"], \
            (y, stats)

    def plain(h, *w):
        with jax.default_matmul_precision("highest"):
            y, lb, z = ref.experts(file, h.reshape(-1, h.shape[-1]),
                                   dict(zip(names, w)))
        y = y.reshape(h.shape)
        return jnp.sum(y * target) + lb + z, (y, {"lb_loss": lb, "z_loss": z})

    args = (h,) + tuple(p[n] for n in names)
    (_, (y, stats)), grads = value_and_grad(
        program, *args, argnums=range(5), has_aux=True)
    (_, (y_ref, stats_ref)), grads_ref = value_and_grad(
        plain, *args, argnums=range(5), has_aux=True)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-6)
    for k in ("lb_loss", "z_loss"):
        np.testing.assert_allclose(stats[k], stats_ref[k], rtol=1e-5)
    assert float(stats["dropped"]) == 0.0
    for name, g, g_ref in zip(("h",) + names, grads, grads_ref):
        scale = float(jnp.abs(g_ref).max())
        np.testing.assert_allclose(g, g_ref, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=name)


# --- (b) every token picks the same experts -------------------------------- #


def test_total_imbalance_is_still_dropless(params):
    p = dict(layer0(params))
    E, K = FILE["num_experts"], FILE["num_experts_per_tok"]
    # a constant direction in h that the router reads: experts 3, 7, 8, 12
    # win for every token, in that order
    favourites = [3, 7, 8, 12]
    h = mlp_inputs(seed=2) + 4.0
    router = np.array(p["router"]) * 0.01
    for rank, e in enumerate(favourites):
        router[:, e] += 1.0 - 0.1 * rank
    p["router"] = jnp.asarray(router)
    y, stats = jax.jit(lambda h, p: routed_mlp(
        h, p["router"], p["w_gate"], p["w_up"], p["w_down"], top_k=K))(h, p)
    y_ref, lb, _ = reference(lambda h, p: ref.experts(FILE, h, p),
                             h.reshape(-1, h.shape[-1]), p)
    np.testing.assert_allclose(y, y_ref.reshape(h.shape), rtol=1e-5,
                               atol=1e-5)
    assert float(stats["dropped"]) == 0.0
    assert float(stats["max_load_ratio"]) == pytest.approx(E / K)
    np.testing.assert_allclose(stats["lb_loss"], lb, rtol=1e-5)


# --- (c) QK-norm attention -------------------------------------------------- #


def test_qk_norm_attention_matches_reference(params):
    cfg = program_cfg(num_experts=0, experts_per_token=0)
    p = dict(layer0(params))
    d, f = FILE["hidden_size"], FILE["intermediate_size"]
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    p["w_gate"] = jax.random.normal(ks[0], (d, f)) / np.sqrt(d)
    p["w_up"] = jax.random.normal(ks[1], (d, f)) / np.sqrt(d)
    p["w_down"] = jax.random.normal(ks[2], (f, d)) / np.sqrt(f)
    x = mlp_inputs(seed=6)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None].repeat(2, 0)
    layer = jax.jit(lambda x, p: llama._layer(cfg, None, x, p, positions))
    got, stats = layer(x, p)
    assert stats == {}

    def plain(x):  # one sequence: attention, then a dense SwiGLU half
        x1 = x + ref.attention(FILE, x, p)
        h = ref._rms_norm(x1, p["mlp_norm"], FILE["rms_norm_eps"])
        return x1 + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) \
            @ p["w_down"]

    for b in range(2):
        np.testing.assert_allclose(got[b], reference(plain, x[b]), rtol=2e-5,
                                   atol=2e-5)
    # the norm is over the WHOLE projected vector: one per head is another
    # model, and the reference tells them apart
    per_head = dict(p, q_norm=p["q_norm"].at[:16].mul(3.0))
    other, _ = layer(x, per_head)
    assert float(jnp.abs(other - got).max()) > 1e-3


# --- (d) the loss, its parts apart, and the controls ------------------------ #


def program_parts(cfg, params, tokens):
    total, rep = jax.jit(lambda p, t: llama.loss_parts(cfg, p, t))(
        params, tokens)
    lb, z = float(rep["lb_loss"]), float(rep["z_loss"])
    return {"total": float(total), "lb_loss": lb, "z_loss": z,
            "cross_entropy": float(total) - cfg.lb_loss_coef * lb
            - cfg.z_loss_coef * z, "report": rep}


def reference_parts(params, tokens, file=FILE):
    out = jax.jit(lambda p, t: ref.loss_parts(file, p, t))(params, tokens)
    return {k: float(v) for k, v in out.items()}


def test_loss_parts_match_reference_float32(params, tokens):
    got = program_parts(program_cfg(), params, tokens)
    want = reference_parts(params, tokens)
    for k in ("cross_entropy", "lb_loss", "z_loss", "total"):
        assert got[k] == pytest.approx(want[k], rel=2e-5), k
    assert float(got["report"]["dropped"]) == 0.0
    assert got["total"] == pytest.approx(
        float(loss_fn(program_cfg(), params, tokens)), rel=1e-6)
    assert want["total"] == pytest.approx(float(reference(
        lambda p, t: ref.loss(FILE, p, t), params, tokens)), rel=1e-6)
    # the router losses weigh in: left out, the total is another number
    assert abs(want["total"] - want["cross_entropy"]) > 1e-2


def test_loss_bfloat16_inside_the_files_tolerance(params):
    # 1,024 positions: at 128 the mean of so few rounded logits swings by
    # more than the tolerance, which was set at 16,384 (the cell's step)
    tokens = np.random.RandomState(4).randint(
        0, FILE["vocab_size"], (16, 2 * SEQ + 1)).astype(np.int32)
    got = program_parts(program_cfg(dtype=jnp.bfloat16), params, tokens)
    want = reference_parts(params, tokens)
    assert abs(got["total"] - want["total"]) / want["total"] <= tolerance()
    # each part on its own, so that a total near ln(vocab) hides nothing
    assert got["cross_entropy"] == pytest.approx(want["cross_entropy"],
                                                 rel=tolerance())
    assert got["lb_loss"] == pytest.approx(want["lb_loss"], rel=5e-3)
    assert got["z_loss"] == pytest.approx(want["z_loss"], rel=5e-3)


def _skip_one_expert(params):
    """The program's parameters with one expert's product gone, in every
    layer."""
    layers = dict(params["layers"])
    layers["w_down"] = layers["w_down"].at[:, 5].set(0.0)
    return dict(params, layers=layers)


@pytest.mark.parametrize("control", ["eighth_choice_left_out",
                                     "weights_renormalised",
                                     "one_expert_skipped"])
def test_the_tolerance_refuses_another_model(params, tokens, control):
    """What a faster wrong program would compute must not pass for the
    model: each control moves the total by more than the file's
    tolerance (float32, so that nothing else moves it)."""
    cfg, p = program_cfg(), params
    if control == "eighth_choice_left_out":
        cfg = program_cfg(experts_per_token=FILE["num_experts_per_tok"] - 1)
    elif control == "weights_renormalised":
        cfg = program_cfg(norm_topk_prob=True)
    else:
        p = _skip_one_expert(params)
    got = program_parts(cfg, p, tokens)
    want = reference_parts(params, tokens)
    assert abs(got["total"] - want["total"]) / want["total"] > tolerance(), \
        (control, got["total"], want["total"])


# --- (e) the trainer's step ------------------------------------------------- #


def _mesh(spec, n):
    from ray_tpu.train.spmd import build_train_mesh

    return build_train_mesh(spec, jax.devices()[:n])


def _three_steps(cfg, spec, n, gather, tokens, optimizer=None):
    """``(parameters at the start, after the first step, [(loss, router
    scalars)] of three steps)``."""
    from ray_tpu.train.spmd import make_spmd_train_step

    init, step, sharding, _ = make_spmd_train_step(
        cfg, _mesh(spec, n), optimizer=optimizer, donate=False, gather=gather)
    state = init(jax.random.PRNGKey(0))
    params, out = [jax.device_get(state["params"])], []
    for _ in range(3):
        state, loss, router = step(state, jax.device_put(tokens, sharding))
        params.append(jax.device_get(state["params"]))
        out.append((float(loss), {k: float(v) for k, v in router.items()}))
    return params[0], params[1], out


@pytest.mark.parametrize("gather", ["streamed", "upfront"])
def test_spmd_step_one_device_against_fsdp4(tokens, gather):
    import optax

    cfg = program_cfg(remat=True)
    sgd = optax.sgd(1.0)  # new = old - gradient: the step shows its gradient
    runs = {"one device": _three_steps(cfg, "", 1, gather, tokens, sgd),
            "fsdp=4": _three_steps(cfg, "fsdp=4", 4, gather, tokens, sgd)}
    one, four = runs["one device"][2], runs["fsdp=4"][2]
    for (l1, r1), (l4, r4) in zip(one, four):
        assert l4 == pytest.approx(l1, rel=2e-5)
        for k in ("lb_loss", "z_loss", "max_load_ratio", "dropped"):
            assert r4[k] == pytest.approx(r1[k], rel=2e-5, abs=1e-7), k
    assert one[0][1]["dropped"] == 0.0 and one[0][1]["lb_loss"] > 1.0
    # the first step's gradient is jax.grad of loss_fn on the same batch
    p0 = runs["one device"][0]
    want = jax.grad(lambda p: llama.loss_fn(cfg, p, tokens))(p0)
    for name, (before, after, _) in runs.items():
        got = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                           before, after)
        for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                jax.tree.leaves(want)):
            scale = float(np.abs(w).max()) + 1e-12
            np.testing.assert_allclose(
                g, w, rtol=1e-3, atol=2e-5 * scale,
                err_msg=f"{name} {jax.tree_util.keystr(path)}")


def test_spmd_step_learns_with_adamw(tokens):
    _, _, out = _three_steps(program_cfg(), "data=2,fsdp=2", 4, "streamed",
                             tokens)
    assert out[-1][0] < out[0][0], out


def test_tensor_axis_is_refused():
    from ray_tpu.train.spmd import make_spmd_train_step

    with pytest.raises(ValueError, match="tensor"):
        make_spmd_train_step(program_cfg(), _mesh("fsdp=2,tensor=2", 4))


def test_paths_with_their_own_block_refuse_the_config():
    with pytest.raises(NotImplementedError,
                       match="no test compares its logits with the "
                             "reference") as refused:
        llama.LlamaDecodeEngine(program_cfg())
    assert "dense block" not in str(refused.value)


def test_loop_reports_the_router_and_records_its_gauges():
    from ray_tpu.train.session import TrainContext, set_context
    from ray_tpu.train.spmd import spmd_train_loop
    from ray_tpu.util import flight_recorder as fr

    fr.reset_for_tests()  # this test reads its own loop's records alone
    fr.configure(enabled=True)
    ctx = TrainContext(1, 0, 0, 1, 0)
    set_context(ctx)
    try:
        spmd_train_loop({"llama_config": program_cfg(), "steps": 4,
                         "batch_per_device": 2, "seq": SEQ, "mesh": "data=1",
                         "report_every": 1, "lr": 0.01,
                         "distinct_batches": 1})
        reports = [r.metrics for r in ctx._drain()]
    finally:
        set_context(None)
    assert len(reports) == 4
    for r in reports:
        assert r["moe_dropped"] == 0.0
        assert r["moe_lb_loss"] > 1.0 and r["moe_z_loss"] > 0.0
        assert 1.0 <= r["moe_max_load_ratio"] <= 16 / 4
    assert reports[-1]["loss"] < reports[0]["loss"]
    payload = fr.snapshot_payload()
    payload.update(source="test", node_hex="", offset_s=0.0)
    rep = fr.attribute_trace(fr.build_span_events([payload]))
    assert set(rep["router"]) == {"moe.lb_loss", "moe.z_loss",
                                  "moe.max_load_ratio", "moe.dropped"}
    assert rep["router"]["moe.dropped"]["max"] == 0.0
    assert rep["router"]["moe.lb_loss"]["last"] == pytest.approx(
        reports[-1]["moe_lb_loss"])
    assert "moe.max_load_ratio" in fr.format_attribution(rep)
    # every instant carries its step beside its value: one a name a step
    names = {int(sid): d for sid, d in payload["names"].items()}
    tagged = [(names[int(sid)], tags[1])
              for _, sid, kind, _, _, tags in payload["events"] if kind == 1]
    assert all(d["tag_keys"] == ["value", "step"] for d, _ in tagged)
    assert [step for _, step in tagged] == [
        step for step in (1, 2, 3, 4) for _ in range(4)]
    assert {d["name"] for d, _ in tagged[-4:]} == set(rep["router"])
    # and the slowest step comes with the router's scalars of THAT step
    slow = rep["slowest_step"]
    assert set(slow["router"]) == set(rep["router"])
    assert slow["router"]["moe.lb_loss"] == pytest.approx(
        reports[slow["step"] - 1]["moe_lb_loss"])


# --- (f) the programs are what they were ------------------------------------ #

# sha256 of LlamaConfig.debug()'s parameters (PRNGKey(7)), taken on the commit
# before the routed half went into the block (bea6b96).
DENSE_PARAMS = "9f1ff577332224ef3f7ea22a3b79964c1124efa2069a1b139b8b8193af1eed1f"


def test_dense_parameter_tree_is_what_it_was():
    cfg = LlamaConfig.debug()
    p = llama.init_params(cfg, jax.random.PRNGKey(7))
    assert sorted(p["layers"]) == sorted(
        ["wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "attn_norm",
         "mlp_norm"])
    assert sum(x.size for x in jax.tree.leaves(p)) == cfg.num_params()
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == DENSE_PARAMS
    routed = program_cfg()
    assert sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: llama.init_params(
            routed, jax.random.PRNGKey(0))))) == routed.num_params()
    published = dataclasses.replace(
        routed, vocab_size=50304, dim=2048, n_layers=1, n_heads=16,
        n_kv_heads=16, mlp_dim=1024, num_experts=64, experts_per_token=8)
    assert published.num_params() == 625_616_896


def _step_text(init, step):
    state = jax.eval_shape(init._fn, jax.random.PRNGKey(0))
    return step._fn.lower(
        state, jax.ShapeDtypeStruct((4, 33), jnp.int32)).as_text()


def _spmd_text(cfg, spec, n, gather="streamed"):
    from ray_tpu.train.spmd import make_spmd_train_step

    return _step_text(*make_spmd_train_step(cfg, _mesh(spec, n),
                                            gather=gather)[:2])


def _pipeline_text(**axes):
    from ray_tpu.parallel.mesh import make_mesh

    return _step_text(*llama.make_pipeline_train_step(
        LlamaConfig.debug(), make_mesh(axis_sizes=axes), 2)[:2])


def _patterned_text():
    """The SPMD step of ``tests/nemotron_h_small.py``'s stack (``MEMEM*EME``:
    the Mamba-2 mixer of ``ops/ssm.py`` in four layers)."""
    from nemotron_h_small import program_cfg as patterned_cfg

    return _spmd_text(patterned_cfg(), "", 1)


def _serving_text(program, n):
    """``program`` of the decode engine at ``n`` pages, lowered as the
    engine jits it."""
    from functools import partial

    cfg, ps = LlamaConfig.debug(), 8
    params = jax.eval_shape(
        lambda key: llama.serving_params(cfg, llama.init_params(cfg, key)),
        jax.random.PRNGKey(0))
    store = jax.ShapeDtypeStruct(
        (cfg.n_layers, 16, ps, cfg.n_kv_heads, cfg.head_dim), jnp.float32)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = {llama.prefill_with_cache: (i32(1, n * ps), i32(n), i32()),
            llama.decode_step_with_cache: (i32(1), i32(), i32(n))}[program]
    return jax.jit(partial(program, cfg), donate_argnums=(1, 2)).lower(
        params, store, store, *args).as_text()


def _gspmd_text():
    from ray_tpu.parallel.mesh import make_mesh

    return _step_text(*llama.make_train_step(
        LlamaConfig.debug(), make_mesh(devices=jax.devices()[:1]))[:2])


# sha256 of the lowered text of every program that runs the decoder block.
# The first three: the dense trainer's step, taken on bea6b96 (before the
# routed half went into the block). The rest were taken on 7bda524, the
# commit before the five copies of the block became ``decoder_block``: the
# routed step, the serving programs (page size 8, float32 stores of 16
# pages, the ``serving_params`` tree), the GSPMD and pipeline steps, and the
# two steps whose ``col_in`` / ``row_out`` are collectives. A PR that means
# to change a program replaces its hash; one that does not has changed it
# by accident. PR 29 meant to change the three STREAMED steps with a live
# ``fsdp`` axis and replaced their hashes (``fsdp=4`` streamed, the routed
# ``fsdp=4`` step, ``fsdp=2 tensor=2``): the carried layer's gather is
# under ``stop_gradient``. With that call made the identity they lower to
# the texts they had (``tests/test_spmd_train.py``, which keeps the old
# ``fsdp=4`` hash); the other nine keep theirs. PR 32 gave the block's
# products names (``checkpoint_name``: ``models.llama.KEEP_GROUPS``) and
# all twelve keep their hashes, taken WITH THE NAMES OFF: a name lowers to
# no operation, but while it is lowered each distinct (shape, name) takes a
# number from the module's counter for private functions' names, so the
# functions lowered after it are numbered on (``@_where_70`` is
# ``@_where_71``, ``@silu_204`` ``@silu_208``) and the text's hash moves
# though no operation does. The test below holds a program to its hash
# with the names off, and with them on to the same text but for those
# numbers. PR 41 meant to change the two ROUTED steps and replaced their
# hashes (and the routed one of the three below): ``ops/moe.py``'s row maps
# (``_sum_rows``; the weights' cotangent made where the rows lie); the ten
# programs that run no ``routed_mlp`` keep theirs. The patterned step (the
# ``M`` / ``E`` / ``*`` halves at ``tests/nemotron_h_small.py``'s sizes:
# ``train-nemotron3nano-1chip``'s program, small) was taken on 7e19dcb, the
# commit before PR 58 gave ``ops/ssm.py``'s scan a start state, ``last`` and
# a returned state for the decode engine: the trainer's program is the one
# it was.
PROGRAMS = {
    "dense spmd, one device": (
        "90f52bb7182988719693b307dea1b7af700e18813e501c295d3f917e99fe4f41",
        lambda: _spmd_text(LlamaConfig.debug(), "", 1)),
    "dense spmd, fsdp=4 streamed": (
        "73715f27e998f7bee29d2090c745365dd66b73368e7a6d092a8bf83fddef6aa6",
        lambda: _spmd_text(LlamaConfig.debug(), "fsdp=4", 4)),
    "dense spmd, fsdp=4 upfront": (
        "3383c9e196be230a9964da69289bc26aae093a4df436c9cfbae7037c1882d336",
        lambda: _spmd_text(LlamaConfig.debug(), "fsdp=4", 4, "upfront")),
    "routed spmd, one device": (
        "d166dd80654a151161aa5d6a26814ea2b769b795a94fc31acd9ef2d7ef7be138",
        lambda: _spmd_text(program_cfg(), "", 1)),
    "routed spmd, fsdp=4 streamed": (
        "7b4f54dcac5110c848eb3b17b28ada71a4d3cb98cbdd9888cf7546d9a0341b5d",
        lambda: _spmd_text(program_cfg(), "fsdp=4", 4)),
    "dense spmd, fsdp=2 tensor=2": (
        "344206d8f12065599cf4b0aa7c27095d427915891c1ddf9272e8e4eb170a7fa9",
        lambda: _spmd_text(LlamaConfig.debug(), "fsdp=2,tensor=2", 4)),
    "prefill, 1 page": (
        "7a6a8e472df32aac1668379c02df7a0b9cb645c7df262a647bd42f0788f6845f",
        lambda: _serving_text(llama.prefill_with_cache, 1)),
    "prefill, 2 pages": (
        "c91a3edb75f0fc7b96bb84d087caa02c7a8a631584a8169946e53c2ed5754dc1",
        lambda: _serving_text(llama.prefill_with_cache, 2)),
    "decode, 2 pages": (
        "80aafab84a7adbcfd024c81f93ee91a11faf64b023ebb12b6c1db6a18e8702f6",
        lambda: _serving_text(llama.decode_step_with_cache, 2)),
    "gspmd, one device": (
        "7754f0780379b5b1865945af2396c496b788f0a15139a3e003d229cb0ca194de",
        _gspmd_text),
    "pipeline, pipe=2": (
        "14adea293974ad064dfb395d399b9638952654f126b9bfeade82a89eea856de2",
        lambda: _pipeline_text(pipe=2)),
    "pipeline, pipe=2 tensor=2": (
        "44b3a4ca71797b37d385ba93e44dcbae037328c2518c14d023942ea78641f8ef",
        lambda: _pipeline_text(pipe=2, tensor=2)),
    "patterned M spmd, one device": (
        "5b126790328a47f71e0d8102125958029a40efa047c5d98057d02ccfab4b039b",
        lambda: _patterned_text()),
}


def turn_names_off(monkeypatch):
    """``checkpoint_name`` made the identity where the model calls it."""
    from ray_tpu.ops import flash_attention

    for module in (llama, flash_attention):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)


@pytest.fixture
def names_off(monkeypatch):
    turn_names_off(monkeypatch)
    return monkeypatch


def _unnumbered(text):
    """``text`` with the counter's numbers off its private functions."""
    return re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1", text)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_program_lowers_to_the_same_text(program, names_off):
    sha, lower = PROGRAMS[program]
    unnamed = lower()
    assert hashlib.sha256(unnamed.encode()).hexdigest() == sha
    names_off.undo()  # as the program runs: the names lower to nothing
    assert _unnumbered(lower()) == _unnumbered(unnamed)


# the three streamed steps as bdeba28 lowered them, before PR 29 (the routed
# one with PR 41's row maps, which both sides of that comparison now run)
BEFORE_THE_CARRIED_GATHER_LEFT_THE_BACKWARD = {
    "dense spmd, fsdp=4 streamed":
        "999a6a502c954414038f8f83723808c52872d6a452a507db4bdbe24232dee8db",
    "routed spmd, fsdp=4 streamed":
        "7824e9a08c2bee0064826cdcbad6dd8b6731c65a7352f1b21b8b7752aa716d5f",
    "dense spmd, fsdp=2 tensor=2":
        "fe19fe4132d3b6f18554e68fa84f50481a9b1ca17d1e57bc5eefdf1a667bf3fb",
}


@pytest.mark.parametrize("program",
                         list(BEFORE_THE_CARRIED_GATHER_LEFT_THE_BACKWARD))
def test_the_stop_gradient_is_all_that_changed_the_streamed_steps(
        program, monkeypatch, names_off):
    """With ``prefetch_layer``'s ``stop_gradient`` made the identity (the
    one call that is given a dict: a layer's leaves) the step lowers to
    bdeba28's text: ``tests/test_spmd_train.py`` runs that program against
    this one, bit for bit."""
    real = jax.lax.stop_gradient
    monkeypatch.setattr(
        jax.lax, "stop_gradient",
        lambda x: x if isinstance(x, dict) else real(x))
    text = PROGRAMS[program][1]()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == BEFORE_THE_CARRIED_GATHER_LEFT_THE_BACKWARD[program]


# --- (g) the patterned families' serving programs are what they were -------- #


def _family_text(family, program, n, n_pages, page, **file_keys):
    """``program`` (``"prefill"`` / ``"decode"``) at ``n`` pages of the
    engine ``tests/test_<family>.py`` builds at its tiny config (seeded
    weights), lowered as the engine jits it."""
    import importlib

    cfg = importlib.import_module(f"test_{family}").program_cfg(**file_keys)
    engine = llama.LlamaDecodeEngine(cfg, n_pages=n_pages, page_size=page)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    slots = min(n, engine.window_pages)
    if program == "prefill":
        args = (i32(1, n * page), i32(n), i32()) + (
            (i32(slots),) if engine.n_slots else ())
        fn = engine._prefill_fn
    else:
        args = (i32(1), i32(), i32(n)) + (
            (i32(slots), i32()) if engine.n_slots else ())
        fn = engine._decode_fn
    return fn._fn.lower(engine.params, *engine.stores, *args).as_text()


# sha256 of the lowered text (names off) of the patterned families' prefill
# and decode, taken on 37a7761, the commit before the four serving layer
# loops became ONE walker over a table of served kinds (PR 44): all-"S"
# (LongCat; scanned even at two layers), "F" with "W" at 6 layers (less than
# two periods: in line) and at 10 (two periods scanned, a rest of two in
# line; 5 pages, so the window rows kept are the last 4 pages'), all-"I"
# (Keye: a decode call whose pages hold no more than ``index_topk`` = 8
# positions attends them all, one that holds more picks and gathers rows).
# As ``PROGRAMS``: a PR that means to change a program replaces its hash.
# PR 61 (on b8ab1b2; the walker scans a RUN of one kind wherever it walked in
# line, ``llama._segments``) replaced six on purpose and touched no other:
# "FW by runs" (was "FW in line": ``FWWWFW`` is now F, ONE body of W scanned
# three times, FW), "HN by runs" (was "HN in line": H x 5, N, H x 6) and "HN
# scanned" (its rest ``HH`` is a run of two). "FW scanned"'s rest ``FW`` has
# no run, and a scanned period's BODY stays in line: those texts stood.
# "GL run" (added by PR 61) holds the serving stream as a tuple of rows.
SERVED_PROGRAMS = {
    "S prefill, 2 pages": (
        "47c781408cf89d9ba82926eb02c646b4310eb462249b5181722237379b42881e",
        lambda: _family_text("longcat_flash", "prefill", 2, 12, 8)),
    "S decode, 2 pages": (
        "84c78555f2a06494ce220c31c1161bbbf02602f78cdda188c3ec85b814a4a022",
        lambda: _family_text("longcat_flash", "decode", 2, 12, 8)),
    "FW by runs, prefill, 5 pages": (
        "669f6816989e28b9429191c61c4b0d0e980b0da41d774aac9f9fe15581d91118",
        lambda: _family_text("smallthinker", "prefill", 5, 24, 5)),
    "FW by runs, decode, 5 pages": (
        "c3d35299b69ba5a9d4b27f2d4d7e96069a02c7a9ef19339ad04fe93c5aa2970d",
        lambda: _family_text("smallthinker", "decode", 5, 24, 5)),
    "FW scanned, prefill, 5 pages": (
        "3a07dcddccc72f9c56274e6274cbcd3f38dd323ee81eba9bff7977e4c2a887ed",
        lambda: _family_text("smallthinker", "prefill", 5, 24, 5,
                             num_hidden_layers=10)),
    "FW scanned, decode, 5 pages": (
        "69570fbbadfe0478869d107eeff4537ccb3f3effc04700e156f3dfa397e3dd11",
        lambda: _family_text("smallthinker", "decode", 5, 24, 5,
                             num_hidden_layers=10)),
    "I prefill, 3 pages": (
        "041f739d4bb2810f0b92dae5efea191fcbf644a9b649d23897b1aaf94c8d4a88",
        lambda: _family_text("keye_vl2", "prefill", 3, 24, 4)),
    "I decode under topk, 2 pages": (
        "f72ebbf11a1570926648e15a1a16331202b917df8048cba890203db59f8ac66c",
        lambda: _family_text("keye_vl2", "decode", 2, 24, 4)),
    "I decode past topk, 3 pages": (
        "c7e009f784b970ab5b7b622f460a7f01bacdea6c8f33df65a565f5482c53371f",
        lambda: _family_text("keye_vl2", "decode", 3, 24, 4)),
    # the state-space hybrid ("H" with "N"; tests/test_granite_hybrid.py),
    # brought by PR 58, these four taken on PR 61: a period of TEN layers,
    # walked by runs at 12 layers and scanned at 22 (two periods, and the
    # rest of two a run)
    "HN by runs, prefill, 2 pages": (
        "749a49dad56d81d1e2a5e25dc76ea5e8c3700f29e11ba4b30dd39d489fda6523",
        lambda: _family_text("granite_hybrid", "prefill", 2, 12, 8)),
    "HN by runs, decode, 2 pages": (
        "2e68ccd952d4c93b94ab4c75959498a9ed332b85a9696470a0b6509d7b6df6c7",
        lambda: _family_text("granite_hybrid", "decode", 2, 12, 8)),
    "HN scanned, prefill, 2 pages": (
        "0892db420630ca891050ab5f94349feefa767c686758c4b3823caa5edc6e2c98",
        lambda: _family_text("granite_hybrid", "prefill", 2, 12, 8,
                             num_hidden_layers=22)),
    "HN scanned, decode, 2 pages": (
        "71cb572a47ed216a9cc5cd71d38fbfaa754c6fe887b60f26c9918f124fc45f4f",
        lambda: _family_text("granite_hybrid", "decode", 2, 12, 8,
                             num_hidden_layers=22)),
    # the latent blocks under a stream of four rows ("G" with "L";
    # tests/test_xing4.py), taken on PR 61: ``GLL`` is G in line and ONE body
    # of L scanned twice, the stream a tuple of four rows from the embedding
    # to the head
    "GL run, prefill, 2 pages": (
        "006ad30e596060c7366c67817181e7ac2bf871b86be57c12aa43865b778d497b",
        lambda: _family_text("xing4", "prefill", 2, 12, 8)),
    "GL run, decode, 2 pages": (
        "12b2600029c256015422185270a803ff11360ffbd5384ca81342ed2a0436498f",
        lambda: _family_text("xing4", "decode", 2, 12, 8)),
}


@pytest.mark.parametrize("program", list(SERVED_PROGRAMS))
def test_served_program_lowers_to_the_same_text(program, names_off):
    sha, lower = SERVED_PROGRAMS[program]
    assert hashlib.sha256(lower().encode()).hexdigest() == sha


# --- (h) the table of served kinds is what the engine is built from -------- #


def _gauge(name):
    from ray_tpu.util.metrics import registry

    return {k[0][1]: v for k, v in registry().local_values(name).items()}


def no_page_bytes(cfg):
    """``ray_tpu_serve_engine_page_bytes`` of an engine that keeps nothing:
    zero for every tag of ``SERVED`` whose store keeps a row a POSITION.
    Another model's test puts its own values over it."""
    return {tag: 0.0 for kind in llama.SERVED.values()
            for tag, _, _, table in kind.rows(cfg)
            if llama.TABLES[table].rows is None}


@pytest.mark.parametrize("family,n_pages,page,held,traced", [
    ("dense", 16, 4, None, 1),         # no routed kind: the share is not set
    ("longcat_flash", 12, 8, 0.0, 1),  # from the program's shares
    ("smallthinker", 24, 5, 1.0, 4),   # every expert here; F, W x 3, FW
    ("keye_vl2", 24, 4, 0.0, 1),
    ("glm_moe_dsa", 24, 8, 0.0, 3)])   # X, ONE body of Z scanned, Y
def test_the_engine_is_what_the_table_folds(family, n_pages, page, held,
                                            traced):
    """Stores, slots and the ``page_bytes`` tags are ``served_stores``'
    (``SERVED`` folded over the stack), for every served family at its
    tests' tiny config; the assignment shares are set for a routed stack
    and for no other; ``traced_layers`` is the bodies the walker's segments
    hold, whatever the depth."""
    import importlib
    import math

    from ray_tpu.util.metrics import registry

    cfg = (LlamaConfig.debug() if family == "dense" else
           importlib.import_module(f"test_{family}").program_cfg())
    engine = llama.LlamaDecodeEngine(cfg, n_pages=n_pages, page_size=page)
    assert registry().local_values(
        "ray_tpu_serve_engine_traced_layers")[()] == traced
    layout = llama.served_stores(cfg)
    assert llama.page_rows(cfg)[1] == [(s.layers, s.row) for s in layout]
    assert {llama.SERVED[s.kind].family for s in layout} \
        == {llama.page_rows(cfg)[0]}
    assert bool(engine.n_slots) == any(s.table == "slot" for s in layout)
    assert [s.shape for s in engine.stores] == [
        (s.layers, engine.n_slots if s.table == "slot" else n_pages, page,
         *s.row) for s in layout]
    want = no_page_bytes(cfg)
    for s in layout:
        want[s.tag] += 4.0 * s.layers * math.prod(s.row)
    assert _gauge("ray_tpu_serve_engine_page_bytes") == want
    # the literal set of tags, in THIS place alone: a new kind adds its own
    assert set(want) == {"kv", "latent", "full", "window", "index", "gated",
                         "latent_block", "parallel_full", "parallel_window",
                         "hybrid", "dsa_latent", "dsa_index",
                         "memory_window", "memory_full"}
    # these families keep nothing a SEQUENCE (tests/test_qwen3_next.py)
    assert set(_gauge("ray_tpu_serve_engine_state_bytes").values()) == {0.0}
    for part in ("held", "zero", "elsewhere"):
        llama._g_moe_assignment_share.set(-1.0, tags={"part": part})
    pages = engine.pool.alloc(2)
    engine.prefill(list(range(page + 1)), pages)
    engine.copy_page(pages[1], pages[0])  # a call a table, where it has rows
    shares = _gauge("ray_tpu_serve_moe_assignment_share")
    if held is None:
        assert set(shares.values()) == {-1.0}
    else:
        assert held <= shares["held"] <= 1.0
        assert sum(shares.values()) == pytest.approx(1.0)


@pytest.mark.parametrize("keys,lacks", [
    (dict(layer_pattern="M*", n_layers=2, ssm_heads=2, ssm_head_dim=8,
          ssm_state=8), r"lacks \* M; for"),
    (dict(layer_pattern="E*", n_layers=2, num_experts=4,
          experts_per_token=2), r"lacks \* E; for"),
    ("a mix", r"has F S W, of which the table lacks none; for")])
def test_the_refusal_names_the_kinds_the_table_lacks(keys, lacks):
    if keys == "a mix":  # one family's kinds with another's
        import test_smallthinker

        cfg = dataclasses.replace(
            test_smallthinker.program_cfg(), layer_pattern="FWSFWS",
            mlp_act="swiglu", q_lora_rank=8, kv_lora_rank=8, v_head_dim=8,
            qk_nope_head_dim=8, qk_rope_head_dim=8, dense_mlp_dim=32)
    else:
        cfg = dataclasses.replace(LlamaConfig.debug(), **keys)
    with pytest.raises(NotImplementedError, match=lacks):
        llama.LlamaDecodeEngine(cfg)
