"""A patterned stack (``LlamaConfig.layer_pattern``: Mamba-2, routed with a
shared expert, attention without rotation; each layer one half of the
block) against the plain float32 reference the benchmark keeps
(``benchmarks/reference/nemotron_h_decoder.py``), at small widths on the
CPU: each layer kind, the loss and every gradient leaf, the chunked scan
against the recurrence, the router's bias / renormalisation / scale, the
held range (the shares add up to the uncut layer), the trainer's step on
one device against ``data=2``, the refusals, and the benchmark's files."""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import nemotron_h_decoder as ref

jax = pytest.importorskip("jax")
jnp = jax.numpy

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.ops import ssm  # noqa: E402
from ray_tpu.ops.moe import routed_mlp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmarks", "configs",
                           "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16.json")
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# the published shape, small: the pattern's first 9 layers, 8 Mamba heads of
# 16 in 2 groups, state 16, chunk 16; a router over 32 of which this share
# holds 4 (from the 8th on), top-3; 4 query heads on 2, head width 32 (not
# hidden / heads = 16)
FILE = {
    "hidden_size": 64, "num_hidden_layers": 9,
    "hybrid_override_pattern": PUBLISHED_PATTERN,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "n_routed_experts": 4, "router_experts": 32, "first_expert": 8,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "mlp_hidden_act": "relu2",
    "router_scoring": "sigmoid", "rotary": False, "rope_theta": 10000,
    "vocab_size": 256, "max_position_embeddings": 128,
    "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
}
SEQ = 40  # two chunks of 16 and a rest of 8


def program_cfg(file=FILE, **over):
    with open(CONFIG_FILE) as f:
        fields = json.load(f)["program"]["fields"]
    kw = {field: file[key] for field, key in fields.items()}
    kw.update({"dtype": jnp.float32, **over})
    return LlamaConfig(**kw)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(
        0, FILE["vocab_size"], (2, SEQ + 1)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    p = llama.init_params(program_cfg(), jax.random.PRNGKey(11))
    # scales, biases and skips away from their starting values, so that a
    # misplaced or forgotten one shows
    rng = np.random.RandomState(5)

    def jiggle(kind, name, lo, hi):
        leaf = p["layers"][kind][name]
        p["layers"][kind][name] = leaf + jnp.asarray(
            rng.uniform(lo, hi, leaf.shape), jnp.float32)

    for kind in ("mamba", "moe", "attn"):
        jiggle(kind, "norm", -0.5, 0.5)
    jiggle("mamba", "gate_norm", -0.5, 0.5)
    jiggle("mamba", "conv_b", -0.3, 0.3)
    jiggle("mamba", "D", -0.5, 0.5)
    jiggle("moe", "router_bias", -0.2, 0.2)
    return p


def layer(params, kind, row=0):
    return jax.tree.map(lambda a: a[row], params["layers"][kind])


def normed_inputs(seed=0, batch=2, seq=SEQ):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (batch, seq, FILE["hidden_size"]), jnp.float32)


def per_row(fn, h):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([fn(row) for row in h])


def assert_trees_close(got, want, rtol, atol):
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_w = jax.tree.leaves(want)
    assert len(flat_g) == len(flat_w)
    for (path, g), w in zip(flat_g, flat_w):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=atol * max(scale, 1e-3),
            err_msg=jax.tree_util.keystr(path))


# --- (a) each kind of layer against the reference --------------------------- #


def mixer(cfg, h, p):
    return ssm.mamba2_mixer(
        h, p, heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
        groups=cfg.ssm_groups, state=cfg.ssm_state, chunk=cfg.ssm_chunk,
        eps=cfg.norm_eps)


@pytest.mark.parametrize("seq", [SEQ, 48, 11],
                         ids=["two-chunks-and-a-rest", "three-chunks",
                              "shorter-than-a-chunk"])
def test_mamba_mixer_chunked_against_the_recurrence(params, seq):
    cfg, p = program_cfg(), layer(params, "mamba", 1)
    h = normed_inputs(1, seq=seq)
    target = jax.random.normal(jax.random.PRNGKey(2), h.shape)
    got, g_got = jax.value_and_grad(
        lambda p: jnp.sum(mixer(cfg, h, p) * target))(p)
    want, g_want = jax.value_and_grad(lambda p: jnp.sum(per_row(
        lambda row: ref.mamba(FILE, row, p), h) * target))(p)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    np.testing.assert_allclose(
        mixer(cfg, h, p), per_row(lambda row: ref.mamba(FILE, row, p), h),
        rtol=1e-4, atol=1e-5)
    assert_trees_close(g_got, g_want, rtol=1e-3, atol=1e-5)


def test_ssd_scan_is_the_recurrence_whatever_the_chunk():
    """The same inputs through chunks of 16, of 5 (40 = 8 x 5) and of 64
    (one chunk, shorter than it): one recurrence, so one answer."""
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (2, SEQ, 8, 16))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, SEQ, 8)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (8,), minval=0.0, maxval=2.5))
    b_in = jax.random.normal(k[3], (2, SEQ, 2, 16))
    c_in = jax.random.normal(k[4], (2, SEQ, 2, 16))
    by_chunk = [ssm.ssd_scan(x, dt, a, b_in, c_in, q) for q in (16, 5, 64)]

    def step(state, now):  # [B, H, P, N], heads 0-3 read group 0
        x_t, dt_t, b_t, c_t = now
        b_t, c_t = (jnp.repeat(v, 4, axis=1) for v in (b_t, c_t))
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, want = jax.lax.scan(step, jnp.zeros((2, 8, 16, 16)), jax.tree.map(
        lambda v: jnp.moveaxis(v, 1, 0), (x, dt, b_in, c_in)))
    for got in by_chunk:
        np.testing.assert_allclose(got, jnp.moveaxis(want, 0, 1), rtol=1e-4,
                                   atol=1e-5)


def test_attention_layer_without_rotation_matches_reference(params):
    cfg, p = program_cfg(), layer(params, "attn")
    x = normed_inputs(4)
    got, _ = llama.pattern_layer(cfg, "*", llama.flash_causal, x, p)
    want = x + per_row(lambda row: ref.attention(
        FILE, ref._rms_norm(row, p["norm"], 1e-5), p), x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # rotation is the config's: the same weights WITH it give another result
    rot, _ = llama.pattern_layer(dataclasses.replace(cfg, rope=True), "*",
                                 llama.flash_causal, x, p)
    assert float(jnp.abs(rot - got).max()) > 1e-2


def routed(cfg, h, p, held="cfg", shared=True, **over):
    if held == "cfg":
        held = (cfg.first_expert, cfg.num_experts)
    kw = dict(top_k=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
              scoring=cfg.router_scoring, choice_bias=p["router_bias"],
              scale=cfg.routed_scale, held=held,
              shared=(p["shared_up"], p["shared_down"]) if shared else None)
    kw.update(over)
    return routed_mlp(h, p["router"], None, p["w_up"], p["w_down"], **kw)


def test_routed_layer_matches_reference_outputs_and_gradients(params):
    cfg, p = program_cfg(), layer(params, "moe", 2)
    h = normed_inputs(6)
    target = jax.random.normal(jax.random.PRNGKey(7), h.shape)
    (got, stats), g_got = jax.value_and_grad(
        lambda p, h: (lambda y, s: (jnp.sum(y * target), s))(
            *routed(cfg, h, p)), argnums=(0, 1), has_aux=True)(p, h)
    want, g_want = jax.value_and_grad(lambda p, h: jnp.sum(per_row(
        lambda row: ref.moe(FILE, row, p), h) * target), argnums=(0, 1))(p, h)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert_trees_close(g_got, g_want, rtol=1e-3, atol=1e-5)
    assert float(jnp.abs(g_got[0]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g_got[0]["router"]).max()) > 0.0
    # 4 of 32 held: the share of the 2 x 40 x 3 assignments that fell here
    with jax.default_matmul_precision("highest"):
        weight = ref.route(FILE, h.reshape(-1, 64), p)
    here = float((weight[:, 8:12] > 0).sum()) / float((weight > 0).sum())
    assert float(stats["held_share"]) == pytest.approx(here)
    assert 0.02 < here < 0.4
    assert float(stats["dropped"]) == 0.0
    assert set(stats) == {"max_load_ratio", "dropped", "held_share"}


# --- (b) the router: bias, renormalisation, scale --------------------------- #


def test_bias_changes_the_choice_and_never_the_weights(params):
    cfg, p = program_cfg(), layer(params, "moe", 0)
    hf = normed_inputs(8).reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(hf @ p["router"])
        plain = ref.route(FILE, hf, dict(p, router_bias=jnp.zeros(32)))
        biased = ref.route(FILE, hf, p)
    moved = (plain > 0) != (biased > 0)
    assert 0 < int(moved.any(axis=1).sum()) < hf.shape[0]
    # a chosen expert's weight is its UNBIASED score over the chosen ones'
    # unbiased scores, times the scale: the bias is in no weight
    chosen = biased > 0
    want = jnp.where(chosen, s, 0.0)
    want = want / want.sum(-1, keepdims=True) * 2.5
    np.testing.assert_allclose(biased, want, rtol=1e-5)
    # and the program follows it: all 32 experts held, one token at a time
    full = dict(p, w_up=jnp.tile(p["w_up"], (8, 1, 1)),
                w_down=jnp.tile(p["w_down"], (8, 1, 1)))
    y, _ = routed(cfg, hf, full, held=None, shared=False)
    y0, _ = routed(cfg, hf, dict(full, router_bias=jnp.zeros(32)),
                   held=None, shared=False)
    same = ~moved.any(axis=1)
    np.testing.assert_allclose(y[same], y0[same], rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(y - y0)[~same].max()) > 1e-3


@pytest.mark.parametrize("norm,scale", [(True, 2.5), (True, 1.0),
                                        (False, 2.5), (False, 1.0)])
def test_renormalise_then_scale(params, norm, scale):
    cfg, p = program_cfg(), layer(params, "moe", 1)
    h = normed_inputs(9)
    file = dict(FILE, norm_topk_prob=norm, routed_scaling_factor=scale)
    got, _ = routed(cfg, h, p, norm_topk_prob=norm, scale=scale)
    want = per_row(lambda row: ref.moe(file, row, p), h)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if norm:  # the chosen weights sum to the scale, whatever the scores
        with jax.default_matmul_precision("highest"):
            w = ref.route(file, h.reshape(-1, 64), p)
        np.testing.assert_allclose(w.sum(-1), scale, rtol=1e-5)


# --- (c) the held range: the shares add up ---------------------------------- #


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """A router over 32 experts split 16 ways, 2 a share: the routed parts
    of all 16 shares, plus the shared expert counted once, are the uncut
    layer's output as the reference gives it with all 32 experts."""
    file = dict(FILE, n_routed_experts=32, first_expert=0)
    cfg = program_cfg(file)
    p = layer(llama.init_params(cfg, jax.random.PRNGKey(21)), "moe", 3)
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(22), (32,))
    h = normed_inputs(10)
    want = per_row(lambda row: ref.moe(file, row, p), h)
    total, shares = 0.0, []
    for share in range(16):
        mine = dict(p, w_up=p["w_up"][2 * share:2 * share + 2],
                    w_down=p["w_down"][2 * share:2 * share + 2])
        y, stats = routed(cfg, h, mine, held=(2 * share, 2), shared=False)
        total = total + y
        shares.append(float(stats["held_share"]))
        assert float(stats["dropped"]) == 0.0
        # and this share alone is the reference's for the same range
        np.testing.assert_allclose(y, per_row(lambda row: ref.routed(
            file, row, mine, 2 * share, 2), h), rtol=1e-4, atol=1e-5)
    with jax.default_matmul_precision("highest"):
        total = total + ref.shared(h, p)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    assert sum(shares) == pytest.approx(1.0)
    whole, _ = routed(cfg, h, p, held=None)
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=1e-5)


def test_held_swiglu_softmax_shares_add_up_to_the_whole_layer():
    """The held range is the routed layer's, not one router's: softmax
    scores and SwiGLU experts (the block's routed MLP of
    ``tests/test_olmoe.py``) in four shares of four add up to the same
    call with every expert here."""
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    h = normed_inputs(13)
    router = jax.random.normal(k[0], (64, 16)) / 8
    gate, up = (jax.random.normal(k[i], (16, 64, 32)) / 8 for i in (1, 2))
    down = jax.random.normal(k[3], (16, 32, 64)) / 6
    whole, w_stats = routed_mlp(h, router, gate, up, down, top_k=4)
    total, lb = 0.0, 0.0
    for first in range(0, 16, 4):
        mine = slice(first, first + 4)
        y, stats = routed_mlp(h, router, gate[mine], up[mine], down[mine],
                              top_k=4, held=(first, 4))
        total, lb = total + y, lb + stats["lb_loss"]
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    # a share's load-balancing loss is its terms of the sum over experts
    assert float(lb) == pytest.approx(float(w_stats["lb_loss"]), rel=1e-5)


def test_every_held_assignment_is_computed_when_all_fall_here(params):
    """Dropless whatever the imbalance: a router that sends every choice of
    every token to three of the held experts (64 times an even share)."""
    cfg, p = program_cfg(), layer(params, "moe", 0)
    bias = jnp.zeros(32).at[8:11].set(10.0)  # all three choices held
    p = dict(p, router_bias=bias)
    h = normed_inputs(12, batch=4, seq=256)
    y, stats = routed(cfg, h, p)
    assert float(stats["held_share"]) == 1.0
    assert float(stats["dropped"]) == 0.0
    assert float(stats["max_load_ratio"]) == pytest.approx(32 / 3)
    np.testing.assert_allclose(
        y, per_row(lambda row: ref.moe(FILE, row, p), h), rtol=1e-4,
        atol=1e-5)


@pytest.mark.parametrize("to_held,all_rows", [(0.0, False), (0.05, False),
                                              (10.0, True)])
def test_rows_for_the_first_places_or_for_all(params, to_held, all_rows):
    """4 x 256 tokens make 3,072 assignments and the layer makes rows for
    the first 1,536 sorted places (four even shares of 4 of 32 experts)
    unless more fall here: either way the layer and its gradients are the
    reference's, with zeros (not what a buffer held) where no expert is."""
    cfg, p = program_cfg(), layer(params, "moe", 0)
    p = dict(p, router_bias=jnp.zeros(32).at[8:11].set(to_held))
    h = normed_inputs(12, batch=4, seq=256)
    target = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    (got, stats), g_got = jax.value_and_grad(
        lambda p, h: (lambda y, s: (jnp.sum(y * target), s))(
            *routed(cfg, h, p)), argnums=(0, 1), has_aux=True)(p, h)
    want, g_want = jax.value_and_grad(lambda p, h: jnp.sum(per_row(
        lambda row: ref.moe(FILE, row, p), h) * target), argnums=(0, 1))(p, h)
    assert (float(stats["held_share"]) * 3072 > 1536) == all_rows
    assert float(stats["dropped"]) == 0.0
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert_trees_close(g_got, g_want, rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("gated", [False, True])
def test_a_width_filled_up_to_a_multiple_of_512_adds_nothing(gated):
    """The grouped products run at a width filled up with zeros (520 ->
    1024): values and gradients are the plain products' at 520."""
    from ray_tpu.ops.moe import _expert_ffn

    k = jax.random.split(jax.random.PRNGKey(31), 4)
    xs = jax.random.normal(k[0], (24, 16))
    gate = jax.random.normal(k[1], (2, 16, 520)) / 4 if gated else None
    up = jax.random.normal(k[2], (2, 16, 520)) / 4
    down = jax.random.normal(k[3], (2, 520, 16)) / 20
    counts = jnp.array([9, 15], jnp.int32)

    def plain(xs, gate, up, down):
        def one(x, e):
            u = x @ up[e]
            a = (jnp.square(jax.nn.relu(u)) if gate is None
                 else jax.nn.silu(x @ gate[e]) * u)
            return a @ down[e]
        with jax.default_matmul_precision("highest"):
            return jnp.concatenate([one(xs[:9], 0), one(xs[9:], 1)])

    for f in (_expert_ffn, lambda x, g, u, d, c: plain(x, g, u, d)):
        out, grads = jax.value_and_grad(
            lambda x, g, u, d: jnp.sum(jnp.sin(f(x, g, u, d, counts))),
            argnums=(0, 2, 3))(xs, gate, up, down)
        if f is _expert_ffn:
            got = (out, grads)
    assert float(got[0]) == pytest.approx(float(out), rel=1e-5)
    assert_trees_close(got[1], grads, rtol=1e-3, atol=1e-5)
    assert got[1][1].shape == up.shape and got[1][2].shape == down.shape


# --- (d) the whole model ---------------------------------------------------- #


def test_loss_and_every_gradient_leaf_match_reference(params, tokens):
    cfg = program_cfg()
    (loss, report), grads = jax.value_and_grad(
        lambda p: llama.loss_parts(cfg, p, tokens), has_aux=True)(params)
    want, g_want = jax.value_and_grad(
        lambda p: ref.loss(FILE, p, jnp.asarray(tokens)))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert_trees_close(grads, g_want, rtol=2e-3, atol=2e-5)
    assert set(report) == {"max_load_ratio", "dropped", "held_share"}
    assert float(report["dropped"]) == 0.0
    # remat is the same program's values
    again = llama.loss_fn(dataclasses.replace(cfg, remat=False), params,
                          tokens)
    assert float(again) == pytest.approx(float(loss), rel=1e-6)


def test_loss_bfloat16_near_the_reference(params, tokens):
    cfg = program_cfg(dtype=jnp.bfloat16)
    got = float(llama.loss_fn(cfg, params, tokens))
    want = float(ref.loss(FILE, params, jnp.asarray(tokens)))
    assert abs(got - want) / want < 5e-3  # widths of 64: a coarse bound


def test_the_pattern_is_data():
    cfg = program_cfg()
    assert cfg.kinds == "MEMEM*EME" and cfg.head_dim == 32
    two = program_cfg(dict(FILE, num_hidden_layers=2))  # --rehearsal's stack
    assert two.kinds == "ME"
    tree = jax.eval_shape(lambda: llama.init_params(two, jax.random.PRNGKey(0)))
    assert sorted(tree["layers"]) == ["mamba", "moe"]
    assert tree["layers"]["moe"]["w_up"].shape == (1, 4, 64, 48)
    assert tree["layers"]["moe"]["router"].shape == (1, 64, 32)
    with pytest.raises(ValueError, match="layer_pattern"):
        program_cfg(dict(FILE, hybrid_override_pattern="MEX"))
    with pytest.raises(ValueError, match="layer_pattern"):
        program_cfg(dict(FILE, hybrid_override_pattern="MEM"))
    with pytest.raises(ValueError, match="patterned stack"):
        LlamaConfig(num_experts=4, experts_per_token=2, shared_mlp_dim=8)
    # no pattern: the block in every layer, its tree and config as they were
    plain = LlamaConfig.debug()
    assert plain.layer_pattern == "" and plain.kinds == ""
    assert plain.head_dim == plain.dim // plain.n_heads


def test_num_params_counts_the_kinds_and_the_file():
    cfg = program_cfg()
    tree = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == cfg.num_params()
    with open(CONFIG_FILE) as f:
        file = json.load(f)
    real = spec.program_config(file)
    assert real.kinds == "MEMEM*EME" and real.head_dim == 128
    assert file["hybrid_override_pattern"] == PUBLISHED_PATTERN
    assert len(PUBLISHED_PATTERN) == file["published"]["num_hidden_layers"]
    # 4 x 38,744,896 + 4 x 100,125,440 + 23,399,040 + 88,080,384 + 2,688
    assert real.num_params() == 666_963_456
    tree = jax.eval_shape(lambda: llama.init_params(real,
                                                   jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == 666_963_456
    assert tree["layers"]["mamba"]["w_in"].shape == (4, 2688, 10304)
    assert tree["layers"]["moe"]["w_up"].shape == (4, 8, 2688, 1856)
    assert tree["layers"]["moe"]["router"].shape == (4, 2688, 128)
    assert tree["layers"]["attn"]["wq"].shape == (1, 2688, 4096)
    assert tree["layers"]["attn"]["wk"].shape == (1, 2688, 256)


def test_published_mamba_initialisation():
    p = ssm.init_mamba2(jax.random.PRNGKey(0), 3, 64, heads=64, head_dim=4,
                        groups=8, state=16, conv=4, dt_min=0.001,
                        dt_max=0.1, dt_floor=1e-4)
    a = np.exp(np.asarray(p["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 2.0
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert dt.min() >= 1e-4 * 0.999 and dt.max() <= 0.1 * 1.001
    assert np.median(dt) < 0.03  # log-uniform, not uniform
    assert float(jnp.abs(p["D"] - 1).max()) == 0.0


# --- (e) the trainer's step -------------------------------------------------- #


def _mesh(spec_str, n):
    from ray_tpu.train.spmd import build_train_mesh

    return build_train_mesh(spec_str, jax.devices()[:n])


def _run(cfg, mesh, tokens, steps=2):
    from ray_tpu.train.spmd import make_spmd_train_step

    init, step, sharding, _ = make_spmd_train_step(cfg, mesh)
    state = init(jax.random.PRNGKey(0))
    out = []
    for _ in range(steps):
        state, loss, router = step(state, jax.device_put(tokens, sharding))
        out.append((float(loss), {k: float(v) for k, v in router.items()}))
    return out, state


def test_spmd_step_one_device_against_data2(tokens):
    cfg = program_cfg()
    one, state1 = _run(cfg, _mesh("", 1), tokens)
    two, state2 = _run(cfg, _mesh("data=2", 2), tokens)
    want = float(llama.loss_fn(
        cfg, llama.init_params(cfg, jax.random.PRNGKey(0)), tokens))
    assert one[0][0] == pytest.approx(want, rel=1e-5)
    for (l1, r1), (l2, r2) in zip(one, two):
        assert l1 == pytest.approx(l2, rel=2e-5)
        assert r1["held_share"] == pytest.approx(r2["held_share"])
        assert r1["dropped"] == r2["dropped"] == 0.0
        assert set(r1) == {"max_load_ratio", "dropped", "held_share"}
    assert one[1][0] < one[0][0]  # adamw learns
    assert_trees_close(state2["params"], state1["params"], rtol=1e-3,
                       atol=1e-4)
    # the choice bias is a parameter nothing updates
    assert float(jnp.abs(
        state1["params"]["layers"]["moe"]["router_bias"]).max()) == 0.0


@pytest.mark.parametrize("spec_str,n", [("fsdp=2", 2), ("data=2,fsdp=2", 4),
                                        ("tensor=2", 2)])
def test_a_pattern_on_fsdp_or_tensor_is_refused(spec_str, n):
    from ray_tpu.train.spmd import make_spmd_train_step

    with pytest.raises(ValueError) as e:
        make_spmd_train_step(program_cfg(), _mesh(spec_str, n))
    msg = str(e.value)
    assert "batch axes only" in msg and "per-kind" in msg
    assert "tensor-parallel form" in msg


def test_paths_with_their_own_block_refuse_a_pattern():
    cfg = program_cfg()
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        llama.LlamaDecodeEngine(cfg)
    from ray_tpu.parallel.mesh import make_mesh

    with pytest.raises(NotImplementedError, match="layer_pattern"):
        llama.make_pipeline_train_step(cfg, make_mesh(axis_sizes={"pipe": 2}),
                                       2)


def test_gspmd_step_runs_the_pattern(tokens):
    from ray_tpu.parallel.mesh import make_mesh

    cfg = program_cfg()
    init, step, sharding, _ = llama.make_train_step(
        cfg, make_mesh(devices=jax.devices()[:1]))
    state = init(jax.random.PRNGKey(0))
    _, loss = step(state, jax.device_put(tokens, sharding))
    want = float(llama.loss_fn(
        cfg, llama.init_params(cfg, jax.random.PRNGKey(0)), tokens))
    assert float(loss) == pytest.approx(want, rel=1e-5)


def test_loop_reports_held_share_and_sets_the_stack_gauge():
    from ray_tpu.train.session import TrainContext, set_context
    from ray_tpu.train.spmd import spmd_train_loop
    from ray_tpu.util import flight_recorder as fr
    from ray_tpu.util.metrics import registry

    # the recorder is the process's: this test reads its own instants alone
    # and leaves none for the next test that reads the ring
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
    assert 0.0 < reports[-1]["moe_held_share"] < 1.0
    assert reports[-1]["moe_dropped"] == 0.0
    assert "moe_lb_loss" not in reports[-1]
    payload.update(source="test", node_hex="", offset_s=0.0)
    rep = fr.attribute_trace(fr.build_span_events([payload]))
    assert set(rep["router"]) == {"moe.max_load_ratio", "moe.dropped",
                                  "moe.held_share"}
    assert rep["router"]["moe.held_share"]["last"] == pytest.approx(
        reports[-1]["moe_held_share"])
    gauge = registry().local_values("ray_tpu_train_stack")
    assert {k[0][1]: v for k, v in gauge.items()} == {
        "block_layers": 0.0, "mamba_layers": 4.0, "moe_layers": 4.0,
        "attn_layers": 1.0, "experts_held": 4.0, "router_experts": 32.0}


# --- (f) the programs that were, and the benchmark's files ------------------ #


def test_an_empty_pattern_lowers_to_the_pinned_texts(monkeypatch):
    """The routed and the dense step of ``tests/test_olmoe.py``, whose
    hashes that file pins (with the block's names off, as there): the new
    fields at their defaults build them."""
    import hashlib

    import test_olmoe

    test_olmoe.turn_names_off(monkeypatch)
    for name in ("routed spmd, one device", "dense spmd, one device"):
        sha, lower = test_olmoe.PROGRAMS[name]
        assert hashlib.sha256(lower().encode()).hexdigest() == sha


def test_benchmark_files_fit_together_with_the_new_cell():
    from benchmarks.checks import test_yardstick

    test_yardstick.test_benchmark_files_fit_together()
    b = spec.cell_bundle("train-nemotron3nano-1chip")
    assert b["traffic"]["seq"] == 8192 and b["cell"]["chips"] == 1
    assert sorted(m["name"] for m in b["end_to_end"]) == [
        "setup_s", "train_tokens_per_s_chip"]
    assert "train.mfu" not in {m["name"] for m in b["per_layer"]}
    file = b["config"]
    assert (file["n_routed_experts"], file["router_experts"]) == (8, 128)
    tiny = spec.cell_bundle("train-nemotron3nano-1chip", rehearsal=True)
    assert spec.program_config(tiny["config"]).kinds == "ME"
