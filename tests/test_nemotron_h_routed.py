"""The routed layer of a patterned stack against the plain float32
reference (``benchmarks/reference/nemotron_h_decoder.py``), at the small
widths of ``nemotron_h_small``: values and gradients, the router's bias /
renormalisation / scale, and the held range (the shares add up to the uncut
layer; rows for the first places or for all; a filled-up width)."""

import numpy as np
import pytest

from benchmarks.reference import nemotron_h_decoder as ref
from jitted import reference, value_and_grad
from nemotron_h_small import (FILE, assert_trees_close, jax, jnp, layer,
                              llama, normed_inputs, params, per_row,
                              program_cfg)
from ray_tpu.ops.moe import routed_mlp


def routed(cfg, h, p, held="cfg", shared=True, **over):
    if held == "cfg":
        held = (cfg.first_expert, cfg.num_experts)
    kw = dict(top_k=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
              scoring=cfg.router_scoring, scale=cfg.routed_scale, held=held)
    kw.update(over)
    return jax.jit(lambda h, p: routed_mlp(
        h, p["router"], None, p["w_up"], p["w_down"],
        choice_bias=p["router_bias"],
        shared=(p["shared_up"], p["shared_down"]) if shared else None,
        **kw))(h, p)


def test_routed_layer_matches_reference_outputs_and_gradients(params):
    cfg, p = program_cfg(), layer(params, "moe", 2)
    h = normed_inputs(6)
    target = jax.random.normal(jax.random.PRNGKey(7), h.shape)
    (got, stats), g_got = value_and_grad(
        lambda p, h: (lambda y, s: (jnp.sum(y * target), s))(
            *routed(cfg, h, p)), p, h, argnums=(0, 1), has_aux=True)
    want, g_want = value_and_grad(lambda p, h: jnp.sum(per_row(
        lambda row: ref.moe(FILE, row, p), h) * target), p, h, argnums=(0, 1))
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert_trees_close(g_got, g_want, rtol=1e-3, atol=1e-5)
    assert float(jnp.abs(g_got[0]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g_got[0]["router"]).max()) > 0.0
    # 4 of 32 held: the share of the 2 x 40 x 3 assignments that fell here
    weight = reference(lambda h: ref.route(FILE, h, p), h.reshape(-1, 64))
    here = float((weight[:, 8:12] > 0).sum()) / float((weight > 0).sum())
    assert float(stats["held_share"]) == pytest.approx(here)
    assert 0.02 < here < 0.4
    assert float(stats["dropped"]) == 0.0
    assert set(stats) == {"max_load_ratio", "dropped", "held_share",
                          "held_chunks"}


# --- the router: bias, renormalisation, scale --------------------------- #


def test_bias_changes_the_choice_and_never_the_weights(params):
    cfg, p = program_cfg(), layer(params, "moe", 0)
    hf = normed_inputs(8).reshape(-1, 64)
    s, plain, biased = reference(lambda hf: (
        jax.nn.sigmoid(hf @ p["router"]),
        ref.route(FILE, hf, dict(p, router_bias=jnp.zeros(32))),
        ref.route(FILE, hf, p)), hf)
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
        w = reference(lambda h: ref.route(file, h, p), h.reshape(-1, 64))
        np.testing.assert_allclose(w.sum(-1), scale, rtol=1e-5)


# --- the held range: the shares add up ---------------------------------- #


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

    def a_share(share):  # the program's, and the reference's for its range
        mine = dict(p, w_up=p["w_up"][2 * share:2 * share + 2],
                    w_down=p["w_down"][2 * share:2 * share + 2])
        return (routed(cfg, h, mine, held=(2 * share, 2), shared=False),
                per_row(lambda row: ref.routed(file, row, mine, 2 * share, 2),
                        h))

    total, shares = 0.0, []
    for (y, stats), alone in jax.jit(
            lambda: [a_share(share) for share in range(16)])():
        total = total + y
        shares.append(float(stats["held_share"]))
        assert float(stats["dropped"]) == 0.0
        np.testing.assert_allclose(y, alone, rtol=1e-4, atol=1e-5)
    total = total + reference(lambda h: ref.shared(h, p), h)
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
    mlp = jax.jit(routed_mlp, static_argnames=("top_k", "held"))
    whole, w_stats = mlp(h, router, gate, up, down, top_k=4)
    total, lb = 0.0, 0.0
    for first in range(0, 16, 4):
        mine = slice(first, first + 4)
        y, stats = mlp(h, router, gate[mine], up[mine], down[mine], top_k=4,
                       held=(first, 4))
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
    (got, stats), g_got = value_and_grad(
        lambda p, h: (lambda y, s: (jnp.sum(y * target), s))(
            *routed(cfg, h, p)), p, h, argnums=(0, 1), has_aux=True)
    want, g_want = value_and_grad(lambda p, h: jnp.sum(per_row(
        lambda row: ref.moe(FILE, row, p), h) * target), p, h, argnums=(0, 1))
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
        out, grads = value_and_grad(
            lambda x, g, u, d: jnp.sum(jnp.sin(f(x, g, u, d, counts))),
            xs, gate, up, down, argnums=(0, 2, 3))
        if f is _expert_ffn:
            got = (out, grads)
    assert float(got[0]) == pytest.approx(float(out), rel=1e-5)
    assert_trees_close(got[1], grads, rtol=1e-3, atol=1e-5)
    assert got[1][1].shape == up.shape and got[1][2].shape == down.shape
