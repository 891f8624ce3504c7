"""The Mamba-2 layer and the attention layer of a patterned stack against
the plain float32 reference (``benchmarks/reference/nemotron_h_decoder.py``),
at the small widths of ``nemotron_h_small``: the mixer's values and gradients
at three lengths, the chunked scan against the recurrence whatever the
chunk, attention without rotation, the published initialisation."""

import dataclasses

import numpy as np
import pytest

from benchmarks.reference import nemotron_h_decoder as ref
from jitted import value_and_grad
from nemotron_h_small import (FILE, SEQ, assert_trees_close, jax, jnp, layer,
                              llama, normed_inputs, params, per_row,
                              program_cfg)
from ray_tpu.ops import ssm


def mixer(cfg, h, p):
    return jax.jit(lambda h, p: ssm.mamba2_mixer(
        h, p, heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
        groups=cfg.ssm_groups, state=cfg.ssm_state, chunk=cfg.ssm_chunk,
        eps=cfg.norm_eps))(h, p)


@pytest.mark.parametrize("seq", [SEQ, 48, 11],
                         ids=["two-chunks-and-a-rest", "three-chunks",
                              "shorter-than-a-chunk"])
def test_mamba_mixer_chunked_against_the_recurrence(params, seq):
    cfg, p = program_cfg(), layer(params, "mamba", 1)
    h = normed_inputs(1, seq=seq)
    target = jax.random.normal(jax.random.PRNGKey(2), h.shape)
    got, g_got = value_and_grad(
        lambda p: jnp.sum(mixer(cfg, h, p) * target), p)
    want, g_want = value_and_grad(lambda p: jnp.sum(per_row(
        lambda row: ref.mamba(FILE, row, p), h) * target), p)
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
    scan = jax.jit(ssm.ssd_scan, static_argnums=5)
    by_chunk = [scan(x, dt, a, b_in, c_in, q) for q in (16, 5, 64)]

    def step(state, now):  # [B, H, P, N], heads 0-3 read group 0
        x_t, dt_t, b_t, c_t = now
        b_t, c_t = (jnp.repeat(v, 4, axis=1) for v in (b_t, c_t))
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    after, want = jax.jit(lambda *now: jax.lax.scan(
        step, jnp.zeros((2, 8, 16, 16)), jax.tree.map(
            lambda v: jnp.moveaxis(v, 1, 0), now)))(x, dt, b_in, c_in)
    for got, state in by_chunk:  # the outputs, and the state it ends in
        np.testing.assert_allclose(got, jnp.moveaxis(want, 0, 1), rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(state, after, rtol=1e-4, atol=1e-5)


def test_attention_layer_without_rotation_matches_reference(params):
    cfg, p = program_cfg(), layer(params, "attn")
    x = normed_inputs(4)
    attend = jax.jit(lambda cfg, x, p: llama.pattern_layer(
        cfg, "*", llama.flash_causal, x, p)[0], static_argnums=0)
    got = attend(cfg, x, p)
    want = x + per_row(lambda row: ref.attention(
        FILE, ref._rms_norm(row, p["norm"], 1e-5), p), x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # rotation is the config's: the same weights WITH it give another result
    rot = attend(dataclasses.replace(cfg, rope=True), x, p)
    assert float(jnp.abs(rot - got).max()) > 1e-2


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
