"""Flash-attention kernel correctness vs the exact reference path.

Runs the Pallas kernels in interpreter mode on the CPU test mesh (shapes
kept tiny — interpret mode executes block-by-block in Python). The same
kernels run compiled on the chip in chip_smoke.py, against the same reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.ring_attention import plain_attention


def _ref(q, k, v, causal=True):
    Hq, Hkv = q.shape[2], k.shape[2]
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return plain_attention(q, k, v, causal=causal)


CASES = [
    # (B, T, Hq, Hkv, D, causal) — T must block (>=64); D=64 exercises the
    # lane-padding path, Hq != Hkv the GQA index map.
    (1, 128, 2, 1, 64, True),
    (1, 128, 2, 2, 128, False),
]


@pytest.mark.parametrize("B,T,Hq,Hkv,D,causal", CASES)
def test_forward_matches_reference(B, T, Hq, Hkv, D, causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, T, Hq, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    expect = _ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-2, rtol=2e-2)


def test_grads_match_reference():
    B, T, Hq, Hkv, D, causal = 1, 128, 2, 1, 64, True
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, T, Hq, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True)
        return (o * o).sum()

    def loss_ref(q, k, v):
        o = _ref(q, k, v, causal)
        return (o * o).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-2, rtol=5e-2, err_msg=name)


def test_odd_shapes_take_the_reference_and_say_so():
    # T=100 doesn't block: the exact path runs, and the run can tell
    from ray_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 100, 2, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 100, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 100, 2, 32), jnp.float32)
    path, reason = fa.attention_path(q.shape, k.shape, interpret=True)
    assert path == fa.PATH_REFERENCE and "100" in reason
    out = flash_attention(q, k, v, causal=True, interpret=True)
    expect = _ref(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)
    rec = [r for r in fa.paths_taken() if r["q_shape"] == [1, 100, 2, 32]]
    assert len(rec) == 1 and rec[0]["path"] == fa.PATH_REFERENCE
    assert rec[0]["reason"] == reason and rec[0]["calls"] >= 1


def test_flash_attention_reports_its_path():
    """Which attention ran is recorded per (shapes, dtype): the kernel
    where it can run (interpreted here), the reference on a backend that
    is not a TPU, and a backend that cannot be asked is an error."""
    from ray_tpu.ops import flash_attention as fa

    q_shape, k_shape = (1, 128, 4, 32), (1, 128, 2, 32)
    assert fa.attention_path(q_shape, k_shape, interpret=True) == (
        fa.PATH_PALLAS_INTERPRET, "interpret=True")
    path, reason = fa.attention_path(q_shape, k_shape)  # CPU backend
    assert path == fa.PATH_REFERENCE and "'cpu'" in reason

    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(*q_shape), jnp.bfloat16)
    k = jnp.asarray(rng.randn(*k_shape), jnp.bfloat16)
    flash_attention(q, k, k, causal=True, interpret=True)
    flash_attention(q, k, k, causal=True)
    taken = {r["path"] for r in fa.paths_taken()
             if r["q_shape"] == list(q_shape) and r["dtype"] == "bfloat16"}
    assert taken == {fa.PATH_PALLAS_INTERPRET, fa.PATH_REFERENCE}

    def broken_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    real, fa.jax.default_backend = fa.jax.default_backend, broken_backend
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            fa.attention_path(q_shape, k_shape)
    finally:
        fa.jax.default_backend = real


def test_the_three_kernels_carry_their_names():
    """A trace or a reader tells the kernels apart by ``name=``: the forward
    and backward traced hold one ``pallas_call`` each of ``flash_fwd``,
    ``flash_dq`` and ``flash_dkv``."""
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    kv = jnp.zeros((1, 128, 1, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv).jaxpr)
    assert sorted(names) == ["flash_dkv", "flash_dq", "flash_fwd"]


# --- head width 256 on 20 / 20 heads: a latent block's attention ------------ #


@pytest.fixture(scope="module")
def wide():
    """q, k, v [1, 128, 20, 256] (two blocks of 64) and a cotangent."""
    rng = np.random.RandomState(2)
    return tuple(jnp.asarray(rng.randn(1, 128, 20, 256), jnp.float32)
                 for _ in range(4))


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_width_256_on_20_heads_forward(wide, dtype, tol):
    """The kernel's scale is ``1 / sqrt(256)`` and nothing is padded."""
    q, k, v, _ = (a.astype(dtype) for a in wide)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, block=64, interpret=True))(q, k, v)
    want = _ref(*(a.astype(jnp.float32) for a in (q, k, v)))
    assert out.dtype == jnp.dtype(dtype) and out.shape == (1, 128, 20, 256)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    assert err < tol, err


@pytest.mark.parametrize("wrt", ["dq", "dk", "dv"])
def test_width_256_on_20_heads_gradients(wide, wrt):
    """dQ from its kernel, dK and dV from theirs (20 KV heads: the group sum
    is over ONE head), against plain attention's."""
    q, k, v, ct = wide
    arg = "dq dk dv".split().index(wrt)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, block=64, interpret=True) * ct), argnums=arg))(q, k, v)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(_ref(*a) * ct),
                            argnums=arg))(q, k, v)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 5e-5, err
