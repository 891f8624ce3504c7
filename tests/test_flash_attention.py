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
    # (B, T, Hq, Hkv, D, causal, block) -- T must block (>=64); D=64 exercises
    # the lane-padding path, Hq != Hkv the GQA index map; block None: the
    # rule's (pick_blocks)
    (1, 128, 2, 1, 64, True, None),
    (1, 128, 2, 2, 128, False, None),
    # several loop steps a grid block: whole steps of 128, whole blocks of 64
    # up to the next step, and the block the diagonal cuts; GQA 4
    (1, 512, 4, 1, 64, True, (64, 128)),
    (1, 512, 2, 2, 128, False, (64, 128)),
    # the rule's own blocks (512 x 512 at both): a length 1,024 does not
    # divide, and two grid blocks (the first walks the cut block alone)
    (1, 1536, 4, 1, 128, True, None),
    (1, 1024, 2, 2, 128, True, None),
]
DTYPES = [("float32", 2e-2), ("bfloat16", 2e-2)]


def _inputs(seed, B, T, Hq, Hkv, D, dtype):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, T, h, D), jnp.float32).astype(dtype)
                 for h in (Hq, Hkv, Hkv))


def _f32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


@pytest.mark.parametrize("dtype,tol", DTYPES)
@pytest.mark.parametrize("B,T,Hq,Hkv,D,causal,block", CASES)
def test_forward_matches_reference(B, T, Hq, Hkv, D, causal, block, dtype,
                                   tol):
    q, k, v = _inputs(0, B, T, Hq, Hkv, D, dtype)
    out = flash_attention(q, k, v, causal=causal, block=block, interpret=True)
    assert out.dtype == jnp.dtype(dtype)
    expect = _ref(*_f32(q, k, v), causal)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(expect), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype,tol", [("float32", 5e-2), ("bfloat16", 5e-2)])
@pytest.mark.parametrize("B,T,Hq,Hkv,D,causal,block", CASES)
def test_grads_match_reference(B, T, Hq, Hkv, D, causal, block, dtype, tol):
    """dQ from its kernel, dK and dV from theirs and the group's sum, against
    plain attention's in float32 on the same (rounded) inputs."""
    q, k, v = _inputs(1, B, T, Hq, Hkv, D, dtype)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block=block,
                            interpret=True).astype(jnp.float32)
        return (o * o).sum()

    def loss_ref(q, k, v):
        o = _ref(q, k, v, causal)
        return (o * o).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(*_f32(q, k, v))
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        assert a.dtype == jnp.dtype(dtype), name
        # float32: element by element, as it always was. bfloat16: an element
        # against the largest of its array (each of a sum's many terms is
        # rounded to 8 bits of its own size)
        scale = float(jnp.max(jnp.abs(b))) if dtype == "bfloat16" else 1.0
        np.testing.assert_allclose(
            np.asarray(a.astype(jnp.float32)) / scale, np.asarray(b) / scale,
            atol=tol, rtol=tol, err_msg=name)


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
    # a record names the [grid block, loop step] its kernels ran
    assert {r["path"]: r["blocks"] for r in fa.paths_taken()
            if r["q_shape"] == list(q_shape) and r["dtype"] == "bfloat16"
            } == {fa.PATH_PALLAS_INTERPRET: [128, 128],
                  fa.PATH_REFERENCE: None}

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


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_three_kernels_keep_the_result_types_the_yardstick_reads(dtype):
    """``benchmarks/layer_metrics/flash_roofline.json`` tells the three calls
    apart in a trace by RESULT TYPES (a call that matches none of its three
    patterns makes the metric null): the forward gives ``(o in q's type,
    float32[B, Hq, T, 1])``, dQ ONE array in q's type, dK/dV ``(float32,
    float32)`` a QUERY head (the group's sum is outside the kernel)."""
    B, T, Hq, Hkv, D = 1, 128, 4, 2, 128
    q = jnp.zeros((B, T, Hq, D), dtype)
    kv = jnp.zeros((B, T, Hkv, D), dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    results = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                results[eqn.params["name"]] = [
                    (v.aval.shape, str(v.aval.dtype)) for v in eqn.outvars]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv).jaxpr)
    head = (B, Hq, T, D)
    assert results == {
        "flash_fwd": [(head, dtype), ((B, Hq, T, 1), "float32")],
        "flash_dq": [(head, dtype)],
        "flash_dkv": [(head, "float32"), (head, "float32")],
    }


def test_the_block_rule_at_the_cells_shapes():
    """(grid block, loop step) by sequence: the five train cells' (2,048 twice,
    4,096, 8,192 twice), the lengths 1,024 does not divide, and what takes
    the kernel with smaller blocks or not at all. Every step divides its
    sequence and is whole grid blocks, so no shape that blocks falls to the
    reference for its step."""
    from ray_tpu.ops.flash_attention import attention_path, pick_blocks

    assert {t: pick_blocks(t) for t in (2048, 4096, 8192, 4608, 5120, 1536,
                                        2560, 1024, 512, 384, 128, 64)} == {
        2048: (512, 512), 4096: (512, 1024), 8192: (512, 2048),
        4608: (512, 512), 5120: (512, 1024), 1536: (512, 512),
        2560: (512, 512), 1024: (512, 512), 512: (512, 512),
        384: (128, 128), 128: (128, 128), 64: (64, 64)}
    assert pick_blocks(100) is None
    # float32 operands (twice the bytes in VMEM): the step stays at the block
    assert pick_blocks(8192, 4) == (512, 512) == pick_blocks(2048, 4)
    for t in range(64, 16384 + 1, 64):
        blk, step = pick_blocks(t)
        assert t % step == 0 and step % blk == 0 and blk <= step <= 4 * blk
        assert attention_path((1, t, 8, 128), (1, t, 2, 128),
                              interpret=True)[0] == "pallas_interpret"


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
