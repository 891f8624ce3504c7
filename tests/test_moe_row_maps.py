"""The routed layer's row maps (``ops/moe.py``) against the plain ``[N, k,
d]`` forms they replaced: ``_sum_rows`` (a token's k rows summed, weighted
or not, from ONE slot-major gather in the rows' own type: ``_combine`` and
``_dispatch``'s transpose) and ``_combine``'s cotangents (the weights' made
where the rows lie). Then ``routed_mlp`` with every expert here, values AND
gradients, against the repository's float32 reference
(``benchmarks/reference/olmoe_decoder.py``): over k in {6, 8, 12} and N in
{1, 5, 64} (a decode call; a count that is no multiple of 8), with every
token on the same experts, and the lowered text, which holds no ``[N, k,
d]`` operand in either type."""

import re

import numpy as np
import pytest

from benchmarks.reference import olmoe_decoder as ref

jax = pytest.importorskip("jax")
jnp = jax.numpy

from jitted import value_and_grad  # noqa: E402
from ray_tpu.ops.moe import (_combine, _dispatch,  # noqa: E402
                             _sum_rows, routed_mlp)

KS, NS = (6, 8, 12), (1, 5, 64)
D, F, E = 64, 32, 16


# --- (a) the three maps alone against the forms they replaced ---------------- #


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("k", KS)
def test_the_maps_match_the_plain_forms(k, n, dtype):
    """``_combine``, its weights' cotangent and ``_dispatch``'s transpose
    against ``einsum("nk,nkd->nd")``, ``einsum("nkd,nd->nk")`` and
    ``[N, k, d].sum(1)`` over the same rows in float32."""
    rng = np.random.RandomState(k * 100 + n)
    rows = jnp.asarray(rng.standard_normal((n * k, D)), dtype)
    w = jnp.asarray(rng.uniform(0.0, 1.0, (n, k)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    order = jnp.asarray(np.argsort(rng.randint(0, E, n * k), kind="stable"),
                        jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)

    @jax.jit
    def both(rows, w, ct, order, inverse):
        nkd = rows[inverse.reshape(n, k)].astype(jnp.float32)
        hi = jax.lax.Precision.HIGHEST
        y, back = jax.vjp(lambda r, w: _combine(r, w, order, inverse),
                          rows, w)
        d_ys, d_w = back(ct)
        # a cotangent of the sorted rows, in their type: the rows themselves
        _, back = jax.vjp(lambda h: _dispatch(h, order, inverse),
                          jnp.zeros((n, D), rows.dtype))
        return (
            (y, _sum_rows(rows, inverse, n), d_w, back(rows)[0], d_ys),
            (jnp.einsum("nk,nkd->nd", w, nkd, precision=hi), nkd.sum(1),
             jnp.einsum("nkd,nd->nk", nkd, ct, precision=hi),
             nkd.sum(1).astype(rows.dtype),
             (w.reshape(-1)[order][:, None] * ct[order // k]).astype(
                 rows.dtype)))

    got, want = both(rows, w, ct, order, inverse)
    names = ("weighted", "summed", "weights' cotangent",
             "dispatch's transpose", "rows' cotangent")
    for g, r, name in zip(got, want, names):
        assert g.dtype == r.dtype and g.shape == r.shape, name
        tol = 1e-6 if g.dtype == jnp.float32 else 1e-2  # one bfloat16 step
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r, np.float32), rtol=tol,
            atol=tol * float(jnp.abs(r).max()), err_msg=name)


# --- (b) routed_mlp, every expert here, against the float32 reference ------- #


def _layer(k, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    file = {"num_experts": E, "num_experts_per_tok": k,
            "norm_topk_prob": False}
    p = {"router": jax.random.normal(keys[0], (D, E)) * 0.3,
         "w_gate": jax.random.normal(keys[1], (E, D, F)) / np.sqrt(D),
         "w_up": jax.random.normal(keys[2], (E, D, F)) / np.sqrt(D),
         "w_down": jax.random.normal(keys[3], (E, F, D)) / np.sqrt(F)}
    return file, p


NAMES = ("router", "w_gate", "w_up", "w_down")


def _against_the_reference(file, p, h):
    k = file["num_experts_per_tok"]
    target = jax.random.normal(jax.random.PRNGKey(1), h.shape)

    def program(h, *w):
        y, stats = routed_mlp(h, *w, top_k=k)
        return jnp.sum(y * target) + stats["lb_loss"], (y, stats)

    def plain(h, *w):
        with jax.default_matmul_precision("highest"):
            y, lb, _ = ref.experts(file, h, dict(zip(NAMES, w)))
        return jnp.sum(y * target) + lb, y

    args = (h,) + tuple(p[n] for n in NAMES)
    (_, (y, stats)), grads = value_and_grad(
        program, *args, argnums=range(5), has_aux=True)
    (_, y_ref), grads_ref = value_and_grad(
        plain, *args, argnums=range(5), has_aux=True)
    assert y.dtype == jnp.float32
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    assert float(stats["dropped"]) == 0.0
    for name, g, g_ref in zip(("h",) + NAMES, grads, grads_ref):
        scale = float(jnp.abs(g_ref).max())
        np.testing.assert_allclose(g, g_ref, rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=name)
    return stats


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("k", KS)
def test_routed_mlp_values_and_gradients(k, n):
    file, p = _layer(k)
    h = jax.random.normal(jax.random.PRNGKey(7 + n), (n, D), jnp.float32)
    _against_the_reference(file, p, h)


@pytest.mark.parametrize("k", KS)
def test_every_token_on_the_same_experts(k):
    """A total imbalance: one constant direction in ``h`` that the router
    reads, so the same k experts win for every token, in the same order."""
    file, p = _layer(k, seed=3)
    router = np.array(p["router"]) * 0.01
    for rank, e in enumerate(range(1, 2 * k, 2)):
        router[:, e % E] += 1.0 - 0.05 * rank
    p["router"] = jnp.asarray(router)
    h = jax.random.normal(jax.random.PRNGKey(5), (40, D), jnp.float32) + 4.0
    stats = _against_the_reference(file, p, h)
    assert float(stats["max_load_ratio"]) == pytest.approx(E / k)


# --- (c) the lowered text --------------------------------------------------- #


@pytest.mark.parametrize("passes", ["forward", "forward_and_backward"])
def test_no_float32_n_k_d_operand_in_the_lowered_text(passes):
    """At [64, 6, 128] in bfloat16: the gathers' results are slot-major
    bfloat16 ``[6 * 64, 128]``; nothing of shape ``[64, 6, 128]`` is made in
    either type, and the one float32 ``[6, 64, 128]`` is the widening inside
    the fused sum."""
    n, k, d = 64, 6, 128
    shapes = [jax.ShapeDtypeStruct(s, t) for s, t in (
        ((n, d), jnp.bfloat16), ((d, E), jnp.float32),
        ((E, d, F), jnp.float32), ((E, d, F), jnp.float32),
        ((E, F, d), jnp.float32))]

    def fwd(h, *w):
        return routed_mlp(h, *w, top_k=k)[0].sum()

    fn = fwd if passes == "forward" else jax.grad(fwd, argnums=range(5))
    text = jax.jit(fn).lower(*shapes).as_text()
    assert not re.search(rf"{n}x{k}x{d}x(f32|bf16)", text)
    assert f"{k * n}x{d}xbf16" in text
    assert "optimization_barrier" in text
