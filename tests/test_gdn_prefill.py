"""The delta rule's prefill kernel (``ops/gdn_prefill.py``), interpreted on
the CPU, against the XLA form it replaces on a TPU (``ops/ssm.py
causal_conv``, ``llama._delta_heads``, ``ops/gdn.py gated_delta_chunked``)
and against the recurrence token by token in float32 (``gated_delta_step``);
the rule that chooses between them, its record, and what ``jax.grad`` gets."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import gdn
from ray_tpu.ops import gdn_prefill as gp
from ray_tpu.ops.ssm import causal_conv
from test_flash_prefill import attend_gauge

HK, HV, D, TAPS = 1, 2, 128, 4
WIDTH = 2 * HK * D + HV * D


def config(dtype=jnp.float32, **fields):
    """What ``attend_delta`` and the rule read of a config."""
    return types.SimpleNamespace(**{**dict(
        dtype=dtype, lin_key_heads=HK, lin_value_heads=HV, lin_key_dim=D,
        lin_value_dim=D, lin_chunk=64), **fields})


def operands(seed, B, T, dtype, start=False, hk=HK, hv=HV):
    """``[q | k | v]`` as an in-projection leaves it, the convolution's
    weights, ``g`` and ``beta`` with decays of every size (``A`` from 1 to
    15, steps around 0.05), and where the sequence stands: zeros, or with
    ``start`` a state and a tail as a decode call would find them."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    width = 2 * hk * D + hv * D
    qkv = jax.random.normal(next(ks), (B, T, width)).astype(dtype)
    conv_w = jax.random.normal(next(ks), (TAPS, width)) * 0.5
    beta = jax.nn.sigmoid(jax.random.normal(next(ks), (B, T, hv)))
    g = -jnp.exp(jax.random.uniform(next(ks), (hv,), maxval=2.7)) \
        * jax.nn.softplus(jax.random.normal(next(ks), (B, T, hv)) - 3.0)
    state = 0.3 * start * jax.random.normal(next(ks), (B, hv, D, D))
    tail = (start * jax.random.normal(next(ks), (B, TAPS - 1, width))
            ).astype(dtype)
    return qkv, conv_w, g, beta, state, tail


def heads(cfg, qkv, conv_w, tail):
    """The recurrence's operands, as the XLA path makes them."""
    rows = jnp.concatenate([tail, qkv], axis=1).astype(jnp.float32)
    mixed = jax.nn.silu(causal_conv(rows, conv_w, 0.0)[:, TAPS - 1:])
    return llama._delta_heads(cfg, mixed)


@jax.jit
def by_steps(q, k, v, g, beta, state):
    def step(state, row):
        o, state = gdn.gated_delta_step(*row, state)
        return state, o

    end, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), end


def kernel(*a, **kw):
    return gp.gdn_prefill(*a, key_heads=HK, key_dim=D, interpret=True, **kw)


def off(got, want) -> float:
    return float(jnp.linalg.norm((got.astype(jnp.float32) - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


# (positions, sequences, type, from a kept state and tail): one row tile;
# several of 128; of 512; a row tile that is one chunk pair; three pages
CASES = {
    "one tile": (128, 1, jnp.float32, False),
    "two sequences from a kept state": (256, 2, jnp.float32, True),
    "three tiles, bfloat16, from a kept state": (384, 1, jnp.bfloat16, True),
    "two tiles of 512, bfloat16": (1024, 1, jnp.bfloat16, False),
    "five tiles, two sequences, bfloat16, kept": (640, 2, jnp.bfloat16, True),
    "three pages of 2,048, bfloat16": (6144, 1, jnp.bfloat16, False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_chunked_form_and_as_near_the_steps(case):
    """Outputs and the final state: the XLA form's to rounding, and no
    farther from the token-by-token recurrence in float32 than that form
    is (which rounds the same operands at the same places)."""
    T, B, dtype, start = CASES[case]
    cfg = config(dtype)
    qkv, conv_w, g, beta, state, tail = operands(T + B, B, T, dtype, start)
    got_o, got_s = jax.jit(kernel)(qkv, conv_w, g, beta, state, tail)
    assert got_o.shape == (B, T, HV, D) and got_o.dtype == jnp.float32
    assert got_s.shape == (B, HV, D, D) and got_s.dtype == jnp.float32
    q, k, v = heads(cfg, qkv, conv_w, tail)
    want_o, want_s = jax.jit(lambda *a: gdn.gated_delta_chunked(
        *a[:5], 64, a[5]))(q, k, v, g, beta, state)
    exact = dtype == jnp.float32
    assert off(got_o, want_o) < (2e-5 if exact else 2e-3)
    assert off(got_s, want_s) < (2e-5 if exact else 2e-3)
    step_o, step_s = by_steps(q, k, v, g, beta, state)
    for got, want, step in ((got_o, want_o, step_o), (got_s, want_s, step_s)):
        assert off(got, step) < max(1.05 * off(want, step), 2e-5)
        assert off(got, step) < 6e-3


@pytest.mark.parametrize("noise", [0.0, 0.1, 0.5])
def test_keys_of_a_chunk_that_are_alike(noise):
    """Every position nearly the same row (cosine 1.0, over 0.95 and over 0.5
    between a chunk's keys), ``beta`` near 1 and next to no decay: the
    triangular system whose inverse as a plain sum of powers over 64 rows
    passes 1e17 on its way to entries of size 1 (``_unit_lower_inverses``).
    The kernel stays as near the token-by-token recurrence as the XLA form's
    solve."""
    cfg = config()
    qkv, conv_w, g, beta, state, tail = operands(1, 1, 256, jnp.float32)
    same = jax.random.normal(jax.random.PRNGKey(9), (1, 1, WIDTH))
    qkv = same + noise * qkv
    g, beta = jnp.full_like(g, -0.001), jnp.full_like(beta, 0.98)
    got_o, got_s = jax.jit(kernel)(qkv, conv_w, g, beta, state, tail)
    q, k, v = heads(cfg, qkv, conv_w, tail)
    alike = jnp.einsum("td,sd->ts", k[0, 100:, 0], k[0, 100:, 0])
    assert float(jnp.min(alike)) > (0.95 if noise < 0.5 else 0.5)
    want_o, want_s = gdn.gated_delta_chunked(q, k, v, g, beta, 64, state)
    step_o, step_s = by_steps(q, k, v, g, beta, state)
    for got, want, step in ((got_o, want_o, step_o), (got_s, want_s, step_s)):
        assert off(got, step) < max(2 * off(want, step), 2e-6)


# the last real position: none (the last); inside the first tile of 128;
# inside a later tile; a tile's last row; the first position
@pytest.mark.parametrize("last", [None, 37, 200, 255, 0])
@pytest.mark.parametrize("sequences", [1, 2])
def test_attend_delta_on_the_kernels_path_stops_at_last(last, sequences,
                                                        monkeypatch):
    """What the engine's prefill takes of it: the outputs up to ``last``,
    the state after ``last`` whatever follows, the tail at ``last``; with
    ``last`` traced, as the engine traces it."""
    T, cfg = 384, config()
    qkv, conv_w, g, beta, _, _ = operands(11, sequences, T, jnp.float32)
    fn = jax.jit(lambda last, *a: llama.attend_delta(cfg, last, *a))
    at = None if last is None else jnp.int32(last)
    want = fn(at, qkv, g, beta, conv_w)
    on_the_kernel_path(monkeypatch)
    got = jax.jit(lambda last, *a: llama.attend_delta(cfg, last, *a))(
        at, qkv, g, beta, conv_w)
    live = T if last is None else last + 1
    assert off(got[0][:, :live], want[0][:, :live]) < 2e-5
    assert got[1].shape == (sequences, 1, HV, D, D)
    assert off(got[1], want[1]) < 2e-5
    np.testing.assert_array_equal(got[2], want[2])
    if last is not None:  # and it is the state of the prompt cut there
        cut = fn(None, qkv[:, :128 * -(-live // 128)],
                 *(jnp.where((jnp.arange(T) < live)[None, :, None], a, 0.0)[
                     :, :128 * -(-live // 128)] for a in (g, beta)), conv_w)
        assert off(got[1], cut[1]) < 2e-5


def test_a_wider_group_and_more_key_heads():
    """Two key heads of two value heads each, and one value head a key
    head: the column blocks of each group are its own."""
    for hk, hv in ((2, 4), (2, 2)):
        cfg = config(lin_key_heads=hk, lin_value_heads=hv)
        qkv, conv_w, g, beta, state, tail = operands(
            3, 1, 128, jnp.float32, True, hk, hv)
        got_o, got_s = gp.gdn_prefill(qkv, conv_w, g, beta, state, tail,
                                      key_heads=hk, key_dim=D,
                                      interpret=True)
        want_o, want_s = gdn.gated_delta_chunked(
            *heads(cfg, qkv, conv_w, tail), g, beta, 64, state)
        assert off(got_o, want_o) < 2e-5 and off(got_s, want_s) < 2e-5


def test_row_tiles_and_what_the_call_refuses():
    assert gp.pick_rows(6144) == gp.pick_rows(30720) == 512
    assert gp.pick_rows(768) == 256 and gp.pick_rows(384) == 128
    assert gp.pick_rows(64) is None and gp.pick_rows(1000) is None
    args = operands(0, 1, 128, jnp.float32)
    with pytest.raises(ValueError, match="qkv"):
        kernel(args[0][:, :100], args[1], args[2][:, :100],
               args[3][:, :100], *args[4:])
    with pytest.raises(ValueError, match="key heads"):
        gp.gdn_prefill(*args, key_heads=2, key_dim=64, interpret=True)


# --- the rule: which path, why, and what it leaves to jax.grad ------------- #

REFUSED = {
    "backend": (dict(), dict(), "cpu", "backend is 'cpu', not tpu"),
    "rows in another type": (dict(), dict(qkv=jnp.bfloat16), "tpu",
                             "bfloat16"),
    "g in the compute type": (dict(dtype=jnp.bfloat16),
                              dict(qkv=jnp.bfloat16, g=jnp.bfloat16), "tpu",
                              "float32 twice"),
    "a key of 64": (dict(lin_key_dim=64), dict(), "tpu", "key width 64"),
    "a value of 192": (dict(lin_value_dim=192), dict(), "tpu",
                       "value width 192"),
    "value heads no multiple": (dict(lin_key_heads=3, lin_value_heads=4),
                                dict(), "tpu", "4 value heads"),
    "four value heads a key head": (dict(lin_value_heads=4), dict(), "tpu",
                                    "at most 2"),
    "100 positions": (dict(), dict(positions=100), "tpu", "100 positions"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_the_rule_refuses_with_its_reason(what, monkeypatch):
    fields, shapes, backend, why = REFUSED[what]
    cfg = config(**fields)
    T = shapes.get("positions", 256)
    qkv = jnp.zeros((1, T, 8), shapes.get("qkv", cfg.dtype))
    g = jnp.zeros((1, T, 2), shapes.get("g", jnp.float32))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    path, reason = llama.delta_prefill_path(cfg, qkv, g, g.astype(
        jnp.float32))
    assert path == "chunks" and why in reason


def test_the_rule_takes_the_cells_widths_on_a_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = config(jnp.bfloat16, lin_key_heads=16, lin_value_heads=32)
    for pages in (3, 9, 14, 15, 16):
        qkv = jax.ShapeDtypeStruct((1, 2048 * pages, 8192), jnp.bfloat16)
        g = jax.ShapeDtypeStruct((1, 2048 * pages, 32), jnp.float32)
        assert llama.delta_prefill_path(cfg, qkv, g, g) == (
            "kernel", "tpu backend")


def on_the_kernel_path(monkeypatch):
    """Steer this CPU process onto the kernel's path (it is interpreted
    there), as ``test_flash_prefill.py`` steers ``attend_tiles``."""
    monkeypatch.setattr(llama, "delta_prefill_path",
                        lambda *a: ("kernel", "steered by a test"))


def test_a_traced_layer_is_counted_with_its_reason(monkeypatch):
    """Where a program is traced: the gauge's two series of kind ``delta``
    (both set, the one not taken at what it has counted), the record with
    its reason."""
    cfg = config()
    qkv, conv_w, g, beta, _, _ = operands(2, 1, 128, jnp.float32)
    before = attend_gauge()
    jax.jit(lambda *a: llama.attend_delta(cfg, None, *a))(qkv, g, beta,
                                                          conv_w)
    after = attend_gauge()
    assert after[("delta", "chunks")] == before.get(("delta", "chunks"),
                                                    0) + 1
    assert after[("delta", "kernel")] == before.get(("delta", "kernel"), 0)
    assert ("delta", "tiles") not in after
    on_the_kernel_path(monkeypatch)
    jax.jit(lambda *a: llama.attend_delta(cfg, None, *a))(qkv, g, beta,
                                                          conv_w)
    assert attend_gauge()[("delta", "kernel")] == after[
        ("delta", "kernel")] + 1
    mine = {r["path"]: r for r in llama.prefill_attend_paths()
            if r["kind"] == "delta" and r["q_shape"] == [1, 128, WIDTH]}
    assert "'cpu'" in mine["chunks"]["reason"]
    assert mine["kernel"]["reason"] == "steered by a test"


def test_grad_through_the_kernels_path_is_the_chunked_forms(monkeypatch):
    """The value is the kernel's; ``jax.grad`` runs the XLA path's
    transpose from the operands, and both are what that path gives."""
    cfg = config()
    qkv, conv_w, g, beta, _, _ = operands(5, 1, 128, jnp.float32)

    def loss(qkv, g, beta, conv_w):
        o, state, tail = llama.attend_delta(cfg, jnp.int32(100), qkv, g,
                                            beta, conv_w)
        return jnp.sum(jnp.sin(o[:, :101])) + jnp.sum(state * state) \
            + jnp.sum(tail)

    n = (0, 1, 2, 3)
    want = jax.jit(jax.value_and_grad(loss, argnums=n))(qkv, g, beta, conv_w)
    on_the_kernel_path(monkeypatch)
    got = jax.jit(jax.value_and_grad(loss, argnums=n))(qkv, g, beta, conv_w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for mine, theirs in zip(got[1], want[1]):
        np.testing.assert_allclose(mine, theirs, atol=2e-4, rtol=2e-4)
