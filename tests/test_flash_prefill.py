"""The decode engine's forward-only prefill kernel (``ops/flash_prefill.py``)
against its oracle, the XLA tile loop (``models/llama.py _tile_loop``), at
small sizes with the kernel interpreted: the CPU, so values and paths, never
a time. What the compiler of the chip says of the real shapes is in
``test_tpu_compile.py``."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import flash_prefill as fp

BLK = 8  # a query block; T is a whole number of them


def operands(seq, heads, kv_heads, width, own, value, dtype=jnp.float32,
             seed=0):
    """``q, k, v, shared``: ``own`` of the score's ``width`` are a head's
    own keys, the rest ONE slice all heads share (None where ``own`` is the
    whole width)."""
    k = jax.random.split(jax.random.PRNGKey(seed + seq + heads), 4)
    q = jax.random.normal(k[0], (1, seq, heads, width)).astype(dtype)
    kk = jax.random.normal(k[1], (1, seq, kv_heads, own)).astype(dtype)
    vv = jax.random.normal(k[2], (1, seq, kv_heads, value)).astype(dtype)
    shared = None if own == width else jax.random.normal(
        k[3], (1, seq, width - own)).astype(dtype)
    return q, kk, vv, shared


def tile_loop(q, k, v, shared, window, monkeypatch, block=BLK):
    monkeypatch.setattr(llama, "LATENT_QUERY_BLOCK", block)
    return jax.jit(lambda *a: llama._tile_loop(*a, window, q.dtype))(
        q, k, v, shared)


def kernel(q, k, v, shared, window, blocks=(BLK, 2 * BLK)):
    return jax.jit(lambda *a: fp.flash_prefill(
        *a[:3], shared=a[3], window=window, blocks=blocks,
        interpret=True))(q, k, v, shared)


# (sequence, query heads, key/value heads, score width, a head's own part of
# it, value width): a split score with one shared slice and a narrower value
# (latent), GQA 7:1 (full and window layers), one head on one
SHAPES = {
    "latent": (3 * BLK, 4, 4, 24, 16, 16),
    "gqa 7:1": (3 * BLK, 7, 1, 16, 16, 16),
    "one block": (BLK, 2, 2, 16, 16, 16),
}
# no window; smaller than T and no multiple of the block; one block; equal
# to T; larger than T
WINDOWS = [0, 5, 8, 11, 24, 100]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernel_is_the_tile_loop(shape, window, monkeypatch):
    q, k, v, shared = operands(*SHAPES[shape])
    want = tile_loop(q, k, v, shared, window, monkeypatch)
    got = kernel(q, k, v, shared, window)
    assert got.shape == want.shape == q.shape[:3] + v.shape[3:]
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("blocks", [(8, 8), (8, 32), (24, 8), (4, 12)])
@pytest.mark.parametrize("window", [0, 11])
def test_blocks_change_nothing(blocks, window, monkeypatch):
    """Keys a loop step that are fewer, more (the keys filled up past T) and
    no multiple of the queries a grid step."""
    q, k, v, shared = operands(*SHAPES["latent"], seed=1)
    want = tile_loop(q, k, v, shared, window, monkeypatch)
    np.testing.assert_allclose(kernel(q, k, v, shared, window, blocks), want,
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("window", [0, 11])
def test_operands_go_in_as_they_come_and_probabilities_are_rounded(
        window, monkeypatch):
    """bfloat16 operands: the products take them as they are and the
    probabilities are rounded to bfloat16 for the second product, as the
    tile loop does; a kernel that widened its operands first would differ
    from it by more than a float32 sum's order."""
    q, k, v, shared = operands(*SHAPES["latent"], dtype=jnp.bfloat16, seed=2)
    want = tile_loop(q, k, v, shared, window, monkeypatch)
    got = kernel(q, k, v, shared, window, (BLK, BLK))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), atol=1e-2)
    assert float(jnp.mean(got == want)) > 0.98


@pytest.mark.parametrize("blk", [4, 8, 512])
@pytest.mark.parametrize("window", [1, 3, 8, 11, 4096, 5000])
def test_the_band_starts_at_the_tile_loops_first_block(blk, window):
    """``first_key_block`` against the tile loop's ``near``; with keys two
    query blocks a step, the step that holds that block."""
    for i in range(40):
        near = [j for j in range(i + 1)
                if j >= (i * blk - window + 1) // blk]
        assert int(fp.first_key_block(i, blk, blk, window)) == near[0]
        assert int(fp.first_key_block(i, blk, 2 * blk, window)) \
            == near[0] // 2
    assert fp.first_key_block(7, blk, blk, 0) == 0


def test_blocks_are_picked_from_the_positions():
    assert fp.pick_blocks(8192) == fp.pick_blocks(2560) == (512, 1024)
    assert fp.pick_blocks(16384 + 256) == (256, 512)
    assert fp.pick_blocks(384) == (128, 256)
    assert fp.pick_blocks(100) is None
    with pytest.raises(ValueError, match="no multiple"):
        kernel(*operands(10, 2, 2, 16, 16, 16), 0)
    with pytest.raises(ValueError, match="shared"):
        kernel(*operands(8, 2, 2, 24, 16, 16)[:3], None, 0)


# --- the path: which, why, and what it leaves to jax.grad ------------------ #


def test_the_path_is_read_from_backend_and_shapes(monkeypatch):
    q, k, v, shared = operands(256, 2, 1, 192, 128, 128, jnp.bfloat16)
    cd = jnp.bfloat16
    assert llama.prefill_attend_path(q, k, v, cd, shared) == (
        "tiles", "backend is 'cpu', not tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert llama.prefill_attend_path(q, k, v, cd, shared) == (
        "kernel", "tpu backend")
    assert llama.prefill_attend_path(q, k, v, cd) == ("kernel", "tpu backend")
    path, why = llama.prefill_attend_path(
        *(a[:, :100] for a in (q, k, v)), cd, shared[:, :100])
    assert path == "tiles" and "100 positions" in why
    path, why = llama.prefill_attend_path(q, k, v, jnp.float32, shared)
    assert path == "tiles" and "bfloat16" in why and "float32" in why
    path, why = llama.prefill_attend_path(q, k[..., :64], v, cd, shared)
    assert path == "tiles" and "width 64" in why


def attend_gauge():
    from ray_tpu.util.metrics import registry

    return {tuple(v for _, v in sorted(tags)): n for tags, n in
            registry().local_values(
                "ray_tpu_serve_engine_prefill_attend").items()}


def test_a_traced_attention_is_counted_with_its_reason(monkeypatch):
    """Where a program is traced: the gauge by kind and path (both paths of
    the kind set), the record with its reason."""
    q, k, v, _ = operands(16, 2, 1, 16, 16, 16, seed=5)
    before = attend_gauge()
    jax.jit(lambda *a: llama.attend_tiles(*a, jnp.float32, window=3))(q, k, v)
    after = attend_gauge()
    assert after[("window", "tiles")] == before.get(("window", "tiles"),
                                                    0) + 1
    assert after[("window", "kernel")] == before.get(("window", "kernel"), 0)
    rec = [r for r in llama.prefill_attend_paths()
           if r["q_shape"] == [1, 16, 2, 16] and r["window"] == 3]
    assert len(rec) == 1 and rec[0]["kind"] == "window"
    assert rec[0]["path"] == "tiles" and "'cpu'" in rec[0]["reason"]


def on_the_kernel_path(monkeypatch):
    """Steer this CPU process onto the kernel's path (it is interpreted
    there), as ``test_tpu_compile.py`` steers ``flash_attention``."""
    monkeypatch.setattr(llama, "prefill_attend_path",
                        lambda *a, **kw: ("kernel", "steered by a test"))


@pytest.mark.parametrize("what", ["latent", "full", "window"])
def test_grad_through_the_kernels_path_is_the_tile_loops(what, monkeypatch):
    """``attend_latent_expanded`` and ``attend_window_tiles`` on the
    kernel's path: the value is the kernel's, ``jax.grad`` runs the tile
    loop's transpose, and both are what the tile loop's path gives."""
    # the four fields the two functions read of a config
    cfg = types.SimpleNamespace(kv_lora_rank=16, qk_nope_head_dim=16,
                                window=40, dtype=jnp.float32)
    seq = 128
    if what == "latent":
        key = jax.random.split(jax.random.PRNGKey(3), 3)
        args = (jax.random.normal(key[0], (1, seq, 2, 24)),
                jax.random.normal(key[1], (1, seq, 24)),
                jax.random.normal(key[2], (16, 2 * 32)) / 4)
        attend = lambda *a: llama.attend_latent_expanded(cfg, *a)  # noqa
    else:
        args = operands(seq, 2, 1, 16, 16, 16, seed=4)[:3]
        attend = lambda *a: llama.attend_window_tiles(  # noqa: E731
            cfg, "W" if what == "window" else "F", *a)

    def loss(*a):
        return jnp.sum(jnp.sin(attend(*a)))

    n = tuple(range(len(args)))
    want = jax.jit(jax.value_and_grad(loss, argnums=n))(*args)
    on_the_kernel_path(monkeypatch)
    got = jax.jit(jax.value_and_grad(loss, argnums=n))(*args)
    taken = [r for r in llama.prefill_attend_paths()
             if r["kind"] == what and r["reason"] == "steered by a test"]
    assert taken and taken[0]["path"] == "kernel"
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5)
