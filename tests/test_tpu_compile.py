"""Kernels of the measured path compiled at real widths for a v5e chip that
is described and not attached (the TPU's compiler is installed here): what
interpret mode cannot refuse, at no chip time. Nothing runs, so nothing here
is a result or a time. All such compiles live in THIS file: the process that
describes the topology holds the TPU library until it exits."""

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler for it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """``flash_attention`` asks the default backend which path to take, and
    here that is the CPU: steer it to the kernel. The persistent compile
    cache cannot read back what was compiled for an absent chip: off."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (batch, sequence, query heads, key/value heads, the dK/dV call asks for a
# VMEM limit): the cells' attention, and the longest sequence whose buffers
# (15.0 MiB) still fit the default limit, with the kernel's own tiles
SHAPES = {
    "train-nemotron3nano-1chip": (2, 8192, 32, 2, True),
    "train-olmoe-1chip": (4, 4096, 16, 16, False),
    "train-mistral7b-1chip": (8, 2048, 32, 8, False),
    "4608 positions": (1, 4608, 8, 8, False),
    "5120 positions": (1, 5120, 8, 8, True),
}


@pytest.mark.parametrize("cell", list(SHAPES))
def test_flash_kernels_compile_at_the_cells_shapes(cell, one_chip,
                                                   as_on_the_chip):
    """Forward, dQ and dK/dV at head width 128. At 8,192 positions the
    dK/dV call's buffers are 25.5 MiB of VMEM, over the compiler's default
    16 MiB: it asks for its limit. The calls that fit the default (every
    cell the benchmark had before) ask for nothing, as they always did."""
    from ray_tpu.ops.flash_attention import flash_attention

    b, t, hq, hkv, asks = SHAPES[cell]

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    def arg(heads):
        return jax.ShapeDtypeStruct((b, t, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(hq), arg(hkv), arg(hkv))
    # a limit that is asked for is the call's scoped_memory_configs
    assert ("scoped_memory_configs" in lowered.as_text()) == asks
    compiled = lowered.compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        >= 3


@pytest.mark.parametrize("tokens", [1, 2048])
def test_grouped_products_over_the_stack_read_it_where_it_lies(
        tokens, one_chip, as_on_the_chip):
    """``routed_mlp`` at LongCat's share (4 layers of 16 held experts, 6144
    x 2048, a 768-wide router, top-12) under a scan that traces the layer's
    number, at a decode call's one token and at a short prefill's 2,048:
    every expert product is XLA's grouped kernel (of a decode call's 12
    rows, not filled up to whole sublanes, the compiler makes a dense
    product over every group), and nothing of a layer's experts' shape is
    made (a copy of 403 MB a matrix and layer)."""
    import re

    from ray_tpu.ops.moe import routed_mlp

    L, count, d, f, wide = 4, 16, 6144, 2048, 768

    def layers(h, router, bias, w_gate, w_up, w_down):
        def body(x, i):
            y, _ = routed_mlp(x, router[i], w_gate, w_up, w_down, top_k=12,
                              scale=6.0, choice_bias=bias[i],
                              held=(0, count), zero_experts=256, layer=i)
            return x + y.astype(x.dtype), None

        return jax.lax.scan(body, h, jnp.arange(L, dtype=jnp.int32))[0]

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(layers).lower(
        arg(tokens, d), arg(L, d, wide, dtype=jnp.float32),
        arg(L, wide, dtype=jnp.float32), arg(L, count, d, f),
        arg(L, count, d, f), arg(L, count, f, d)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) >= 3
    assert not re.findall(r"= f32\[(?:16|64),\d+,\d+\]\S* convolution\(",
                          text)
    made = re.findall(r"%(\S+) = bf16\[16,(?:6144,2048|2048,6144)\]\S* "
                      r"(?!bitcast|parameter|get-tuple-element)(\w[\w-]*)\(",
                      text)
    assert not made, made
    if tokens == 1:  # one such copy is 403 MB
        assert compiled.memory_analysis().temp_size_in_bytes < 100e6


# (positions, query heads, key/value heads, the score's width, a head's own
# part of it, window): the serving cells' prefill attentions at their
# longest, and at a page count whose keys are filled up to whole steps
PREFILL = {
    "longcat 16 pages": (8192, 64, 64, 192, 128, 0),
    "longcat 5 pages": (2560, 64, 64, 192, 128, 0),
    "smallthinker full 16 pages": (16384, 28, 4, 128, 128, 0),
    "smallthinker window 16 pages": (16384, 28, 4, 128, 128, 4096),
    "smallthinker window 5 pages": (5120, 28, 4, 128, 128, 4096),
}


@pytest.mark.parametrize("what", list(PREFILL))
def test_prefill_kernel_compiles_at_the_cells_shapes(what, one_chip,
                                                     as_on_the_chip):
    """``attend_tiles`` on the kernel's path (``ops/flash_prefill.py``) at
    published widths: a 128 + 64 wide score with ONE shared slice and a
    128-wide value, 28 heads on 4, the band; a head's whole keys and values
    in VMEM (16 MB at 16,384 positions with their second buffers), so the
    call asks for its limit. One Mosaic call, nothing of the tile loop."""
    from ray_tpu.models import llama

    t, hq, hkv, d, dk, window = PREFILL[what]

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    shared = arg(1, t, d - dk) if d > dk else None
    lowered = jax.jit(lambda q, k, v, shared: llama.attend_tiles(
        q, k, v, jnp.bfloat16, window=window, shared=shared)).lower(
            arg(1, t, hq, d), arg(1, t, hkv, dk), arg(1, t, hkv, 128), shared)
    assert "scoped_memory_configs" in lowered.as_text()
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%flash_prefill" in text and "while(" not in text


@pytest.mark.parametrize("pages", [4, 16])
def test_selection_kernels_compile_at_the_keye_cells_shapes(pages, one_chip,
                                                            as_on_the_chip):
    """``ops/sparse_prefill.py`` at the indexed block's published widths
    (16 index heads of 64 on one key head, 32 query heads on 4 of 128,
    ``topk`` 2,048), at the cell's shortest and longest page tables: the
    selection call keeps a 128-query block's whole row of order keys in
    VMEM (16 MB at 32,768 keys) and asks for its limit, and the attention
    under the mask keeps a group's whole keys and values there."""
    from ray_tpu.ops import sparse_prefill as sp

    t = pages * 2048

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    select = jax.jit(lambda qi, ki, w: sp.index_select(qi, ki, w, 2048)).lower(
        arg(1, t, 16, 64), arg(1, t, 64), arg(1, t, 16, dtype=jnp.float32))
    assert "scoped_memory_configs" in select.as_text()
    assert select.compile().as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    mask = arg(1, t // 128, t // 512, 128, 512, dtype=jnp.int8)
    attend = jax.jit(sp.masked_flash).lower(
        arg(1, t, 32, 128), arg(1, t, 4, 128), arg(1, t, 4, 128), mask)
    assert attend.compile().as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
