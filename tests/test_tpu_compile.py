"""Kernels of the measured path compiled at real widths for a v5e chip that
is described and not attached (the TPU's compiler is installed here): what
interpret mode cannot refuse, at no chip time. Nothing runs, so nothing here
is a result or a time. All such compiles live in THIS file: the process that
describes the topology holds the TPU library until it exits."""

import json
import math
import os
import re

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


# (batch, sequence, query heads, key/value heads, a call asks for a VMEM
# limit): the cells' attention and two lengths 1,024 does not divide or
# divides five times. What asks follows the loop step (pick_blocks): 512 x
# 2,048 at 8,192 positions (every call asks: a backward step's five tiles are
# 20 MiB), 512 x 1,024 at 4,096 (none: dK/dV's buffers and tiles are 16 MiB
# to the byte) and at 5,120 (dQ and dK/dV), 512 x 512 at 2,048 and 4,608
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
    """Forward, dQ and dK/dV at head width 128. A call whose resident blocks
    and float32 tiles pass the compiler's default 16 MiB of VMEM asks for
    its limit; the calls that fit the default ask for nothing."""
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
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    # the yardstick tells the three apart by result types, in the compiled
    # program's own text: each of its patterns finds ONE of the calls
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "layer_metrics", "flash_roofline.json")) as f:
        kernels = json.load(f)["args"]["kernels"]
    found = {name: [c.split(" = ")[0] for c in calls
                    if re.search(spec["pattern"], c)]
             for name, spec in kernels.items()}
    assert {name: len(hits) for name, hits in found.items()} == {
        "flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}, found
    assert all(name in hits[0] for name, hits in found.items()), found


def test_flash_kernels_compile_at_head_width_256(one_chip, as_on_the_chip):
    """``train-glm47flash-1chip``'s attention, [2, 8192, 20, 256] on 20 KV
    heads: a head group's whole K and V are 17.5 MiB of VMEM in the forward
    call (18.5 in dQ; a head's whole q and do 16.5 in dK/dV), so ALL THREE
    ask for their limit whatever the loop step."""
    from ray_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    arg = jax.ShapeDtypeStruct((2, 8192, 20, 256), jnp.bfloat16,
                               sharding=one_chip)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(arg, arg, arg)
    assert lowered.as_text().count("scoped_memory_configs") == 3
    assert lowered.compile().as_text().count(
        'custom_call_target="tpu_custom_call"') == 3


def _whole_step(cell, one_chip):
    """``(compiled, bytes)``: a train cell's WHOLE step that keeps nothing
    (batch 2 x 8192) compiled for the described chip, and what it needs of
    the device by the compiler's account (``spmd.program_bytes``)."""
    from benchmarks.lib import spec
    from ray_tpu.train.spmd import (build_train_mesh, make_spmd_train_step,
                                    program_bytes)

    cfg = spec.program_config(spec.cell_bundle(cell)["config"])
    init, step, *_ = make_spmd_train_step(
        cfg, build_train_mesh("", list(one_chip.device_set)), keep=())
    state = jax.eval_shape(init._fn, jax.random.PRNGKey(0))
    compiled = step._fn.lower(
        state, jax.ShapeDtypeStruct((2, 8193), jnp.int32)).compile()
    m = compiled.memory_analysis()
    return compiled, program_bytes({
        "peak": getattr(m, "peak_memory_in_bytes", 0),
        "argument": m.argument_size_in_bytes,
        "output": m.output_size_in_bytes, "alias": m.alias_size_in_bytes,
        "temp": m.temp_size_in_bytes,
        "code": m.generated_code_size_in_bytes})


def test_the_glm47flash_cells_whole_step_fits_the_chip(one_chip,
                                                       as_on_the_chip):
    """The cell's WHOLE train step (706.5M parameters, batch 2 x 8192, every
    KEEP_GROUPS group recomputed) compiled for a described v5e: its memory
    account stays under the chip's 16 GB (15.75 GiB usable), the latent
    blocks' and the module's attention are the flash kernels forward and
    backward (six latent attentions: a forward, its remat and dQ / dK/dV
    each), and no tile loop of prefill's is in it."""
    compiled, need = _whole_step("train-glm47flash-1chip", one_chip)
    assert 12 * 2 ** 30 < need < 15.75 * 2 ** 30, need
    text = compiled.as_text()
    assert len(re.findall(r"%flash_fwd[.\d]* = ", text)) == 12
    assert len(re.findall(r"%flash_dq[.\d]* = ", text)) == 6
    assert len(re.findall(r"%flash_dkv[.\d]* = ", text)) == 6
    assert "flash_prefill" not in text


def _scanned_experts(L, act):
    """``_expert_ffn`` of every layer of a stack, under a scan that traces
    the layer's number as the engine's does."""
    from ray_tpu.ops.moe import _expert_ffn

    def layers(xs, counts, w_gate, w_up, w_down):
        def body(x, i):
            return _expert_ffn(x, w_gate, w_up, w_down, counts[i], i,
                               act), None

        return jax.lax.scan(body, xs, jnp.arange(L, dtype=jnp.int32))[0]

    return layers


# (rows a call, layers, experts a layer, d, f, gate function): the routed
# serving cells' prefill calls at their longest and shortest, and a block of
# the held path's places (Keye: 131,072 of which some 32,768 have a group)
EXPERTS = {
    "smallthinker 16 pages": (98304, 8, 64, 2560, 768, "reglu"),
    "smallthinker 5 pages": (30720, 8, 64, 2560, 768, "reglu"),
    "keye 16 pages, a block": (131072, 8, 16, 2048, 768, "swiglu"),
    # experts 4,096 x 4,096 go in column blocks of 1,024 (pick_columns): a
    # chunk of the held path's places at 16 pages (ops/moe.py held_chunk)
    "commandaplus 16 pages, a chunk": (20480, 4, 16, 4096, 4096, "swiglu"),
}


@pytest.mark.parametrize("what", list(EXPERTS))
def test_grouped_ffn_compiles_at_the_cells_shapes(what, one_chip,
                                                  as_on_the_chip):
    """``_expert_ffn`` on the kernel's path (``ops/grouped_ffn.py``) at
    published widths, the stack read by the scan's traced layer number: ONE
    Mosaic call that asks for its VMEM (an expert's three matrices twice
    buffered), none of XLA's grouped products, and no float32 array of the
    rows' length (the parent's three products and their conversions)."""
    import re

    rows, L, count, d, f, act = EXPERTS[what]

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(_scanned_experts(L, act)).lower(
        arg(rows, d), arg(L, count, dtype=jnp.int32), arg(L, count, d, f),
        arg(L, count, d, f), arg(L, count, f, d))
    assert "scoped_memory_configs" in lowered.as_text()
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%moe_ffn" in text and "ragged-dot" not in text
    assert not re.findall(rf"f32\[{rows},\d+\]", text)


@pytest.mark.parametrize("rows", [6, 16, 200])
def test_a_decode_calls_rows_stay_on_xlas_grouped_products(rows, one_chip,
                                                          as_on_the_chip):
    """Under a row tile of rows (a decode call's six, a batch's few) the
    same stack goes through ``ragged_dot`` as it did (whose metadata the
    compiler makes in a Mosaic call of its own): not through ``moe_ffn``."""
    _, L, count, d, f, act = EXPERTS["smallthinker 16 pages"]

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(_scanned_experts(L, act)).lower(
        arg(rows, d), arg(L, count, dtype=jnp.int32), arg(L, count, d, f),
        arg(L, count, d, f), arg(L, count, f, d)).compile().as_text()
    assert "moe_ffn" not in text
    assert text.count("ragged-dot") >= 3


@pytest.mark.parametrize("tokens", [1, 2048])
def test_grouped_products_over_the_stack_read_it_where_it_lies(
        tokens, one_chip, as_on_the_chip):
    """``routed_mlp`` at LongCat's share (4 layers of 16 held experts, 6144
    x 2048, a 768-wide router, top-12) under a scan that traces the layer's
    number, at a decode call's one token and at a short prefill's 2,048:
    a decode call's expert products are XLA's grouped kernel (of its 12
    rows, not filled up to whole sublanes, the compiler makes a dense
    product over every group), a prefill's blocks of 2,048 places the
    Pallas call (an expert's matrices, 75 MB, go by in column blocks), and
    nothing of a layer's experts' shape is made (a copy of 403 MB a matrix
    and layer)."""
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
    products = len(re.findall(r"%ragged-dot-none[.\d]* = ", text))
    if tokens == 1:
        assert products >= 3 and "moe_ffn" not in text
    else:
        assert products == 0 and "%moe_ffn" in text
    assert not re.findall(r"= f32\[(?:16|64),\d+,\d+\]\S* convolution\(",
                          text)
    made = re.findall(r"%(\S+) = bf16\[16,(?:6144,2048|2048,6144)\]\S* "
                      r"(?!bitcast|parameter|get-tuple-element)(\w[\w-]*)\(",
                      text)
    assert not made, made
    if tokens == 1:  # one such copy is 403 MB
        assert compiled.memory_analysis().temp_size_in_bytes < 100e6


def _lowered_for(one_chip, fn, *args):
    """``fn`` lowered for the described chip at ``args``' shapes."""
    return fn.lower(*jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)).as_text()


def _train_step_text(one_chip, cfg):
    from ray_tpu.train.spmd import build_train_mesh, make_spmd_train_step

    init, step, *_ = make_spmd_train_step(
        cfg, build_train_mesh("", list(one_chip.device_set)))
    state = jax.eval_shape(init._fn, jax.random.PRNGKey(0))
    return step._fn.lower(
        state, jax.ShapeDtypeStruct((4, 33), jnp.int32)).as_text()


def _engine_text(one_chip, cfg, program, n_pages):
    """``program`` (``"prefill"`` / ``"decode"``) of a decode engine at
    ``n_pages`` pages of 5, lowered as the engine jits it."""
    import numpy as np

    from ray_tpu.models import llama

    shapes = jax.eval_shape(lambda key: llama.serving_params(
        cfg, llama.init_params(cfg, key)), jax.random.PRNGKey(0))
    engine = llama.LlamaDecodeEngine(
        cfg, jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shapes),
        n_pages=24, page_size=5)
    reach = min(n_pages, engine.window_pages)
    window = [np.zeros(reach, np.int32)] if engine.n_slots else []
    if program == "prefill":
        fn, args = engine._prefill_fn, [
            np.zeros((1, 5 * n_pages), np.int32),
            np.zeros(n_pages, np.int32), np.int32(0), *window]
    else:
        fn, args = engine._decode_fn, [
            np.zeros(1, np.int32), np.int32(0), np.zeros(n_pages, np.int32),
            *window, *([np.int32(0)] if window else [])]
    return _lowered_for(one_chip, getattr(fn, "_fn", fn), engine.params,
                        *engine.stores, *args)


def _whole_lanes_smallthinker():
    import test_smallthinker as st

    return st.program_cfg(hidden_size=128, moe_ffn_hidden_size=128,
                          head_dim=32, num_hidden_layers=4,
                          max_position_embeddings=120)


# the programs the grouped kernel must NOT reach, each lowered for the chip
# with every path rule asked as on a TPU: the routed and the patterned train
# step (one layer's experts, out of the scan), a dense engine's prefill, a
# routed engine's decode program at widths the kernel would take
UNTOUCHED = {
    "olmoe train step": lambda chip: _train_step_text(
        chip, __import__("test_olmoe").program_cfg()),
    "nemotron train step": lambda chip: _train_step_text(
        chip, __import__("nemotron_h_small").program_cfg()),
    "dense prefill": lambda chip: _engine_text(
        chip, __import__("ray_tpu.models.llama", fromlist=["x"])
        .LlamaConfig.debug(), "prefill", 4),
    "routed decode": lambda chip: _engine_text(
        chip, _whole_lanes_smallthinker(), "decode", 20),
}


@pytest.mark.parametrize("program", list(UNTOUCHED))
def test_programs_that_must_not_change_hold_no_kernel_of_the_experts(
        program, one_chip, as_on_the_chip):
    """No Mosaic call that the parent did not have (at these sizes it had
    none: sequences under the flash kernels' blocks), and the routed ones
    still hold XLA's grouped products. PERF.md (PR 43) has the hashes of the
    cells' own programs on both trees."""
    text = UNTOUCHED[program](one_chip)
    assert "tpu_custom_call" not in text and "moe_ffn" not in text
    assert "gdn_prefill" not in text and "held_sum" not in text
    assert ("ragged_dot" in text) == (program != "dense prefill")


def test_a_routed_prefill_of_the_same_engine_holds_the_kernel(
        one_chip, as_on_the_chip):
    """What the check above can see: 20 pages x 5 positions x 3 choices are
    300 rows, over a row tile, and every layer's experts are ``moe_ffn``."""
    text = _engine_text(one_chip, _whole_lanes_smallthinker(), "prefill", 20)
    assert 'kernel_name = "moe_ffn"' in text and "ragged_dot" not in text
# (positions, query heads, key/value heads, the score's width, a head's own
# part of it, window): the serving cells' prefill attentions at their
# longest, and at a page count whose keys are filled up to whole steps
PREFILL = {
    "longcat 16 pages": (8192, 64, 64, 192, 128, 0),
    "longcat 5 pages": (2560, 64, 64, 192, 128, 0),
    "smallthinker full 16 pages": (16384, 28, 4, 128, 128, 0),
    "smallthinker window 16 pages": (16384, 28, 4, 128, 128, 4096),
    "smallthinker window 5 pages": (5120, 28, 4, 128, 128, 4096),
    # gated attention at head width 256, values as wide: a head's whole keys
    # and values are 16 MB each at 32,768 positions, twice
    "qwen3next gated 3 pages": (6144, 16, 2, 256, 256, 0, 256),
    "qwen3next gated 16 pages": (32768, 16, 2, 256, 256, 0, 256),
    # 32 heads of 128 + 64 that return 128, at the cell's two ends
    "xing4 2 pages": (2048, 32, 32, 192, 128, 0),
    "xing4 16 pages": (16384, 32, 32, 192, 128, 0),
    # 128 query heads on 8: a KV head's keys stay while 16 query heads go by
    "commandaplus full 16 pages": (16384, 128, 8, 128, 128, 0),
    "commandaplus window 16 pages": (16384, 128, 8, 128, 128, 4096),
    "commandaplus window 5 pages": (5120, 128, 8, 128, 128, 4096),
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

    t, hq, hkv, d, dk, window, *dv = PREFILL[what]
    dv = dv[0] if dv else 128

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    shared = arg(1, t, d - dk) if d > dk else None
    lowered = jax.jit(lambda q, k, v, shared: llama.attend_tiles(
        q, k, v, jnp.bfloat16, window=window, shared=shared)).lower(
            arg(1, t, hq, d), arg(1, t, hkv, dk), arg(1, t, hkv, dv), shared)
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


@pytest.mark.parametrize("pages", [3, 9, 14, 15, 16])
def test_delta_rule_kernel_compiles_at_the_qwen3next_cells_shapes(
        pages, one_chip, as_on_the_chip):
    """``ops/gdn_prefill.py`` at the published widths (16 key heads and 32
    value heads of 128 in rows of 8,192 columns, 4 taps) at the cell's
    shortest prompt, at 9 pages and at its longest three, 14 and 15 among
    them: page counts that 8,192 positions do not divide, which the XLA
    path took in ONE piece. One Mosaic call whatever the pages, under the
    VMEM limit it asks for (its blocks and scratch, some 15 MB)."""
    from ray_tpu.ops.gdn_prefill import gdn_prefill

    t, hk, hv, d, taps = pages * 2048, 16, 32, 128, 4
    width = 2 * hk * d + hv * d

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(lambda *a: gdn_prefill(
        *a, key_heads=hk, key_dim=d)).lower(
            arg(1, t, width, dtype=jnp.bfloat16), arg(taps, width),
            arg(1, t, hv), arg(1, t, hv), arg(1, hv, d, d),
            arg(1, taps - 1, width, dtype=jnp.bfloat16))
    assert "scoped_memory_configs" in lowered.as_text()
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%gdn_prefill" in text and "while(" not in text


def _cell_program(cell, program, pages, one_chip):
    """``(lowered, stores)``: a serving cell's ``program`` (``"prefill"`` /
    ``"decode"``) at ``pages`` pages, at the cell's published widths, its
    deployment's stores and page size, from shapes alone and lowered for
    the described chip as the engine jits it."""
    from functools import partial

    from benchmarks.lib import spec
    from ray_tpu.models import llama

    file = spec.cell_bundle(cell)["config"]
    cfg, dep = spec.program_config(file), file["deployment"]
    ps, n_pages = dep["page_size"], dep["n_pages"]

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: llama.serving_params(cfg, llama.init_params(cfg, key)),
        jax.random.PRNGKey(0)))
    layout = llama.served_stores(cfg)
    # a stack with stores by slot: the engine's rule for how many, and the
    # slots of the last pages behind the other arguments
    k = n_slots = 0
    if any(s.table == "slot" for s in layout):
        k = cfg.window_pages(ps)
        n_slots = min(n_pages, -(-n_pages // -(-cfg.max_seq_len // ps))
                      * (k + 2))
    stores = [jax.ShapeDtypeStruct(s.shape(n_pages, n_slots, ps), jnp.float32,
                                   sharding=one_chip) for s in layout]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    slots = (i32(min(pages, k)),) if n_slots else ()
    if program == "prefill":
        fn, args = llama.prefill_with_cache, (i32(1, pages * ps), i32(pages),
                                              i32(), *slots)
    else:
        fn, args = llama.decode_step_with_cache, (
            i32(1), i32(), i32(pages), *slots, *((i32(),) if slots else ()))
    return jax.jit(partial(fn, cfg), donate_argnums=tuple(
        range(1, 1 + len(layout)))).lower(params, *stores, *args), stores


@pytest.mark.parametrize("program,pages", [
    ("prefill", 3), ("prefill", 15), ("prefill", 16), ("decode", 4),
    ("decode", 16)])
def test_qwen3next_cells_programs_compile_and_fit_the_chip(
        program, pages, one_chip, as_on_the_chip):
    """The delta-rule family's WHOLE prefill and decode programs at the
    cell's published widths, its stores and its shortest and longest page
    tables, from shapes alone: beside 9.7 GB of weights and stores the
    program must stay inside the chip. Prefill holds the delta rule's
    kernel (one a ``D`` layer of the scanned period: no triangular solve,
    no ``[.., 64, 64]`` float32 arrays of a whole prompt), the flash kernel
    at head width 256 and the experts' grouped kernel, ONE a routed block
    inside the held path's loop over chunks (a period's four; the block
    form held two a routed block and the rows of a whole block of places in
    and out); its temporaries are 0.89 / 2.96 / 3.12 GB at 3 / 15 / 16 pages
    (with the block form 1.11 / 3.95 / 4.19; PERF.md, PR 47). Decode holds
    none of the three and no loop over places: a call's ten places are one
    straight block."""
    import re

    lowered, stores = _cell_program("serve-qwen3next-prefill-open", program,
                                    pages, one_chip)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes
    assert 9.7e9 < held < 9.8e9
    assert memory.alias_size_in_bytes >= sum(
        4 * math.prod(a.shape) for a in stores)  # every store in place
    assert held + memory.temp_size_in_bytes < 15.0e9
    text = compiled.as_text()
    prefill = program == "prefill"
    assert ("%flash_prefill" in text) == prefill
    assert ("moe_ffn" in text) == prefill
    assert ("%gdn_prefill" in text) == prefill
    # the held rows' sum (PR 49): one kernel beside each grouped kernel in
    # the loop's body, and no float32 [chunk, d] product or scatter-add
    assert ("%held_sum" in text) == prefill
    if prefill:
        # the period DDDA is one scanned body: three calls of the kernel
        assert text.count("%gdn_prefill") % 3 == 0
        assert "triangular" not in text.lower()
        assert len(re.findall(r"%moe_ffn[.\d]* = ", text)) == 4
        assert len(re.findall(r"%held_sum[.\d]* = ", text)) == 4
        assert "moe.combine/scatter" not in text
        assert memory.temp_size_in_bytes < {3: 1.0e9, 15: 3.1e9,
                                            16: 3.3e9}[pages]
    else:
        assert "moe.combine/while" not in text


def test_longcats_prefill_holds_one_grouped_kernel_a_routed_block(
        one_chip, as_on_the_chip):
    """LongCat's 16-page prefill (98,304 places a routed block, some 2,048
    of them live), lowered for the chip: the held path is one loop with ONE
    call of the grouped kernel in its body, where the block form held twelve
    a routed block, eleven of them under a ``cond`` (what its ``compile_s``
    grew by; PERF.md, PRs 43 and 47). All-``S``: the stack is one scanned
    body, so one call in the whole text."""
    import re

    lowered, _ = _cell_program("serve-longcatflash-prefill-open", "prefill",
                               16, one_chip)
    text = lowered.as_text()
    assert len(re.findall(r"call @grouped_ffn", text)) == 1
    assert 'kernel_name = "moe_ffn"' in text
    assert "stablehlo.case" not in text
    # and one sum of the held rows beside it (PR 49), no scatter
    assert len(re.findall(r"call @held_sum", text)) == 1
    assert 'kernel_name = "held_sum"' in text
    assert "stablehlo.scatter" not in text


# (tokens, d, places of a chunk, held experts): the three held families'
# 16-page prefills and Qwen3-Next's shortest (ops/moe.py held_chunk)
HELD_SUMS = {
    "qwen3next 16 pages": (32768, 2048, 89344, 128),
    "qwen3next 3 pages": (6144, 2048, 16896, 128),
    "keye 16 pages": (32768, 2048, 40960, 16),
    "longcat 16 pages": (8192, 6144, 2560, 16),
    "commandaplus 16 pages": (16384, 4096, 20480, 16),
    "commandaplus 5 pages": (5120, 4096, 6400, 16),
}


@pytest.mark.parametrize("what", list(HELD_SUMS))
def test_held_sum_compiles_at_the_cells_shapes(what, one_chip):
    """The held rows' gather-sum for a described v5e: slabs of 16 rows
    copied out of HBM on whole tiles (one row of ``[c, d]`` is refused), a
    row of a slab and of the tile read and written at a traced sublane, a
    chunk's tokens and weights in scalar memory (715 KB of 1 MiB at
    Qwen3-Next's 16 pages) beside the table of runs, the token tile and the
    ring in VMEM under the limit the call asks for; ``y`` is summed in place
    and nothing of the rows' size is made beside it."""
    from ray_tpu.ops import moe
    from ray_tpu.ops.row_sum import held_sum

    tokens, d, places, count = HELD_SUMS[what]
    router = {"qwen3next": (10, 512), "keye": (8, 128),
              "longcat": (12, 768),
              "commandaplus": (8, 128)}[what.split()[0]]
    assert moe.held_chunk(tokens * router[0], count, router[1]) == places

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(held_sum, donate_argnums=0).lower(
        arg((tokens, d), jnp.float32), arg((places, d), jnp.bfloat16),
        arg((places,), jnp.int32), arg((places,), jnp.float32),
        arg((count + 1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%held_sum" in text and "sort(" not in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 4 * tokens * d
    assert memory.temp_size_in_bytes < 2 ** 24  # the table's one-hots


@pytest.mark.parametrize("program,pages", [
    ("prefill", 2), ("prefill", 16), ("decode", 3), ("decode", 16)])
def test_xing4_cells_programs_compile_and_fit_the_chip(
        program, pages, one_chip, as_on_the_chip):
    """The hyper-connected latent blocks' WHOLE prefill and decode programs
    at the cell's published widths, its two stores and its shortest and
    longest page tables, from shapes alone: beside 9.05 GB of weights and
    stores (every expert, the whole vocabulary) a 16-page prefill, whose
    float32 stream is 0.94 GB a copy, must stay inside the chip. Prefill
    holds the flash kernel once a traced BODY (the split score: 128 + 64
    against 128), ``G`` in line and ``L`` scanned over its run of four
    (``llama._segments``; five and four before the walker scanned runs),
    and the experts' grouped kernel once, in the routed body; decode holds
    neither."""
    lowered, stores = _cell_program("serve-xing4-prefill-open", program,
                                    pages, one_chip)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes
    assert 9.0e9 < held < 9.1e9
    assert memory.alias_size_in_bytes >= sum(
        4 * math.prod(a.shape) for a in stores)  # both stores in place
    assert held + memory.temp_size_in_bytes < 13.0e9
    text = compiled.as_text()
    prefill = program == "prefill"
    assert len(re.findall(r"%flash_prefill[.\d]* = ", text)) == 2 * prefill
    assert len(re.findall(r"%moe_ffn[.\d]* = ", text)) == 1 * prefill
    if prefill:
        # 16 pages: 2.92 GB with the run of L scanned and the stream a tuple
        # of rows, 3.72 with the rows side by side in the scan's carry (a
        # copy of 0.94 GB a repetition), 3.32 with the five layers in line
        # (PR 61); no array of the whole stream is made
        assert "f32[1,16384,14336]" not in text
        assert memory.temp_size_in_bytes < {2: 0.9e9, 16: 3.6e9}[pages]
    else:
        assert memory.temp_size_in_bytes < 0.6e9
