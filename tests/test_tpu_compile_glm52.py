"""``serve-glm52-prefill-open``'s WHOLE programs, and its attention kernel
alone, compiled for a described v5e, with ``tests/test_tpu_compile.py``'s
helpers and fixtures; a file of its own for the reason
``tests/test_tpu_compile_commandaplus.py`` gives (the driver hands a FILE to
one worker)."""

import math
import re

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from test_tpu_compile import (  # noqa: F401 - the two fixtures are used by name
    _cell_program, as_on_the_chip, one_chip)


@pytest.mark.parametrize("program,pages", [
    ("prefill", 3), ("prefill", 8), ("decode", 3), ("decode", 8)])
def test_glm52_cells_programs_compile_and_fit_the_chip(
        program, pages, one_chip, as_on_the_chip):
    """Latent attention under a shared selection, WHOLE prefill and decode
    programs at the cell's published widths, its five stores (a latent row
    every layer, an index key the two FULL layers) and its shortest and
    longest page tables, from shapes alone. Beside 9.0 GB of weights and
    stores an 8-page prefill (q, the expanded keys, the values and the output
    of 64 heads of 256 are 0.54 GB each, the carried mask 0.27 GB) stays
    inside the chip. Prefill holds ``index_select`` TWICE (the dense layer in
    line and the last routed one: at 32 heads of 128, 4,096 stacked rows a
    query block) and ``masked_flash`` THREE times (one a traced body: ``X``,
    the run ``Z Z Z`` scanned, ``Y``; at head width 256, a key head a query
    head), the shared run's body none of the first; decode holds neither,
    expands no key and gathers 2,048 rows a layer."""
    lowered, stores = _cell_program("serve-glm52-prefill-open", program,
                                    pages, one_chip)
    assert [s.shape for s in stores] == [
        (1, 48, 2048, 576), (1, 48, 2048, 128), (3, 48, 2048, 576),
        (1, 48, 2048, 576), (1, 48, 2048, 128)]
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes
    assert 8.9e9 < held < 9.1e9
    assert memory.alias_size_in_bytes >= sum(
        4 * math.prod(a.shape) for a in stores)  # every store in place
    text = compiled.as_text()
    prefill = program == "prefill"
    assert len(re.findall(r"%dsa_index_select[.\d]* = ", text)) == 2 * prefill
    assert len(re.findall(r"%dsa_masked_flash[.\d]* = ", text)) == 3 * prefill
    if prefill:
        assert memory.temp_size_in_bytes < {3: 2.5e9, 8: 5.5e9}[pages]
    else:
        assert memory.temp_size_in_bytes < 0.3e9, memory.temp_size_in_bytes
        # no key or value is expanded: nothing [positions, 64, 256]
        assert not re.findall(r"\[\d{4,},64,256\]", text)
    assert held + memory.temp_size_in_bytes < 15.0e9


@pytest.mark.parametrize("pages", [3, 8])
def test_masked_flash_compiles_at_glm52s_shape(pages, one_chip,
                                               as_on_the_chip):
    """``masked_flash`` ALONE at the cell's shape (64 query heads on 64
    expanded key heads of 256) and its shortest and longest page tables: a
    grid step of eight query blocks (1,024 rows: ``flash_step``) beside a
    head's whole keys and values and eight rows of mask tiles, each twice, is
    60 MiB of VMEM at 3 pages and 100 at 8, asked for and under the chip's
    128. One Mosaic call: what interpret mode cannot refuse."""
    from ray_tpu.ops import sparse_prefill as sp

    t = pages * 2048

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = arg(1, t, 64, 256)
    step = sp.flash_step(q, q, q)
    assert (step["rows_a_step"], step["heads_a_step"]) == (1024, 1)
    assert step["vmem_bytes"] == {3: 60, 8: 100}[pages] * 2 ** 20 \
        <= sp.FLASH_VMEM < 128 * 2 ** 20
    lowered = jax.jit(sp.masked_flash).lower(
        q, q, q, arg(*sp.mask_tiles_shape(1, t), dtype=jnp.int8))
    assert "scoped_memory_configs" in lowered.as_text()
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"bf16[1,64,{t // 1024},1024,256]" in text
