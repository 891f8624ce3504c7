"""``serve-granite4hmicro-prefill-open``'s four WHOLE programs compiled for a
described v5e, with ``tests/test_tpu_compile.py``'s helpers and fixtures. A
file of its own for ``tests/test_tpu_compile_commandaplus.py``'s reason: the
driver hands a FILE to one worker, and these compiles run beside the others
and not behind them."""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from test_tpu_compile import (  # noqa: F401 - the two fixtures are used by name
    _cell_program, as_on_the_chip, one_chip)


@pytest.mark.parametrize("program,pages", [
    ("prefill", 2), ("prefill", 8), ("decode", 2), ("decode", 8)])
def test_granite4h_cells_programs_compile_and_fit_the_chip(
        program, pages, one_chip, as_on_the_chip):
    """The state-space hybrid's WHOLE prefill and decode programs at the
    cell's published widths (40 of 40 layers: four periods of ten through the
    walker's scan), its four stores (the 36 Mamba-2 layers' state and
    convolution tail ONE row a page, the 4 attention layers' keys and values
    a row a position) and its shortest checked and longest page tables, from
    shapes alone: beside 10.8 GB of weights and stores a 16,384-position
    prefill must stay inside the chip. A prefill's period holds the flash
    kernel (heads filled up to 128 lanes, GQA at a group of 4 by index map)
    in its ONE attention layer and the state-space kernel
    (``ops/ssm_prefill.py``) in each of its nine Mamba-2 layers, by name, and
    no other Pallas call; nothing of XLA's chunked scan is left beside them
    (no float32 ``[chunks, 64, 256, 256]`` mask, no float32 layout copy of a
    stretch's ``[.., 4352]`` rows or of its heads); decode holds no Pallas
    call and makes no copy of a store's view a query head."""
    lowered, stores = _cell_program("serve-granite4hmicro-prefill-open",
                                    program, pages, one_chip)
    assert [s.shape for s in stores] == [
        (36, 40, 1, 64, 64, 128), (36, 40, 1, 3, 4352),
        (4, 40, 2048, 8, 64), (4, 40, 2048, 8, 64)]
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes
    # 6.38 GB of bfloat16 matrices (float32 norms, convolutions, A_log,
    # dt_bias and D beside them) and 4.44 GB of float32 stores
    assert 10.8e9 < held < 10.9e9
    assert memory.alias_size_in_bytes >= sum(
        4 * math.prod(a.shape) for a in stores)  # every store in place
    text = compiled.as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"", text)
    if program == "prefill":
        assert len(re.findall(r"%flash_prefill[.\d]* = ", text)) == 1
        assert len(re.findall(r"%ssm_prefill[.\d]* = ", text)) == 9
        assert len(calls) == 10  # and no other kernel
        assert not re.findall(r"f32\[[\d,]*64,256,256\]", text)
        assert not re.findall(r"f32\[[\d,]*4352\]\S* copy\(", text)
        assert not re.findall(r"f32\[\d+,8,32,256\]", text)
        # 0.69 and 1.60 GB (1.85 at 8 pages with the scan in XLA, PR 58)
        assert memory.temp_size_in_bytes < {2: 0.75e9, 8: 1.7e9}[pages]
    else:
        assert not calls
        assert memory.temp_size_in_bytes < 0.7e9
        # no copy of a store's view a query head
        assert not re.findall(r"f32\[\d+,32,64\]", text)
    assert held + memory.temp_size_in_bytes < 14.8e9
    print(program, pages, "held", held, "temp", memory.temp_size_in_bytes)


# (heads, their width, the state's width, chunk, positions, type): the cell's
# mixer at every other page count's tile count is the same kernel; these are
# the OTHER shapes ``llama.ssm_prefill_path`` lets through, which no cell runs
OTHER_WIDTHS = {
    "heads of 128": (32, 128, 128, 256, 4096, jnp.bfloat16),
    "heads of 256, chunks of 128": (16, 256, 128, 128, 2048, jnp.bfloat16),
    "a state of 256": (64, 64, 256, 256, 2048, jnp.bfloat16),
    "float32 operands": (64, 64, 128, 128, 2048, jnp.float32),
}


@pytest.mark.parametrize("what", list(OTHER_WIDTHS))
def test_ssm_prefill_compiles_at_the_widths_its_rule_takes(
        what, one_chip, as_on_the_chip):
    """Interpret mode cannot refuse what Mosaic refuses (a broadcast along
    sublanes and lanes at once, found so at heads of 128): the kernel alone,
    two sequences, compiled for the described chip."""
    from ray_tpu.ops import ssm_prefill as sp

    H, P, N, chunk, T, cd = OTHER_WIDTHS[what]
    width, taps = H * P + 2 * N, 4

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda *a: sp._call(
        *a, heads=H, head_dim=P, chunk=chunk, interpret=False)).lower(
        arg((2, T, width), cd), arg((2, T, H)), arg((taps, width)),
        arg((width,)), arg((H,)), arg((H,)), arg((H,)), arg((2, H, P, N)),
        arg((2, taps - 1, width), cd), arg((), jnp.int32)
    ).compile().as_text()
    assert len(re.findall(r"%ssm_prefill[.\d]* = ", text)) == 1
