"""``serve-granite4hmicro-prefill-open``'s four WHOLE programs compiled for a
described v5e, with ``tests/test_tpu_compile.py``'s helpers and fixtures. A
file of its own for ``tests/test_tpu_compile_commandaplus.py``'s reason: the
driver hands a FILE to one worker, and these compiles run beside the others
and not behind them."""

import math
import re

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
    prefill, whose scan goes 4,096 positions at a time, must stay inside the
    chip. Prefill holds the flash kernel (heads filled up to 128 lanes, GQA
    at a group of 4 by index map) in its period's ONE attention layer and no
    other Pallas call (the scan is XLA's chunks); decode holds none and makes
    no copy of a store's view a query head."""
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
        assert len(calls) == 1  # no other kernel: the scan is XLA's
        assert memory.temp_size_in_bytes < {2: 1.0e9, 8: 2.2e9}[pages]
    else:
        assert not calls
        assert memory.temp_size_in_bytes < 0.7e9
        # no copy of a store's view a query head
        assert not re.findall(r"f32\[\d+,32,64\]", text)
    assert held + memory.temp_size_in_bytes < 14.8e9
    print(program, pages, "held", held, "temp", memory.temp_size_in_bytes)
