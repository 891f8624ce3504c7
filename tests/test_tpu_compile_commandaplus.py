"""``serve-commandaplus-prefill-open``'s four WHOLE programs compiled for a
described v5e, with ``tests/test_tpu_compile.py``'s helpers and fixtures. A
file of its own because the driver hands a FILE to one worker (``--dist
loadfile``) and ``tests/test_tpu_compile.py`` is the whole run's longest
(577-729 s of one worker in PR 54's two whole runs, of 663-943 s): these
four compiles take 100-130 s, which now run beside it and not behind it."""

import math
import re

import pytest

from test_tpu_compile import (  # noqa: F401 - the two fixtures are used by name
    _cell_program, as_on_the_chip, one_chip)


@pytest.mark.parametrize("program,pages", [
    ("prefill", 5), ("prefill", 16), ("decode", 5), ("decode", 16)])
def test_commandaplus_cells_programs_compile_and_fit_the_chip(
        program, pages, one_chip, as_on_the_chip):
    """The parallel blocks' WHOLE prefill and decode programs at the cell's
    published widths, its four stores (the window layers' by slot) and its
    shortest and longest page tables, from shapes alone: beside 11.0 GB of
    weights and stores a 16-page prefill, whose q and attention output are
    537 MB each and whose shared experts' gate and up 537 MB each, must stay
    inside the chip (the halves of a block run in turn: side by side its
    temporaries are 5.0 GB). Prefill holds the flash kernel once a traced
    BODY (GQA at a group of 16 by index map), the experts' grouped kernel and
    the held rows' sum likewise: the cell's ONE period ``RRRP`` is a body of
    ``R`` scanned three times and ``P`` in line (``llama._segments``; four
    of each before the walker scanned runs, temporaries 3.00 GB at 16 pages
    where they are 2.63); decode holds none of them, scores a KV head's 16
    query heads against its keys as ONE product and makes no ``[T, 128,
    128]`` float32 copy of the keys (2.1 GB at 16 pages)."""
    lowered, stores = _cell_program("serve-commandaplus-prefill-open", program,
                                    pages, one_chip)
    assert [s.shape for s in stores] == [(1, 80, 1024, 8, 128)] * 2 \
        + [(3, 35, 1024, 8, 128)] * 2  # 35 slots: k = 5 pages of window
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes
    assert 11.0e9 < held < 11.1e9
    assert memory.alias_size_in_bytes >= sum(
        4 * math.prod(a.shape) for a in stores)  # every store in place
    text = compiled.as_text()
    prefill = program == "prefill"
    assert len(re.findall(r"%flash_prefill[.\d]* = ", text)) == 2 * prefill
    assert len(re.findall(r"%moe_ffn[.\d]* = ", text)) == 2 * prefill
    assert len(re.findall(r"%held_sum[.\d]* = ", text)) == 2 * prefill
    if prefill:
        assert "ragged-dot" not in text
        assert memory.temp_size_in_bytes < {5: 1.5e9, 16: 3.2e9}[pages]
    else:
        assert memory.temp_size_in_bytes < 0.2e9
        # no copy of a store's view a query head
        assert not re.findall(r"f32\[\d+,128,128\]", text)
    assert held + memory.temp_size_in_bytes < 14.5e9
