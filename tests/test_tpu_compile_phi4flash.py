"""``serve-phi4miniflash-prefill-open``'s WHOLE programs, and its Mamba-1
kernel alone, compiled for a described v5e, with ``tests/test_tpu_compile.py``'s
helpers and fixtures; the cell's ``--rehearsal``. A file of its own for the
reason ``tests/test_tpu_compile_commandaplus.py`` gives (the driver hands a
FILE to one worker)."""

import math
import re

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from rehearse import run_cell
from test_tpu_compile import (  # noqa: F401 - the two fixtures are used by name
    _cell_program, as_on_the_chip, one_chip)

CELL = "serve-phi4miniflash-prefill-open"


@pytest.mark.parametrize("program,pages", [
    ("prefill", 2), ("prefill", 16), ("decode", 16)])
def test_phi4flash_cells_programs_compile_and_fit_the_chip(
        program, pages, one_chip, as_on_the_chip):
    """The decoder-hybrid-decoder's WHOLE prefill and decode programs at the
    published widths (32 of 32 layers: ``mw`` x 8 scanned, ``mf`` in line,
    ``gc`` x 7 scanned: SIX traced bodies), its six stores (nine Mamba-1
    layers' state and tail ONE row a page, eight window layers' keys and
    values by slot, ONE full layer's by page; a ``g`` or ``c`` layer none)
    and its shortest and longest page tables, from shapes alone. A prefill
    holds the Mamba-1 kernel twice BY NAME (the scanned body's and layer
    16's), the flash kernel four times (two softmax maps a differential
    layer: the scanned window body's and the full layer's) and no other
    Pallas call; no ``[T, 5120, 16]`` array exists; the layers behind the cut
    run on ONE position (nothing ``[.., 16384, 10240]`` beside the one the
    first 18 layers' MLP makes is theirs: their products are ``[1, 1, ..]``).
    Decode holds no Pallas call."""
    from benchmarks.lib import spec
    from ray_tpu.models import llama

    cfg = spec.program_config(spec.cell_bundle(CELL)["config"])
    assert llama.traced_layers(cfg) == 6 and llama.stream_cut(cfg) == 18
    lowered, stores = _cell_program(CELL, program, pages, one_chip)
    assert [s.shape for s in stores] == [
        (9, 80, 1, 5120, 16), (9, 80, 1, 3, 5120),
        (8, 20, 1024, 20, 64), (8, 20, 1024, 20, 64),
        (1, 80, 1024, 20, 64), (1, 80, 1024, 20, 64)]
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes
    # 7.71 GB of bfloat16 matrices (float32 norms, biases, convolutions,
    # A_log, dt_bias, D beside them) and 2.80 GB of float32 stores
    assert 10.45e9 < held < 10.55e9
    assert memory.alias_size_in_bytes >= sum(
        4 * math.prod(a.shape) for a in stores)  # every store in place
    text = compiled.as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"", text)
    if program == "prefill":
        assert len(re.findall(r"%s6_prefill[.\d]* = ", text)) == 2
        assert len(re.findall(r"%flash_prefill[.\d]* = ", text)) == 4
        assert len(calls) == 6  # and no other kernel
        assert not re.findall(r"f32\[[\d,]*\d{4,},5120,16\]", text)
        # behind the cut: the gated memory units' product is ONE row's
        assert re.findall(r"f32\[1,1,5120\]", text)
        assert memory.temp_size_in_bytes < {2: 0.5e9, 16: 2.3e9}[pages]
    else:
        assert not calls
        assert memory.temp_size_in_bytes < 0.3e9
    assert held + memory.temp_size_in_bytes < 13.0e9
    print(program, pages, "held", held, "temp", memory.temp_size_in_bytes)


@pytest.mark.parametrize("positions", [2048, 16384])
def test_s6_prefill_compiles_at_the_cells_shape(positions, one_chip,
                                                as_on_the_chip):
    """``ops/s6_prefill.py`` ALONE at the cell's 5,120 channels of 16 states
    and its shortest and longest prompts: five channel blocks of 1,024, row
    tiles of 512, a position's ``B`` and ``C`` as scalars in SMEM. One Mosaic
    call: what interpret mode cannot refuse."""
    from ray_tpu.ops.s6_prefill import pick_rows, s6_prefill

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert pick_rows(positions) == 512 and pick_rows(1000) is None
    wide, narrow = arg(1, positions, 5120), arg(1, positions, 16)
    p = {"A_log": arg(5120, 16), "dt_bias": arg(5120), "D": arg(5120)}
    text = jax.jit(s6_prefill).lower(
        wide, wide, narrow, narrow, p, arg(1, 5120, 16),
        arg(dtype=jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(r"%s6_prefill[.\d]* = ", text)) == 1


def test_the_cells_rehearsal_runs_end_to_end():
    """``--rehearsal`` cuts the stack to its first two layers, ``m w``, a
    part of the family that is served alone, at tiny widths in bfloat16: the
    harness, the window's slots and the state's pages run; nothing is
    measured."""
    out = run_cell(CELL, 6500000007)
    assert out["correct"] is True, out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert "rehearsal_only.ttft_p95_ms" in out["metrics"]
