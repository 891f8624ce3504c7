"""``train-nemotron3nano-1chip``'s WHOLE train step compiled for a described
v5e, with ``tests/test_tpu_compile.py``'s fixtures (a file of its own for the
reason ``tests/test_tpu_compile_commandaplus.py`` gives: the driver hands a
FILE to one worker, and this compile takes 40-60 s). Nothing runs, so nothing
here is a time."""

import re

from test_tpu_compile import (  # noqa: F401 - the two fixtures are used by name
    _whole_step, as_on_the_chip, one_chip)

CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
TAKEN = re.compile(r"(?:true_computation|false_computation)=%([\w.\-]+)")


def _rows_by_place(text):
    """``(always, under)``: the row counts of the grouped products' results
    (``ragged-dot``: ``[rows, width]``) in the compiled ``text``, those in
    computations that run every step and those only some ``conditional``'s
    branch reaches."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    calls = {n: set() for n in bodies}
    roots = set()
    for n, lines in bodies.items():
        for line in lines:
            calls[n].update(CALLED.findall(line))
            branches = TAKEN.findall(line) + [
                b.strip().lstrip("%") for group in BRANCHES.findall(line)
                for b in group.split(",")]
            calls[n].update(branches)
            roots.update(branches)
    under, todo = set(), list(roots)
    while todo:
        n = todo.pop()
        if n not in under and n in calls:
            under.add(n)
            todo.extend(calls[n])
    always, inside = set(), set()
    for n, lines in bodies.items():
        for line in lines:
            m = re.match(
                r"\s*(?:ROOT )?%ragged-dot[\w.\-]* = \w+\[(\d+),\d+\]", line)
            if m:
                (inside if n in under else always).add(int(m.group(1)))
    return always, inside


def test_the_nemotron_cells_whole_step_fits_and_makes_the_first_tiers_rows(
        one_chip, as_on_the_chip):
    """The cell's WHOLE step (batch 2 x 8192, every KEEP_GROUPS group
    recomputed): its memory account stays under the chip's 15.75 GiB, and a
    routed layer's grouped products that run EVERY step are the first
    tier's 8,448 places (``ops/moe.py held_tiers``: 8 of 128 experts on
    98,304 places) where the 24,576 of an even block stood; the tiers behind
    it (16,128 and three of 24,576) are only in a ``conditional``'s
    branches."""
    from ray_tpu.ops import moe

    edges = moe.held_tiers(2 * 8192 * 6, 8, 128)  # 8 of 128 experts, top 6
    assert edges == (0, 8448, 24576, 49152, 73728, 98304)
    compiled, need = _whole_step("train-nemotron3nano-1chip", one_chip)
    assert 12 * 2 ** 30 < need < 15.75 * 2 ** 30, need
    text = compiled.as_text()
    assert "[8448,2688]" in text
    always, under = _rows_by_place(text)
    tiers = {hi - lo for lo, hi in zip(edges, edges[1:])}
    assert (always, under) == ({8448}, tiers - {8448})
