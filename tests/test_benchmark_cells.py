"""How many cells the benchmark has, said ONCE: a ``model_config`` PR that
adds one edits this number and no other model's test."""

from benchmarks.lib import spec


def test_the_benchmark_has_thirteen_cells_one_on_four_chips():
    cells = spec.load_benchmark()["workloads"]
    assert len(cells) == 13
    assert sum(c["chips"] == 4 for c in cells) == 1
    assert sum("prefill-open" in c["name"] for c in cells) == 8
    assert len({c["name"] for c in cells}) == len(cells)
