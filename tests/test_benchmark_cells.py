"""How many cells the benchmark has, said ONCE: a ``model_config`` PR that
adds one edits this number and no other model's test."""

from benchmarks.lib import spec


def test_the_benchmark_has_fifteen_cells_one_on_four_chips():
    cells = spec.load_benchmark()["workloads"]
    assert len(cells) == 15
    assert sum(c["chips"] == 4 for c in cells) == 1
    assert sum("prefill-open" in c["name"] for c in cells) == 10
    assert cells[-1]["name"] == "serve-phi4miniflash-prefill-open"
    assert len({c["name"] for c in cells}) == len(cells)
