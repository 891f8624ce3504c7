"""Serve deployment graphs (round-4 ask #6; reference:
python/ray/serve/dag.py + _private/deployment_graph_build.py)."""

import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve import InputNode


@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=4)
    # a port of its own: serve.run() alone would take the default, 8000,
    # which another xdist worker's serve test may hold
    serve.start(serve.HTTPOptions(port=0))
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@serve.deployment
class Doubler:
    def apply(self, x):
        return x * 2


@serve.deployment
class Adder:
    def __init__(self, bias=0):
        self.bias = bias

    def apply(self, x):
        return x + self.bias


@serve.deployment
class Combiner:
    def combine(self, a, b):
        return {"sum": a + b}


def test_two_stage_graph(cluster):
    with InputNode() as inp:
        doubled = Doubler.bind().apply.bind(inp)
        out = Adder.bind(10).apply.bind(doubled)
    handle = serve.run(out, route_prefix=None)
    assert handle.remote(5).result(timeout=60) == 20  # 5*2 + 10
    assert handle.remote(0).result(timeout=60) == 10
    # both stages exist as first-class deployments
    st = serve.status()
    assert "Doubler" in st and "Adder" in st and "DAGDriver" in st


def test_diamond_graph_branches(cluster):
    with InputNode() as inp:
        left = Doubler.bind().apply.bind(inp)
        right = Adder.bind(100).apply.bind(inp)
        out = Combiner.bind().combine.bind(left, right)
    handle = serve.run(out, route_prefix=None)
    assert handle.remote(3).result(timeout=60) == {"sum": 6 + 103}


def test_rolling_update_of_one_stage_under_traffic(cluster):
    """Redeploying one stage (new version/bias) swaps replicas under
    live traffic via the long-poll handles; no request fails."""
    with InputNode() as inp:
        out = Adder.options(num_replicas=2).bind(1).apply.bind(inp)
    handle = serve.run(out, route_prefix=None)
    assert handle.remote(1).result(timeout=60) == 2

    failures = []
    seen = set()
    stop = threading.Event()

    def traffic():
        while not stop.is_set():
            try:
                seen.add(handle.remote(1).result(timeout=30))
            except Exception as e:  # noqa: BLE001
                failures.append(e)
            time.sleep(0.02)

    t = threading.Thread(target=traffic)
    t.start()
    try:
        time.sleep(0.5)
        # roll the stage to bias=5 (a new code version)
        with InputNode() as inp:
            out2 = Adder.options(num_replicas=2, version="2").bind(
                5).apply.bind(inp)
        serve.run(out2, route_prefix=None)
        deadline = time.time() + 60
        while time.time() < deadline and 6 not in seen:
            time.sleep(0.1)
    finally:
        stop.set()
        t.join()
    assert not failures, failures[:3]
    assert 2 in seen and 6 in seen  # old then new version served
