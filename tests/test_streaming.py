"""Streaming generators: num_returns="streaming" on tasks and actors.

Reference: _raylet.pyx:1074-1317 streaming generator plumbing +
ObjectRefGenerator semantics (incremental consumption, mid-stream errors).
"""

import time

import pytest

import ray_tpu
from ray_tpu.core.exceptions import TaskError


def test_task_stream_basic(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    g = gen.remote(5)
    assert [ray_tpu.get(r) for r in g] == [0, 10, 20, 30, 40]
    # completed() resolves to the item count
    assert ray_tpu.get(g.completed()) == 5


def test_stream_incremental_consumption(ray_start_regular):
    """Items are consumable while the producer is still running."""
    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        for i in range(4):
            time.sleep(0.3)
            yield i

    @ray_tpu.remote
    def warmup():
        return 1

    ray_tpu.get(warmup.remote())  # absorb worker cold start
    t0 = time.monotonic()
    it = iter(slow_gen.remote())
    first = ray_tpu.get(next(it))
    elapsed = time.monotonic() - t0
    assert first == 0
    assert elapsed < 1.0, f"first item took {elapsed:.2f}s (not incremental)"
    assert [ray_tpu.get(r) for r in it] == [1, 2, 3]


def test_stream_mid_error(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def bad_gen():
        yield 1
        raise ValueError("boom")

    it = iter(bad_gen.remote())
    assert ray_tpu.get(next(it)) == 1
    with pytest.raises(TaskError):
        ray_tpu.get(next(it))


def test_stream_empty(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def empty():
        return
        yield  # pragma: no cover

    assert list(empty.remote()) == []


@pytest.mark.slow  # >5s on the 1-core box: full-tier only (tier-1 wall budget)
def test_stream_empty_stress(ray_start_regular):
    """Regression: empty-stream EOF delivery under GC + task load.

    Round-5 full-suite runs hung forever in test_stream_empty (zero CPU):
    ``ObjectRef.__del__`` ran ``remove_local_ref`` inside the garbage
    collector, which can fire on a thread already holding the
    DirectTaskManager lock — self-deadlocking the completion path and
    losing the stream's EOF (an empty stream's ONLY signal is the EOF).
    Drops are now handed to a reaper thread; this loops empty-stream
    creation under background load with forced GC to keep the original
    interleaving covered.
    """
    import gc
    import threading

    @ray_tpu.remote(num_returns="streaming")
    def empty():
        return
        yield  # pragma: no cover

    @ray_tpu.remote
    def busy(i):
        return [i] * 64

    stop = threading.Event()
    errors = []

    def load():
        while not stop.is_set():
            try:
                ray_tpu.get([busy.remote(i) for i in range(4)], timeout=60)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    t = threading.Thread(target=load, daemon=True)
    t.start()
    try:
        for i in range(20):
            # churn refs so the GC has ObjectRefs to finalize mid-loop
            assert list(empty.remote()) == []
            if i % 5 == 0:
                gc.collect()
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errors, errors
    # the queued __del__ drops must drain without wedging the runtime
    from ray_tpu.core.object_ref import _drop_queue, flush_pending_drops

    flush_pending_drops(timeout=10.0)
    assert not _drop_queue


def test_actor_method_stream(ray_start_regular):
    @ray_tpu.remote
    class A:
        def stream(self, n):
            for i in range(n):
                yield chr(65 + i)

    a = A.remote()
    g = a.stream.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(r) for r in g] == ["A", "B", "C"]


def test_stream_consumed_inside_task(ray_start_regular):
    """A worker task can consume another task's stream (worker-side
    stream_next goes through the bounded-rounds RPC path)."""
    @ray_tpu.remote(num_returns="streaming")
    def source():
        for i in range(3):
            yield i + 1

    @ray_tpu.remote
    def consume(g):
        return sum(ray_tpu.get(r) for r in g)

    assert ray_tpu.get(consume.remote(source.remote())) == 6


def test_stream_large_items(ray_start_regular):
    """Items above the inline threshold go through the arena."""
    import numpy as np

    @ray_tpu.remote(num_returns="streaming")
    def big_gen():
        for i in range(3):
            yield np.full(200_000, i, dtype=np.int64)  # 1.6MB each

    vals = [ray_tpu.get(r) for r in big_gen.remote()]
    assert [int(v[0]) for v in vals] == [0, 1, 2]


def test_direct_stream_zero_head_records(ray_start_regular):
    """Round-5 invariant: streaming rides the direct path end to end —
    a task stream and an actor-call stream leave ZERO head task records
    beyond the actor creation, and no head stream records at all
    (items ride the direct reply chain to the owner)."""
    from ray_tpu.core import runtime as runtime_mod

    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    @ray_tpu.remote
    class A:
        def stream(self, n):
            for i in range(n):
                yield i * 2

    head = runtime_mod.get_current_runtime().head
    a = A.remote()
    assert ray_tpu.get(a.stream.options(  # warm the actor
        num_returns="streaming").remote(1).completed()) == 1
    before = len(head.tasks)

    assert [ray_tpu.get(r) for r in gen.remote(4)] == [0, 1, 2, 3]
    assert [ray_tpu.get(r)
            for r in a.stream.options(
                num_returns="streaming").remote(3)] == [0, 2, 4]

    assert len(head.tasks) == before  # no new head task records
    assert not head.streams           # no head stream records


def test_stream_across_daemon_nodes(ray_start_cluster):
    """Stream items hop the peer mesh: the producer actor lives on a
    separate-process daemon, the driver consumes — item announcements
    ride executor-worker -> daemon node -> head node -> owner, with the
    completion FIFO behind them."""
    cluster = ray_start_cluster
    # capacity 2: the Producer actor holds one unit for life, the big()
    # task needs the other
    cluster.add_node(num_cpus=2, resources={"там": 2},
                     separate_process=True)

    @ray_tpu.remote(resources={"там": 1})
    class Producer:
        def stream(self, n):
            for i in range(n):
                yield ("item", i)

    p = Producer.remote()
    g = p.stream.options(num_returns="streaming").remote(5)
    assert [ray_tpu.get(r) for r in g] == [("item", i) for i in range(5)]

    # large items cross the mesh via the store path
    import numpy as np

    @ray_tpu.remote(resources={"там": 1}, num_returns="streaming")
    def big():
        for i in range(2):
            yield np.full(150_000, i, dtype=np.int64)

    vals = [ray_tpu.get(r) for r in big.remote()]
    assert [int(v[0]) for v in vals] == [0, 1]


@pytest.mark.slow  # >5s on the 1-core box: full-tier only (tier-1 wall budget)
def test_serve_streaming_and_data_split_head_free(ray_start_regular):
    """Round-5 verdict ask #1 "done" criteria: a Serve streaming response
    and a Data streaming_split iterator both run with zero new head task
    records and zero head stream records."""
    from ray_tpu import serve
    from ray_tpu.core import runtime as runtime_mod

    head = runtime_mod.get_current_runtime().head

    @serve.deployment(stream=True)
    class Streamer:
        def __call__(self, n):
            for i in range(n):
                yield f"chunk{i}"

    # not the default port 8000: another xdist worker may hold it
    serve.start(serve.HTTPOptions(port=0))
    h = serve.run(Streamer.bind())
    assert list(h.options(stream=True).remote(2)) == ["chunk0", "chunk1"]
    before = len(head.tasks)
    assert list(h.options(stream=True).remote(3)) == [
        "chunk0", "chunk1", "chunk2"]
    assert len(head.tasks) == before, "serve streaming touched the head"
    assert not head.streams
    serve.shutdown()

    import ray_tpu.data as rdata

    ds = rdata.range(20)
    it = ds.streaming_split(1)[0]
    total = sum(sum(b["id"]) for b in it.iter_batches(batch_size=5))
    assert total == sum(range(20))
    assert not head.streams, "streaming_split left head stream records"
