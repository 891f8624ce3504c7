"""Generative decode on the compiled serve plane (serve/decode.py,
serve/compiled_dispatch.py decode lanes, TAG_STREAM framing).

Covers the decode request path end to end: token streaming over compiled
stream lanes (no eager fallback after warm-up), iteration-level
continuous batching (admissions between decode steps, short requests
finishing first), prefix-affinity routing across replicas, SSE at the
HTTP proxy, the TAG_BYTES bytes-body fast lane, the eager fallback
parity path, replica death mid-stream (attributed error, survivor
retry), and the prewarmed-worker pool that kills the scale-out
cold-start tail.
"""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.config import global_config

PORT = 18493


@pytest.fixture
def serve_instance():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    serve.start(serve.HTTPOptions(port=PORT))
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _planes(deployment):
    from ray_tpu.serve import observability as obs

    obs.drain_deferred()
    return serve.status().get(deployment, {}).get("dispatch_planes", {})


def _toy_lm(**opts):
    @serve.deployment(decode=True, **opts)
    class ToyLM:
        def create_decode_engine(self):
            from ray_tpu.serve.decode import ToyEngine

            return ToyEngine(n_pages=64, page_size=4)

    return ToyLM


def _warm_stream(handle, deployment, plane="compiled_stream",
                 rounds=10):
    """Issue tiny streams until one rides the compiled plane (the first
    lands eager while the lane compiles)."""
    for _ in range(rounds):
        list(handle.options(stream=True).remote(
            {"prompt": [99, 98], "max_tokens": 1}))
        if _planes(deployment).get(plane, 0) >= 1:
            return
        time.sleep(0.5)
    raise AssertionError(
        f"stream never rode {plane}: {_planes(deployment)}")


def _reference_tokens(prompt, max_tokens, n_pages=64, page_size=4):
    """Ground-truth token sequence from an in-process scheduler."""
    from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

    sched = DecodeScheduler(ToyEngine(n_pages=n_pages,
                                      page_size=page_size))
    assert sched.submit("r", {"prompt": list(prompt),
                              "max_tokens": max_tokens}) is None
    frames, active = [], True
    while active:
        out, active = sched.step()
        frames.extend(out)
    assert frames[-1][1] == "final", frames[-1]
    return json.loads(frames[-1][2])["tokens"]


# --------------------------------------------------------------------------
# iteration-level continuous batching (scheduler, no cluster)
# --------------------------------------------------------------------------


class TestIterationLevelAdmission:
    def test_short_admitted_mid_decode_finishes_first(self):
        """The Orca property: admission happens between decode
        iterations, so a short request that arrives while a long one is
        mid-generation joins the running batch immediately and retires
        first — batch membership is fluid, not epoch-based."""
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        sched = DecodeScheduler(ToyEngine(n_pages=64, page_size=4),
                                max_batch=4)
        sched.submit("long", {"prompt": [1, 2, 3], "max_tokens": 24})
        for _ in range(3):  # long is now mid-decode
            sched.step()
        assert [c for c, _ in sched.retired] == []
        sched.submit("short", {"prompt": [5], "max_tokens": 2})
        active = True
        while active:
            _, active = sched.step()
        retired = [c for c, _ in sched.retired]
        assert retired == ["short", "long"], \
            "short request must finish before the long one it joined"
        assert dict(sched.retired)["long"] == 24, \
            "the long sequence must be unaffected by the mid-flight join"

    def test_admission_capped_by_max_batch(self):
        """A step admits ONE request; the cap holds over the steps: the
        third finds the batch full, admits nobody and decodes."""
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        eng = ToyEngine(n_pages=64, page_size=4)
        sched = DecodeScheduler(eng, max_batch=2)
        for i in range(4):
            sched.submit(f"c{i}", {"prompt": [i + 1], "max_tokens": 8})
        sched.step()
        st = sched.stats()
        assert st["running"] == 1 and st["waiting"] == 3
        sched.step()
        st = sched.stats()
        assert st["running"] == 2 and st["waiting"] == 2
        assert eng.decode_calls == 0
        out, active = sched.step()
        st = sched.stats()
        assert st["running"] == 2 and st["waiting"] == 2 and active
        assert eng.prefill_calls == 2 and eng.decode_calls == 2
        assert [c for c, _, _ in out] == ["c0", "c1"]

    def test_interleaved_arrivals_decode_as_each_would_alone(self):
        """Requests that arrive between steps (before an admit step, a
        decode step, during another's life) each get the token stream
        they get alone, chunk by chunk and in their ``final``."""
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        reqs = {"a": ([1, 2, 3], 9), "b": ([4, 5], 3), "c": ([6], 6),
                "d": ([7, 8, 9, 10, 11], 4), "e": ([1, 2, 3], 5)}
        arrives = {0: ["a"], 1: ["b", "c"], 4: ["d"], 7: ["e"]}
        sched = DecodeScheduler(ToyEngine(n_pages=64, page_size=4),
                                max_batch=3)
        chunks, finals = {}, {}
        n, active = 0, True
        while active or n <= max(arrives):
            for corr in arrives.get(n, ()):
                prompt, max_tokens = reqs[corr]
                assert sched.submit(corr, {"prompt": prompt,
                                           "max_tokens": max_tokens}) is None
            out, active = sched.step()
            n += 1
            for corr, kind, payload in out:
                body = json.loads(payload)
                if kind == "chunk":
                    assert body["i"] == len(chunks.setdefault(corr, []))
                    chunks[corr].append(body["token"])
                else:
                    assert kind == "final", (corr, kind, payload)
                    finals[corr] = body
            assert n < 200
        for corr, (prompt, max_tokens) in reqs.items():
            want = _reference_tokens(prompt, max_tokens)
            assert chunks[corr] == finals[corr]["tokens"] == want, corr
        assert finals["e"]["cached_prefix"] and not finals["a"]["cached_prefix"]
        assert sched.pool.used == sum(
            len(e.pages) for e in sched.prefix_cache._entries.values())

    def test_arrivals_before_every_step_starve_nobody(self):
        """Prefill keeps its priority only while the batch has room: with
        ``max_batch`` 2 and a request arriving before EVERY step, a full
        batch still decodes (its sequences' tokens advance step by step),
        every request ends, and they end in the order they came."""
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        eng = ToyEngine(n_pages=64, page_size=4)
        sched = DecodeScheduler(eng, max_batch=2)
        n_reqs, seen, progress = 12, {}, []
        n, active = 0, True
        while active or n < n_reqs:
            if n < n_reqs:
                sched.submit(n, {"prompt": [n + 1, n + 2], "max_tokens": 4})
            out, active = sched.step()
            n += 1
            for corr, kind, _payload in out:
                seen.setdefault(corr, []).append(kind)
            progress.append(sum(len(s.generated)
                                for s in sched.running.values())
                            + sum(g for _, g in sched.retired))
            assert n < 200
        assert all(b > a for a, b in zip(progress, progress[1:])), \
            "a step made no token: something waited without working"
        assert [c for c, _ in sched.retired] == list(range(n_reqs))
        assert all(kinds == ["chunk"] * 4 + ["final"]
                   for kinds in seen.values()) and len(seen) == n_reqs
        # admissions never outran the cap, and decode was put off by at
        # most the batch's free places
        assert eng.prefill_calls == n_reqs
        assert eng.decode_calls == n_reqs * 3

    @pytest.mark.parametrize("pressure", ["pool", "slots"])
    def test_a_step_that_cannot_admit_decodes(self, pressure):
        """Pages or window slots short for the oldest waiting request:
        nothing behind it is tried, and the SAME step decodes the running
        batch (which is what frees the room)."""
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        eng = ToyEngine(n_pages=4, page_size=2)
        if pressure == "slots":  # an engine with room beside the pages
            eng.n_slots = 3
            eng.window_slots_needed = lambda n_prompt, max_tokens: 2
        sched = DecodeScheduler(eng, max_batch=4)
        # 3 pages now and a fourth at its second decode call
        sched.submit("big", {"prompt": [1, 2, 3, 4, 5, 6], "max_tokens": 3})
        sched.submit("next", {"prompt": [7, 8, 9], "max_tokens": 2})
        sched.submit("small", {"prompt": [9], "max_tokens": 2})  # would fit
        out, _ = sched.step()
        assert [(c, k) for c, k, _ in out] == [("big", "chunk")]
        assert eng.decode_calls == 0
        out, active = sched.step()  # no room for "next": decodes "big"
        assert [(c, k) for c, k, _ in out] == [("big", "chunk")]
        assert eng.decode_calls == 1 and eng.prefill_calls == 1
        assert list(sched.running) == ["big"] and active
        assert [w[0] for w in sched.waiting] == ["next", "small"]
        order = []
        while active:
            out, active = sched.step()
            order += [c for c, k, _ in out if k == "final"]
        assert order == ["big", "next", "small"]
        assert [n for _, n in sched.retired] == [3, 2, 2]

    def test_a_first_token_that_ends_its_request_leaves_with_its_final(self):
        """``max_tokens`` 1: chunk and ``final`` come out of the one step
        that admitted, no decode call runs for it, and ``active`` says
        what is left."""
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        eng = ToyEngine(n_pages=64, page_size=4)
        sched = DecodeScheduler(eng)
        sched.submit("one", {"prompt": [1, 2, 3], "max_tokens": 1})
        out, active = sched.step()
        assert [(c, k) for c, k, _ in out] == [("one", "chunk"),
                                               ("one", "final")]
        assert json.loads(out[1][2])["tokens"] \
            == [json.loads(out[0][2])["token"]] \
            == _reference_tokens([1, 2, 3], 1)
        assert not active and not sched.running and eng.decode_calls == 0
        # with another waiting behind it, and one running, it stays true
        sched.submit("long", {"prompt": [4], "max_tokens": 3})
        assert sched.step()[1]
        sched.submit("one-more", {"prompt": [5, 6], "max_tokens": 1})
        sched.submit("waits", {"prompt": [7], "max_tokens": 1})
        out, active = sched.step()
        assert [(c, k) for c, k, _ in out] == [("one-more", "chunk"),
                                               ("one-more", "final")]
        assert active and list(sched.running) == ["long"]
        assert [w[0] for w in sched.waiting] == ["waits"]
        assert eng.decode_calls == 0

    def test_steps_are_counted_by_kind(self):
        """``ray_tpu_serve_decode_steps_total{deployment, kind}``: one
        ``admit`` a request taken off the queue (an error's included),
        one ``decode`` an iteration over the running batch, nothing for
        a step with nothing to do; no step is both."""
        from ray_tpu.serve import observability as obs
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine
        from ray_tpu.util.metrics import registry

        assert obs.enabled()

        def counts():
            vals = registry().local_values(
                "ray_tpu_serve_decode_steps_total")
            return {kind: vals.get(obs.dep_step_kind_key("stepdep", kind),
                                   0.0)
                    for kind in ("admit", "decode")}

        before = counts()
        eng = ToyEngine(n_pages=8, page_size=2)
        sched = DecodeScheduler(eng, deployment="stepdep", max_batch=2)
        sched.step()  # idle: neither
        sched.submit("a", {"prompt": [1, 2], "max_tokens": 4})
        sched.submit("b", {"prompt": [3], "max_tokens": 2})
        sched.submit("never", {"prompt": list(range(40)), "max_tokens": 2})
        sched.submit("c", {"prompt": [4], "max_tokens": 1})
        kinds, active = [], True
        while active:
            calls = (eng.prefill_calls, eng.decode_calls)
            out, active = sched.step()
            kinds.append("decode" if eng.decode_calls > calls[1]
                         else "admit")
            # never both in one step
            assert (eng.prefill_calls > calls[0]) \
                + (eng.decode_calls > calls[1]) <= 1
        after = counts()
        got = {k: after[k] - before[k] for k in after}
        assert got == {"admit": float(kinds.count("admit")),
                       "decode": float(kinds.count("decode"))}
        assert got["admit"] == 4.0  # a, b, the error, c
        assert got["decode"] == 3.0  # a's three tokens after its first
        assert sched.steps == 1 + len(kinds)
        assert set(registry().local_values(
            "ray_tpu_serve_decode_steps_total")) >= {
            (("deployment", "stepdep"), ("kind", "admit")),
            (("deployment", "stepdep"), ("kind", "decode"))}


# --------------------------------------------------------------------------
# a request's way to its first token, as flight-recorder spans
# --------------------------------------------------------------------------


@pytest.fixture
def recorder():
    """Fresh, enabled recorder with the DEFAULT floor: the per-request
    spans must not need a floor of zero to be seen."""
    from ray_tpu.util import flight_recorder as fr

    saved_on, saved_min = fr._on[0], fr._min_dur[0]
    fr.reset_for_tests()
    fr.configure(enabled=True, min_span_us=500.0)
    yield fr
    fr.reset_for_tests()
    fr._on[0], fr._min_dur[0] = saved_on, saved_min


def _recorded(fr, name):
    """This process's spans called ``name``, through the recorder's own
    exporter: [(start s, duration s, tags dict)]."""
    return [(e["ts"] / 1e6, e["dur"] / 1e6, e["args"])
            for e in fr.build_span_events([fr.snapshot_payload()])
            if e["name"] == name]


class TestTimeToFirstTokenSpans:
    def _run_two(self, delay=0.01):
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        sched = DecodeScheduler(
            ToyEngine(n_pages=64, page_size=4, step_delay_s=delay),
            deployment="toy", max_batch=4)
        sched.submit(7, {"prompt": [1, 2, 3], "max_tokens": 3})
        sched.submit(8, {"prompt": [4, 5], "max_tokens": 4})
        active = True
        while active:
            _, active = sched.step()
        return sched

    def test_second_request_waits_out_the_first_prefill(self, recorder):
        """Two requests submitted together are prefilled in two steps,
        back to back: the second's scheduler wait holds the first's whole
        prefill (and no decode call), and the first's (an idle scheduler:
        microseconds) is recorded all the same."""
        self._run_two()
        wait = {t["corr"]: d
                for _, d, t in _recorded(recorder, "serve.sched_wait")}
        prefill = {t["corr"]: d
                   for _, d, t in _recorded(recorder, "serve.prefill")}
        assert sorted(wait) == sorted(prefill) == [7, 8]
        assert wait[8] >= prefill[7] >= 0.01
        assert wait[8] < prefill[7] + 0.01  # nothing decoded in between
        assert wait[7] < prefill[7]
        assert all(t["deployment"] == "toy"
                   for _, _, t in _recorded(recorder, "serve.sched_wait"))

    def test_first_token_leaves_with_the_step_that_made_it(self, recorder):
        """One hold per request, from the end of its prefill to the
        step's return, and nothing of the engine's inside it: shorter
        than one engine call. The first request's chunk is returned by
        the step that prefilled it, BEFORE the second's prefill starts."""
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        delay = 0.01
        eng = ToyEngine(n_pages=64, page_size=4, step_delay_s=delay)
        sched = DecodeScheduler(eng, deployment="toy", max_batch=4)
        sched.submit(7, {"prompt": [1, 2, 3], "max_tokens": 3})
        sched.submit(8, {"prompt": [4, 5], "max_tokens": 4})
        out, active = sched.step()
        assert [(c, k, json.loads(p)["i"]) for c, k, p in out] \
            == [(7, "chunk", 0)]
        assert active and eng.prefill_calls == 1 and eng.decode_calls == 0
        out, active = sched.step()
        assert [(c, k) for c, k, _ in out] == [(8, "chunk")]
        assert eng.prefill_calls == 2 and eng.decode_calls == 0
        while active:
            _, active = sched.step()
        holds = _recorded(recorder, "serve.first_token_hold")
        assert sorted(t["corr"] for _, _, t in holds) == [7, 8]
        hold = {t["corr"]: (t0, d) for t0, d, t in holds}
        prefill = {t["corr"]: (t0, d)
                   for t0, d, t in _recorded(recorder, "serve.prefill")}
        for corr in (7, 8):
            # it starts where the prefill ended, and holds no engine call
            assert hold[corr][0] == pytest.approx(
                sum(prefill[corr]), abs=1e-3)
            assert hold[corr][1] < delay / 2
        # 7's token was out before 8's prefill began
        assert sum(hold[7]) <= prefill[8][0] + 1e-4

    def test_decode_step_spans_count_their_tokens(self, recorder):
        sched = self._run_two()
        steps = _recorded(recorder, "serve.decode_step")
        generated = sum(n for _, n in sched.retired)
        # every token but each request's first came from a decode step
        assert sum(t["tokens"] for _, _, t in steps) \
            == generated - len(sched.retired) == 5
        # and no step that admitted ran one: three iterations, not five
        assert [t["tokens"] for _, _, t in steps] == [2, 2, 1]
        assert sched.steps == 2 + 3

    def test_itl_anchor_does_not_need_the_recorder(self, recorder):
        """The gap between tokens is taken from the monotonic clock: with
        the recorder off the anchors are still real times, not 0.0."""
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        recorder.configure(enabled=False)
        sched = DecodeScheduler(ToyEngine(n_pages=64, page_size=4))
        sched.submit("a", {"prompt": [1], "max_tokens": 8})
        before = time.monotonic()
        sched.step()
        assert before <= sched.running["a"].last_chunk_ts \
            <= time.monotonic()
        assert recorder.snapshot_payload()["events"] == []

    def test_stream_lane_spans_share_the_request_corr(self, recorder):
        """Through the worker's stream loop over a real ring: ingress,
        scheduler wait, prefill and hold of one request carry the lane's
        arrival counter, and a request that arrives while the replica is
        busy is seen to have sat in the ring."""
        import threading
        import uuid

        from ray_tpu.core.worker_runtime import WorkerRuntime
        from ray_tpu.experimental import channel as chan
        from ray_tpu.serve.decode import DecodeScheduler, ToyEngine

        sched = DecodeScheduler(
            ToyEngine(n_pages=64, page_size=4, step_delay_s=0.01),
            deployment="toy")

        def invoke(args):
            (entries,) = args
            for corr, value in entries:
                assert sched.submit(corr, value) is None
            return sched.step()

        uid = uuid.uuid4().hex[:8]
        rings = []
        for side in ("in", "out"):
            path = chan.channel_path(f"ttft_{uid}_{side}")
            rings.append((chan.ShmChannel(path, 4096, create=True,
                                          n_slots=8),
                          chan.ShmChannel(path)))
        (in_w, in_r), (out_w, out_r) = rings
        loop = threading.Thread(
            target=WorkerRuntime._compiled_stream_loop,
            args=(None, in_r, [out_w], lambda *a: None, invoke,
                  lambda e: repr(e).encode(), 4, False, "decode"),
            daemon=True)
        try:
            loop.start()
            for prompt in ([1, 2, 3], [4, 5]):
                in_w.write(json.dumps(
                    {"prompt": prompt, "max_tokens": 2}).encode(),
                    tag=chan.TAG_BYTES)
                # the second lands mid-step: once the first's prefill
                # (10 ms) has begun, nearly all of it is still to come
                deadline = time.monotonic() + 30
                while not sched.engine.prefill_calls:
                    assert time.monotonic() < deadline
                    time.sleep(0.0005)
            finals = 0
            while finals < 2:
                _tag, frame = out_r.read(timeout=30)
                _corr, flags, _body = chan.unpack_stream_frame(frame)
                assert not flags & chan.STREAM_F_ERROR
                finals += bool(flags & chan.STREAM_F_FINAL)
            in_w.write(b"", tag=chan.TAG_STOP)
            loop.join(10)
            assert not loop.is_alive()
        finally:
            for w, r in rings:
                r.close()
                w.close(unlink=True)
        by_span = {name: {t["corr"]: d
                          for _, d, t in _recorded(recorder, name)}
                   for name in ("dag.stream_ingress", "serve.sched_wait",
                                "serve.prefill", "serve.first_token_hold")}
        for name, by_corr in by_span.items():
            assert sorted(by_corr) == [0, 1], name
        ingress = _recorded(recorder, "dag.stream_ingress")
        assert all(t["method"] == "decode" for _, _, t in ingress)
        # request 1 was published with most of a 10 ms prefill to come
        # (and is read as soon as that step returns: no decode call first)
        assert by_span["dag.stream_ingress"][1] >= 0.005
        assert by_span["dag.stream_ingress"][0] \
            < by_span["dag.stream_ingress"][1]


# --------------------------------------------------------------------------
# streaming over the compiled plane
# --------------------------------------------------------------------------


class TestCompiledDecodeStream:
    def test_stream_rides_rings_and_matches_reference(self, serve_instance):
        h = serve.run(_toy_lm(route_prefix=None).bind())
        _warm_stream(h, "ToyLM")
        before = _planes("ToyLM")
        items = list(h.options(stream=True).remote(
            {"prompt": [3, 1, 4], "max_tokens": 12}))
        # per-token chunks followed by the final summary frame
        chunks, final = items[:-1], items[-1]
        assert final["done"] is True and final["n_generated"] == 12
        assert [c["token"] for c in chunks] == final["tokens"]
        assert [c["i"] for c in chunks] == list(range(12))
        assert final["tokens"] == _reference_tokens([3, 1, 4], 12)
        after = _planes("ToyLM")
        assert after.get("compiled_stream", 0) \
            == before.get("compiled_stream", 0) + 1
        # zero eager fallbacks once warm
        assert after.get("eager", 0) == before.get("eager", 0)

    def test_concurrent_streams_share_the_running_batch(
            self, serve_instance):
        """Two streams in flight at once continuous-batch on one
        replica; both outputs match their solo references."""
        from concurrent.futures import ThreadPoolExecutor

        h = serve.run(_toy_lm(route_prefix=None).bind())
        _warm_stream(h, "ToyLM")

        def run(prompt):
            return list(h.options(stream=True).remote(
                {"prompt": prompt, "max_tokens": 10}))[-1]["tokens"]

        with ThreadPoolExecutor(2) as ex:
            fa = ex.submit(run, [1, 2])
            fb = ex.submit(run, [7, 8, 9])
            assert fa.result(timeout=60) == _reference_tokens([1, 2], 10)
            assert fb.result(timeout=60) == _reference_tokens([7, 8, 9], 10)

    def test_prefix_affinity_routes_repeat_prompts_to_warm_replica(
            self, serve_instance):
        """With two replicas, the router pins a prompt hash to the lane
        that served it: the repeat request lands on the cache-warm
        replica and reports cached_prefix — skipping its prefill."""
        h = serve.run(_toy_lm(route_prefix=None,
                              num_replicas=2).bind())
        _warm_stream(h, "ToyLM")
        prompt = {"prompt": [42, 43, 44, 45], "max_tokens": 3}
        first = list(h.options(stream=True).remote(dict(prompt)))[-1]
        hits = 0
        for _ in range(3):
            final = list(h.options(stream=True).remote(dict(prompt)))[-1]
            hits += bool(final.get("cached_prefix"))
        assert hits == 3, \
            "repeat prompts must ride the prefix-affinity lane " \
            f"(first={first}, hits={hits}/3)"

    def test_malformed_request_fails_fast(self, serve_instance):
        h = serve.run(_toy_lm(route_prefix=None).bind())
        _warm_stream(h, "ToyLM")
        with pytest.raises(Exception, match="prompt"):
            list(h.options(stream=True).remote({"prompt": []}))


# --------------------------------------------------------------------------
# HTTP: SSE + bytes-body fast lane
# --------------------------------------------------------------------------


class TestHTTPDecodeAndBytes:
    def test_sse_stream_over_http(self, serve_instance):
        serve.run(_toy_lm(route_prefix="/lm").bind())
        body = json.dumps({"prompt": [3, 1, 4],
                           "max_tokens": 6}).encode()
        # warm: the first request may ride eager; the payload path (raw
        # TAG_BYTES body) and the SSE framing are identical either way
        for _ in range(2):
            resp = urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{PORT}/lm", data=body), timeout=30)
        assert resp.headers["content-type"] == "text/event-stream"
        records = [json.loads(line[len(b"data: "):])
                   for line in resp.read().split(b"\n\n")
                   if line.startswith(b"data: ")]
        assert records[-1]["done"] is True
        assert records[-1]["tokens"] == _reference_tokens([3, 1, 4], 6)
        assert [r["token"] for r in records[:-1]] == records[-1]["tokens"]

    def test_bytes_body_rides_tag_bytes_lane(self, serve_instance):
        @serve.deployment(bytes_body=True, route_prefix="/raw")
        class Shout:
            def __call__(self, body):
                assert isinstance(body, bytes), type(body)
                return body.upper()

        h = serve.run(Shout.bind())
        # warm until a call rides the bytes lane (first may land eager)
        for _ in range(10):
            assert h.remote(b"abc").result(timeout=30) == b"ABC"
            if _planes("Shout").get("compiled_bytes", 0) >= 1:
                break
            time.sleep(0.5)
        planes = _planes("Shout")
        assert planes.get("compiled_bytes", 0) >= 1, planes
        # HTTP: the raw request body goes straight to __call__
        resp = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{PORT}/raw", data=b"hello"), timeout=30)
        assert resp.read() == b"HELLO"

    def test_eager_fallback_parity_when_compiled_disabled(
            self, serve_instance):
        """compiled_dispatch=False: decode streams ride the eager actor
        plane (num_returns="streaming") with identical frames."""
        h = serve.run(_toy_lm(route_prefix=None, name="ToyLMEager",
                              compiled_dispatch=False).bind())
        items = list(h.options(stream=True).remote(
            {"prompt": [3, 1, 4], "max_tokens": 5}))
        assert items[-1]["tokens"] == _reference_tokens([3, 1, 4], 5)
        planes = _planes("ToyLMEager")
        assert planes.get("compiled_stream", 0) == 0, planes
        assert planes.get("eager", 0) >= 1, planes


# --------------------------------------------------------------------------
# chaos: replica dies mid-stream
# --------------------------------------------------------------------------


class TestDecodeStreamChaos:
    @pytest.mark.slow  # >5s on the 1-core box: full-tier only (tier-1 wall budget)
    def test_replica_death_mid_stream_attributed_then_survivor_serves(
            self):
        """Kill the replica worker at a decode iteration mid-stream (the
        dag.exec chaos point). The consumer gets an attributed
        ActorDiedError promptly — never a wedge or bare timeout — and
        once the controller restarts the replica, a retry re-prefills
        and completes."""
        from ray_tpu.core.exceptions import ActorDiedError

        cfg = global_config()
        # every dag.exec invoke from the 25th on crashes the worker:
        # warm-up streams (~2 invokes each) stay under the threshold,
        # the long stream crosses it mid-generation
        cfg.test_fault_spec = "dag.exec.handle_request_decode=crash@25+"
        ray_tpu.init(num_cpus=4, num_tpus=0)
        serve.start(serve.HTTPOptions(port=PORT + 1))
        try:
            h = serve.run(_toy_lm(route_prefix=None).bind())
            _warm_stream(h, "ToyLM")
            it = h.options(stream=True).remote(
                {"prompt": [1, 2, 3], "max_tokens": 50})
            got, err, t0 = [], None, time.monotonic()
            try:
                for item in it:
                    got.append(item)
            except ActorDiedError as e:
                err = e
            elapsed = time.monotonic() - t0
            assert err is not None, \
                f"stream completed without error: {got[-1:]}"
            assert elapsed < 30, "wedged instead of failing fast"
            # attribution: node + worker pid, never a bare timeout
            msg = str(err)
            assert "node" in msg and "pid" in msg, msg
            # the restarted replica (fresh process, hit counter at 0)
            # serves a retry with a fresh prefill
            deadline = time.monotonic() + 60
            while True:
                try:
                    out = list(h.options(stream=True).remote(
                        {"prompt": [1, 2, 3], "max_tokens": 3}))
                    if out and out[-1].get("done"):
                        break
                except Exception:
                    pass
                assert time.monotonic() < deadline, \
                    "no survivor served the retry"
                time.sleep(0.5)
            assert out[-1]["tokens"] == _reference_tokens([1, 2, 3], 3)
        finally:
            cfg.test_fault_spec = ""
            from ray_tpu.core import fault_injection

            fault_injection.reset()
            serve.shutdown()
            ray_tpu.shutdown()


# --------------------------------------------------------------------------
# prewarmed worker pool
# --------------------------------------------------------------------------


class TestPrewarmPool:
    def test_node_maintains_spare_workers_and_refills(self):
        """serve_prewarm_pool_size keeps N idle-or-starting workers
        beyond demand, so a scale-out replica binds to a live process
        instead of paying fork+import. Consuming the spares triggers a
        refill."""
        from ray_tpu.core import runtime as runtime_mod

        cfg = global_config()
        cfg.serve_prewarm_pool_size = 2
        try:
            ray_tpu.init(num_cpus=4, num_tpus=0)
            rt = runtime_mod.get_current_runtime()
            nodes = list(rt.head.nodes.values())

            def warm():
                return sum(
                    sum(1 for w in n._idle if w.state == "idle")
                    + n._num_starting for n in nodes)

            deadline = time.monotonic() + 30
            while warm() < 2:
                assert time.monotonic() < deadline, \
                    f"prewarm pool never filled: {warm()}"
                time.sleep(0.1)

            # occupy workers with long-lived actors; the pump refills
            # the spare pool behind them
            @ray_tpu.remote
            class Hold:
                def ping(self):
                    return "ok"

            actors = [Hold.remote() for _ in range(2)]
            assert all(ray_tpu.get(a.ping.remote(), timeout=60) == "ok"
                       for a in actors)
            deadline = time.monotonic() + 30
            while warm() < 2:
                assert time.monotonic() < deadline, \
                    f"prewarm pool never refilled: {warm()}"
                time.sleep(0.1)
        finally:
            cfg.serve_prewarm_pool_size = 0
            ray_tpu.shutdown()


# --------------------------------------------------------------------------
# health pings during a long eager stream (found on the chip, ISSUE 21)
# --------------------------------------------------------------------------


def test_health_ping_is_answered_while_an_eager_stream_runs():
    """The replica is an async actor, so its sync methods share ONE
    thread, and an eager decode stream holds it until the stream ends. On
    the chip a replica's first stream lasts as long as reaching the chip
    and compiling do; a health ping queued behind it timed out and the
    controller killed the replica. The ping is answered from the event
    loop instead."""
    import cloudpickle

    from ray_tpu.serve.replica import ServeReplica

    class SlowLM:
        def create_decode_engine(self):
            from ray_tpu.serve.decode import ToyEngine

            return ToyEngine(n_pages=64, page_size=4, step_delay_s=0.05)

    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        replica = ServeReplica.remote(cloudpickle.dumps(SlowLM), (), {},
                                      None, "SlowLM", "SlowLM#t")
        assert ray_tpu.get(replica.check_health.remote(), timeout=30)
        stream = replica.handle_request_decode_stream.options(
            num_returns="streaming").remote(
            {"prompt": [1, 2, 3], "max_tokens": 60})  # ~3 s of steps
        first = next(iter(stream))  # the stream is running now
        t0 = time.time()
        assert ray_tpu.get(replica.check_health.remote(), timeout=30)
        waited = time.time() - t0
        frames = [ray_tpu.get(first)] + [ray_tpu.get(r) for r in stream]
        assert frames[-1][0] == "final"
        assert waited < 1.5, (
            f"health ping waited {waited:.2f}s behind the stream")
    finally:
        ray_tpu.shutdown()
