"""Paged KV-cache invariants (serve/kv_cache.py + serve/decode.py).

Pins the page-accounting contract that generative decode rides on:
all-or-nothing allocation, alloc/free balance under churn, the
eviction-safety rule (referenced prefix entries are never freed), the
copy-on-write tail-page rule (no shared-page writes), prefix reuse
reproducing the cold prefill's logits byte-identically, and the
occupancy gauges matching pool ground truth. No cluster needed — these
drive the scheduler and engines in-process.
"""

import random

import numpy as np
import pytest

from ray_tpu.serve.decode import DecodeScheduler, ToyEngine
from ray_tpu.serve.kv_cache import (
    PagePool,
    PrefixCache,
    SequenceKV,
    flush_kv_gauges,
    pages_for,
)


def _run_all(sched, reqs, eager=False):
    """Submit requests and step the scheduler to completion; returns
    {corr: [frames]} keyed by correlation id."""
    frames = {}
    for corr, req in reqs:
        err = sched.submit(corr, req, eager=eager)
        assert err is None, err
    active = True
    for _ in range(10_000):
        out, active = sched.step()
        for corr, kind, payload in out:
            frames.setdefault(corr, []).append((kind, payload))
        if not active:
            break
    assert not active, "scheduler never drained"
    return frames


# --------------------------------------------------------------------------
# PagePool
# --------------------------------------------------------------------------


class TestPagePool:
    def test_alloc_is_all_or_nothing(self):
        pool = PagePool(4, 8)
        assert pool.alloc(5) is None
        assert pool.used == 0, "failed alloc must not strand pages"
        got = pool.alloc(4)
        assert sorted(got) == [0, 1, 2, 3]
        assert pool.alloc(1) is None
        pool.release(got)
        assert pool.used == 0

    def test_release_rejects_double_free_and_bad_ids(self):
        pool = PagePool(2, 4)
        pages = pool.alloc(1)
        pool.release(pages)
        with pytest.raises(ValueError, match="double free"):
            pool.release(pages)
        with pytest.raises(ValueError, match="out of range"):
            pool.release([99])

    def test_balance_under_random_churn(self):
        """Seeded random alloc/release interleave: used + free always
        equals capacity, the ledger totals reconcile, and a full drain
        returns the pool to empty."""
        pool = PagePool(32, 4)
        rng = random.Random(7)
        held = []
        for _ in range(2000):
            if held and rng.random() < 0.5:
                pool.release(held.pop(rng.randrange(len(held))))
            else:
                got = pool.alloc(rng.randint(1, 5))
                if got is not None:
                    held.append(got)
            assert pool.used + pool.free_count == pool.n_pages
            assert pool.alloc_total - pool.free_total == pool.used
        for pages in held:
            pool.release(pages)
        assert pool.used == 0
        assert pool.alloc_total == pool.free_total

    def test_pages_for(self):
        assert pages_for(0, 8) == 0
        assert pages_for(1, 8) == 1
        assert pages_for(8, 8) == 1
        assert pages_for(9, 8) == 2


# --------------------------------------------------------------------------
# PrefixCache: refcounts and eviction safety
# --------------------------------------------------------------------------


class TestPrefixCache:
    def test_eviction_never_frees_referenced_entries(self):
        """The RUNNING-sequence safety rule: evict_lru only frees
        refcount-0 entries, even when that means failing the
        allocation."""
        pool = PagePool(4, 4)
        cache = PrefixCache(pool)
        busy = cache.insert((1,), 4, pool.alloc(2))   # refs=1 (caller)
        idle = cache.insert((2,), 4, pool.alloc(2))
        cache.release(idle)                           # refs=0: evictable
        got = cache.alloc_with_evict(2)
        assert got is not None, "idle entry should have been evicted"
        assert sorted(got) == sorted(idle.pages)
        assert (1,) in cache._entries and (2,) not in cache._entries
        # only the referenced entry remains; nothing can be evicted for
        # a request that needs more than the free pages
        pool.release(got)
        assert cache.alloc_with_evict(3) is None
        assert (1,) in cache._entries, \
            "referenced entry must survive allocation pressure"
        assert busy.refs == 1

    def test_lru_order_and_hit_refcount(self):
        pool = PagePool(6, 4)
        cache = PrefixCache(pool)
        a = cache.insert((1,), 4, pool.alloc(2))
        b = cache.insert((2,), 4, pool.alloc(2))
        cache.release(a)
        cache.release(b)
        # touching a makes b the LRU entry
        assert cache.lookup((1,)) is a
        cache.release(a)
        cache.evict_lru(4)
        assert (2,) not in cache._entries and (1,) in cache._entries
        assert cache.hit_rate == 1.0
        assert cache.lookup((9,)) is None
        assert cache.hit_rate == 0.5

    def test_insert_replacing_idle_duplicate_releases_pages(self):
        pool = PagePool(4, 4)
        cache = PrefixCache(pool)
        first = cache.insert((1,), 4, pool.alloc(2))
        cache.release(first)
        cache.insert((1,), 4, pool.alloc(2))
        # the idle duplicate's pages went back to the pool
        assert pool.used == 2


class TestSequenceKV:
    def test_write_never_lands_in_shared_page(self):
        kv = SequenceKV(page_size=4, shared=[7], owned=[3])
        assert kv.page_for(2) == (7, 2)
        assert kv.page_for(5) == (3, 1)
        with pytest.raises(ValueError, match="copy-on-write"):
            kv.writable_for(1)
        assert kv.writable_for(4) == (3, 0)
        with pytest.raises(IndexError):
            kv.page_for(8)


# --------------------------------------------------------------------------
# Scheduler-level invariants (ToyEngine)
# --------------------------------------------------------------------------


class TestSchedulerAccounting:
    def test_alloc_free_balance_under_request_churn(self):
        """After many generations complete, every page is either free or
        pinned by a prefix entry — sequences leak nothing."""
        eng = ToyEngine(n_pages=32, page_size=4)
        sched = DecodeScheduler(eng, max_batch=4)
        rng = random.Random(3)
        reqs = [(f"c{i}", {"prompt": [rng.randrange(50) for _ in
                                      range(rng.randint(1, 9))],
                           "max_tokens": rng.randint(1, 12)})
                for i in range(40)]
        frames = _run_all(sched, reqs)
        assert len(frames) == 40
        for corr, fs in frames.items():
            assert fs[-1][0] == "final", (corr, fs[-1])
        prefix_pages = sum(len(e.pages)
                           for e in eng.prefix_cache._entries.values())
        assert eng.pool.used == prefix_pages, \
            "pages outside the prefix cache leaked"
        assert all(e.refs == 0 for e in eng.prefix_cache._entries.values())
        # evicting everything drains the pool completely
        eng.prefix_cache.evict_lru(eng.pool.n_pages)
        assert eng.pool.used == 0
        assert eng.pool.alloc_total == eng.pool.free_total

    def test_running_prefix_pages_survive_pressure(self):
        """A long-running sequence's prefix pages are never evicted out
        from under it, even while later admissions force evictions —
        its history stays intact (ToyEngine recomputes from the paged
        history, so a freed page would corrupt the output)."""
        eng = ToyEngine(n_pages=8, page_size=2)
        sched = DecodeScheduler(eng, max_batch=2)
        # peak footprint: 2 prefix pages + 4 owned decode pages = 6 of 8,
        # leaving 2 pages for the churn to fight over
        long_req = {"prompt": [5, 6, 7, 8], "max_tokens": 8}
        # reference run, no contention
        ref = _run_all(DecodeScheduler(ToyEngine(n_pages=8, page_size=2)),
                       [("ref", long_req)])
        assert sched.submit("long", long_req) is None
        sched.step()  # admit the long sequence
        frames = {"long": []}
        # churn short requests through the remaining pool space
        for i in range(12):
            sched.submit(f"s{i}", {"prompt": [i + 1], "max_tokens": 2})
        active = True
        while active:
            out, active = sched.step()
            for corr, kind, payload in out:
                frames.setdefault(corr, []).append((kind, payload))
        assert frames["long"][-1][0] == "final"
        import json as _json

        got = _json.loads(frames["long"][-1][1])
        want = _json.loads(ref["ref"][-1][1])
        assert got["tokens"] == want["tokens"], \
            "contention changed the long sequence's output: a page it " \
            "was using was freed or overwritten"

    def test_oversized_prompt_errors_instead_of_queueing_forever(self):
        eng = ToyEngine(n_pages=4, page_size=2)
        sched = DecodeScheduler(eng)
        sched.submit("big", {"prompt": list(range(20)), "max_tokens": 2})
        out, active = sched.step()
        assert not active
        assert out[0][1] == "error"
        assert "can never fit" in str(out[0][2])

    def test_occupancy_gauge_matches_ground_truth(self):
        from ray_tpu.util.metrics import registry

        eng = ToyEngine(n_pages=16, page_size=4)
        sched = DecodeScheduler(eng, deployment="gaugedep")
        sched.submit("a", {"prompt": [1, 2, 3, 4, 5], "max_tokens": 4})
        sched.step()
        flush_kv_gauges("gaugedep", eng.pool, eng.prefix_cache)
        snap = registry().snapshot()
        tags = (("deployment", "gaugedep"),)
        assert snap["ray_tpu_serve_kv_pages_used"]["values"][tags] \
            == float(eng.pool.used) != 0.0
        assert snap["ray_tpu_serve_kv_pages_capacity"]["values"][tags] \
            == 16.0
        assert snap["ray_tpu_serve_kv_prefix_hit_rate"]["values"][tags] \
            == eng.prefix_cache.hit_rate


class TestPrefixReuse:
    def test_hit_skips_prefill_and_output_is_identical(self):
        eng = ToyEngine(n_pages=32, page_size=4)
        sched = DecodeScheduler(eng)
        req = {"prompt": [3, 1, 4, 1, 5, 9], "max_tokens": 8}
        import json as _json

        cold = _run_all(sched, [("cold", req)])
        prefills = eng.prefill_calls
        warm = _run_all(sched, [("warm", req)])
        assert eng.prefill_calls == prefills, "hit must skip prefill"
        c = _json.loads(cold["cold"][-1][1])
        w = _json.loads(warm["warm"][-1][1])
        assert w["tokens"] == c["tokens"]
        assert w["cached_prefix"] is True and c["cached_prefix"] is False
        assert eng.prefix_cache.hit_rate > 0

    def test_concurrent_same_prompt_sequences_do_not_cross_write(self):
        """Two sequences sharing a prefix with a partial tail page decode
        together: copy-on-write keeps their tail writes on different
        physical pages, so both match the solo reference output."""
        import json as _json

        req = {"prompt": [2, 7, 1], "max_tokens": 10}   # 3 % 4 != 0: COW
        ref = _json.loads(_run_all(
            DecodeScheduler(ToyEngine(n_pages=32, page_size=4)),
            [("r", req)])["r"][-1][1])
        eng = ToyEngine(n_pages=32, page_size=4)
        sched = DecodeScheduler(eng, max_batch=4)
        frames = _run_all(sched, [("a", req), ("b", req)])
        for corr in ("a", "b"):
            got = _json.loads(frames[corr][-1][1])
            assert got["tokens"] == ref["tokens"], corr


# --------------------------------------------------------------------------
# Llama engine: byte-identical logits on prefix hit
# --------------------------------------------------------------------------


class TestLlamaEngine:
    @pytest.fixture
    def engine(self):
        from ray_tpu.models.llama import LlamaDecodeEngine

        # default cfg is LlamaConfig.debug() — tiny, CPU-friendly
        return LlamaDecodeEngine(n_pages=16, page_size=4, seed=0)

    def test_prefix_hit_blob_is_cold_prefill_logits(self, engine):
        sched = DecodeScheduler(engine)
        prompt = [3, 1, 4, 1, 5]
        cold = engine.prefill(
            prompt, engine.prefix_cache.alloc_with_evict(
                pages_for(len(prompt), engine.page_size)))
        entry = engine.prefix_cache._entries.get(tuple(prompt))
        if entry is None:  # prefill alone doesn't insert; go via sched
            _run_all(sched, [("c", {"prompt": prompt, "max_tokens": 1})])
            entry = engine.prefix_cache._entries[tuple(prompt)]
        np.testing.assert_array_equal(np.asarray(entry.blob),
                                      np.asarray(cold))

    def test_generation_identical_with_and_without_cache_hit(self, engine):
        import json as _json

        sched = DecodeScheduler(engine)
        req = {"prompt": [7, 8, 9], "max_tokens": 6}
        cold = _json.loads(_run_all(sched, [("c", req)])["c"][-1][1])
        warm = _json.loads(_run_all(sched, [("w", req)])["w"][-1][1])
        assert warm["cached_prefix"] is True
        assert warm["tokens"] == cold["tokens"]

    # ---- the page store on the device (PR 25)

    @staticmethod
    def _store(engine):
        """Both page stores as numpy, ``[2, L, n_pages, page_size, ...]``."""
        return np.stack([np.asarray(pages) for pages in engine.stores])

    @staticmethod
    def _forward_logits(engine, toks):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.llama import forward

        return np.asarray(jax.jit(
            lambda prm, t: forward(engine.cfg, prm, t))(
                engine.params, jnp.asarray(np.asarray(toks, np.int32)[None])),
            np.float32)[0]

    @staticmethod
    def _teacher_forced(engine, toks, n_prompt, pages):
        """Prefill ``toks[:n_prompt]``, then decode the rest one by one;
        the logits of positions ``n_prompt - 1 ..``, one row each."""
        ps = engine.page_size
        rows = [engine.prefill([int(t) for t in toks[:n_prompt]],
                               pages[:pages_for(n_prompt, ps)])]
        for pos in range(n_prompt, len(toks)):
            rows.append(engine.decode(pos, int(toks[pos]),
                                      pages[:pages_for(pos + 1, ps)]))
        return np.stack(rows)

    def test_scattered_page_table_matches_forward(self, engine):
        """A prompt that ends inside a page, then four decoded positions
        that cross a page boundary, through page ids that are neither
        contiguous nor ascending: the same bytes as through ascending
        ones (a gather in another order would unmask the pad positions
        and mask real ones), and forward()'s logits at those positions."""
        rng = np.random.RandomState(7)
        toks = rng.randint(0, engine.cfg.vocab_size, size=10)
        # stale keys in every page: a wrong page is not a page of zeros
        junk = engine.pool.alloc(16)
        for lo in range(0, 16, 4):
            engine.prefill(list(rng.randint(0, 256, size=16)),
                           junk[lo:lo + 4])
        engine.pool.release(junk)
        straight = self._teacher_forced(engine, toks, 6, [0, 1, 2])
        scattered = self._teacher_forced(engine, toks, 6, [11, 3, 7])
        np.testing.assert_array_equal(scattered, straight)
        want = self._forward_logits(engine, toks)[5:]
        assert scattered.shape == want.shape == (5, engine.cfg.vocab_size)
        scale = float(np.max(np.abs(want)))
        # bf16: forward() attends with the flash path over the whole
        # sequence, the engine with float32 scores over cached pages
        assert float(np.max(np.abs(scattered - want))) / scale < 0.05

    def test_qk_norm_through_the_serving_programs_matches_forward(self):
        """The serving programs run the trainer's block, so they compute
        QK-norm where the config has it: a dense QK-norm config through
        ``prefill_with_cache`` and one ``decode_step_with_cache``, called
        directly (the engine refuses the config until its logits are held
        to the reference), against ``forward`` on the same tokens; and the
        norms are read, not skipped."""
        import dataclasses
        from functools import partial

        import jax
        import jax.numpy as jnp

        from ray_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.debug(), qk_norm=True)
        params = llama.init_params(cfg, jax.random.PRNGKey(3))
        rng = np.random.RandomState(5)
        for name in ("q_norm", "k_norm"):  # away from one: a skipped norm shows
            leaf = params["layers"][name]
            params["layers"][name] = leaf * jnp.asarray(
                rng.uniform(0.5, 1.5, leaf.shape), jnp.float32)
        ps, toks = 4, rng.randint(0, cfg.vocab_size, size=7).astype(np.int32)
        pages = np.asarray([5, 2], np.int32)

        def serve(params):
            store = jnp.zeros((cfg.n_layers, 8, ps, cfg.n_kv_heads,
                               cfg.head_dim), jnp.float32)
            padded = np.zeros((1, 2 * ps), np.int32)
            padded[0, :6] = toks[:6]
            k, v, pre, _ = jax.jit(partial(llama.prefill_with_cache, cfg))(
                params, store, store, padded, pages, np.asarray(5, np.int32))
            _, _, dec = jax.jit(partial(llama.decode_step_with_cache, cfg))(
                params, k, v, toks[6:7], np.asarray(6, np.int32), pages)
            return np.stack([np.asarray(pre), np.asarray(dec)])

        got = serve(params)
        want = np.asarray(jax.jit(partial(llama.forward, cfg))(
            params, jnp.asarray(toks[None])))[0, 5:]
        scale = float(np.max(np.abs(want)))
        assert float(np.max(np.abs(got - want))) / scale < 0.05
        plain = dict(params, layers=dict(
            params["layers"],
            q_norm=jnp.ones_like(params["layers"]["q_norm"])))
        assert float(np.max(np.abs(serve(plain) - got))) / scale > 0.05

    def test_alternating_sequences_do_not_touch_each_other(self, engine):
        """Two sequences decoded in turn: each one's logits are what it
        gives alone, and a decode changes exactly one slot of the store,
        its own sequence's write position."""
        rng = np.random.RandomState(11)
        ps = engine.page_size
        seqs = {"a": (rng.randint(0, 256, size=9), 5, [9, 2, 12]),
                "b": (rng.randint(0, 256, size=11), 7, [4, 14, 1])}
        alone = {}
        for name, (toks, n_prompt, pages) in seqs.items():
            alone[name] = self._teacher_forced(engine, toks, n_prompt, pages)
        # again, interleaved, on other pages
        seqs = {"a": seqs["a"][:2] + ([6, 13, 0],),
                "b": seqs["b"][:2] + ([10, 5, 8],)}
        got = {}
        for name, (toks, n_prompt, pages) in seqs.items():
            got[name] = [engine.prefill([int(t) for t in toks[:n_prompt]],
                                        pages[:pages_for(n_prompt, ps)])]
        for step in range(4):
            for name, (toks, n_prompt, pages) in seqs.items():
                pos = n_prompt + step
                before = self._store(engine)
                got[name].append(engine.decode(
                    pos, int(toks[pos]), pages[:pages_for(pos + 1, ps)]))
                after = self._store(engine)
                changed = before != after
                assert changed[:, :, pages[pos // ps], pos % ps].any()
                changed[:, :, pages[pos // ps], pos % ps] = False
                assert not changed.any()
        for name in seqs:
            np.testing.assert_array_equal(np.stack(got[name]), alone[name])

    def test_prefix_hit_copies_the_partial_tail_page(self, engine):
        """A prefix hit on a prompt that ends inside a page: the second
        sequence decodes into a copy of the tail page, made by
        ``copy_page``, and the entry's own pages keep their bytes."""
        import json as _json

        copies = []
        inner = engine.copy_page
        engine.copy_page = lambda src, dst: (copies.append((src, dst)),
                                             inner(src, dst))[1]
        sched = DecodeScheduler(engine)
        req = {"prompt": [2, 7, 1, 8, 2, 8], "max_tokens": 5}  # 4 + 2
        cold = _json.loads(_run_all(sched, [("c", req)])["c"][-1][1])
        entry = engine.prefix_cache._entries[tuple(req["prompt"])]
        before = self._store(engine)[:, :, entry.pages]
        assert len(copies) == 1
        warm = _json.loads(_run_all(sched, [("w", req)])["w"][-1][1])
        assert warm["cached_prefix"] is True
        assert warm["tokens"] == cold["tokens"]
        assert engine.prefill_calls == 1
        assert len(copies) == 2 and copies[1][0] == entry.pages[1] \
            and copies[1][1] not in entry.pages
        np.testing.assert_array_equal(
            self._store(engine)[:, :, entry.pages], before)

    def test_second_round_compiles_nothing(self, engine):
        """The serving benchmark's in-window rule: once each page count
        has been prefilled and decoded and one page copied, other tokens,
        positions and page ids ask for no compilation (a cache hit is a
        request too), whatever integer types the caller passes."""
        from ray_tpu.util.device_telemetry import (install_jax_listeners,
                                                   process_device_report)

        assert install_jax_listeners()

        def requests():
            rep = process_device_report()
            return rep["cache_hits"] + rep["cache_misses"]

        ps = engine.page_size
        start = requests()
        for n in (1, 2, 3):
            engine.prefill([1] * (n * ps), list(range(n)))
            engine.decode(n * ps - 1, 1, list(range(n)))
        engine.copy_page(0, 1)
        warm = requests()
        assert warm > start  # the counter sees this engine's programs
        rng = np.random.RandomState(3)
        for n in (3, 1, 2):
            pages = [int(p) for p in rng.permutation(16)[:n]]
            engine.prefill(list(rng.randint(0, 256, size=n * ps - 2)),
                           pages)
            engine.decode(np.int64(n * ps - 2), np.int32(5), pages)
            engine.decode(n * ps - 1, 9, tuple(pages))
        engine.copy_page(np.int64(5), 3)
        assert requests() == warm

    def test_failed_call_leaves_the_store_usable(self, engine):
        """The scheduler fails one request and goes on: a call that
        raises, in the wrapper's own checks or from the program's call,
        has not given the stores away."""
        toks = [5, 3, 9, 1, 4, 8, 2]
        want = self._teacher_forced(engine, toks, 5, [3, 1])
        with pytest.raises(ValueError):
            engine.prefill(toks, [2])  # seven tokens, one page of four
        with pytest.raises(ValueError):
            engine.decode(8, 1, [2, 0])  # position 8 of 8
        jitted = engine._prefill_fn, engine._decode_fn

        def boom(*_a, **_kw):
            raise RuntimeError("injected")

        engine._prefill_fn = engine._decode_fn = boom
        try:
            with pytest.raises(RuntimeError):
                engine.prefill(toks[:5], [6, 7])
            with pytest.raises(RuntimeError):
                engine.decode(5, toks[5], [3, 1])
        finally:
            engine._prefill_fn, engine._decode_fn = jitted
        assert not any(pages.is_deleted() for pages in engine.stores)
        np.testing.assert_array_equal(
            self._teacher_forced(engine, toks, 5, [6, 7]), want)

    def test_prefill_and_decode_are_taken_apart_into_spans(self, engine):
        """The engine's calls as flight-recorder spans: the device
        program and the host copies on either side of it. The three
        prefill parts lie inside the scheduler's ``serve.prefill`` and
        sum to no more than it; the three decode parts lie inside
        ``engine.decode``; all carry the call's page count."""
        from ray_tpu.util import flight_recorder as fr

        saved_on, saved_min = fr._on[0], fr._min_dur[0]
        fr.reset_for_tests()
        fr.configure(enabled=True, min_span_us=0.0)
        try:
            prompt = [3, 1, 4, 1, 5, 9]  # two pages of four
            _run_all(DecodeScheduler(engine, deployment="lm"),
                     [("c", {"prompt": prompt, "max_tokens": 3})])
            events = fr.build_span_events([fr.snapshot_payload()])
        finally:
            fr.reset_for_tests()
            fr._on[0], fr._min_dur[0] = saved_on, saved_min
        spans = {}  # name -> [(start s, duration s, tags)]
        for e in events:
            tags = {k: v for k, v in e["args"].items() if k != "source"}
            spans.setdefault(e["name"], []).append(
                (e["ts"] / 1e6, e["dur"] / 1e6, tags))

        def inside(inner, outer):
            return outer[0] - 1e-5 <= inner[0] \
                and inner[0] + inner[1] <= outer[0] + outer[1] + 1e-5

        (whole,) = spans["serve.prefill"]
        assert whole[2] == {"deployment": "lm", "corr": "c"}
        parts = [spans[f"engine.prefill_{p}"]
                 for p in ("program", "kv", "logits")]
        assert all(len(p) == 1 and inside(p[0], whole)
                   and p[0][2] == {"pages": 2} for p in parts)
        assert sum(p[0][1] for p in parts) <= whole[1]
        # in order, none overlapping the next
        assert parts[0][0][0] + parts[0][0][1] <= parts[1][0][0] + 1e-5
        assert parts[1][0][0] + parts[1][0][1] <= parts[2][0][0] + 1e-5
        calls = spans["engine.decode"]
        assert len(calls) == 2 == engine.decode_calls  # tokens 2 and 3
        for name in ("upload", "program", "readback"):
            got = spans[f"engine.decode_{name}"]
            assert len(got) == 2
            assert all(inside(g, c) and g[2] == c[2] == {"pages": 2}
                       for g, c in zip(got, calls))

    @pytest.mark.parametrize("pattern", ["", "FWWW"])
    def test_the_constructor_is_taken_apart_into_spans(self, pattern):
        """``engine.build`` is the constructor, entry to return; the
        weights (given or made) and one ``engine.stores`` a store KIND of
        ``served_stores`` lie inside it, in that order, none overlapping;
        recorder off, the constructor records nothing."""
        import dataclasses

        from ray_tpu.models.llama import (LlamaConfig, LlamaDecodeEngine,
                                          served_stores)
        from ray_tpu.util import flight_recorder as fr

        cfg = LlamaConfig.debug()
        if pattern:  # full and window layers: two kinds, a store by slot
            cfg = dataclasses.replace(
                cfg, n_layers=4, layer_pattern=pattern, window=6,
                num_experts=4, experts_per_token=2, mlp_act="reglu",
                remat=False)
        saved_on, saved_min = fr._on[0], fr._min_dur[0]
        fr.configure(enabled=False)
        fr.reset_for_tests()
        try:
            LlamaDecodeEngine(cfg, n_pages=16, page_size=4, seed=0)
            assert fr.snapshot_payload()["events"] == []
            fr.configure(enabled=True, min_span_us=0.0)
            LlamaDecodeEngine(cfg, n_pages=16, page_size=4, seed=0)
            events = fr.build_span_events([fr.snapshot_payload()])
        finally:
            fr.reset_for_tests()
            fr._on[0], fr._min_dur[0] = saved_on, saved_min
        spans = {}
        for e in events:
            spans.setdefault(e["name"], []).append(
                (e["ts"], e["ts"] + e["dur"], e["args"].get("kind")))
        (b0, b1, _), = spans["engine.build"]
        (w0, w1, _), = spans["engine.weights"]
        stores = sorted(spans["engine.stores"])
        kinds = list(dict.fromkeys(s.kind for s in served_stores(cfg)))
        assert [k for _, _, k in stores] == kinds
        assert len(kinds) == (2 if pattern else 1)
        inner = [(w0, w1)] + [(a, b) for a, b, _ in stores]
        assert b0 - 10 <= inner[0][0] and inner[-1][1] <= b1 + 10
        for (_, end), (start, _) in zip(inner, inner[1:]):
            assert end <= start + 10  # microseconds

    # ---- the weights in the dtype they are multiplied in (PR 27)

    MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

    @staticmethod
    def _float32_tree(cfg, seed=0):
        import jax

        from ray_tpu.models.llama import init_params

        return jax.jit(lambda key: init_params(cfg, key))(
            jax.random.PRNGKey(seed))

    @classmethod
    def _matmul_leaves(cls, params):
        found = {k: params["layers"][k] for k in cls.MATMUL_LEAVES}
        found.update({k: params[k] for k in ("embedding", "lm_head")
                      if k in params})
        return found

    @staticmethod
    def _programs(engine):
        """The engine's two programs as it jits them, and arguments for
        one call of each on the engine's own stores (not donated here):
        a prefill of two pages, a decode of the position after it."""
        import jax
        import jax.numpy as jnp
        from functools import partial

        from ray_tpu.models.llama import (decode_step_with_cache,
                                          prefill_with_cache)

        toks = np.random.RandomState(5).randint(
            0, engine.cfg.vocab_size, size=(1, 8)).astype(np.int32)
        # the decode reads what a prefill wrote, not pages of zeros
        engine.prefill([int(t) for t in toks[0, :7]], [9, 4])
        stores = tuple(jnp.array(pages) for pages in engine.stores)
        return {
            "prefill": (jax.jit(partial(prefill_with_cache, engine.cfg)),
                        stores + (toks, np.asarray([6, 2], np.int32),
                                  np.asarray(6, np.int32))),
            "decode": (jax.jit(partial(decode_step_with_cache, engine.cfg)),
                       stores + (np.asarray([17], np.int32),
                                 np.asarray(7, np.int32),
                                 np.asarray([9, 4], np.int32)))}

    @pytest.mark.parametrize("kind", ["prefill", "decode"])
    def test_converted_tree_gives_the_float32_trees_result(self, engine,
                                                           kind):
        """Moving the conversion out of the call changes no operand of
        any product: the float32 tree (converted in front of every
        product, inside the call) and the engine's tree (converted once)
        give the same logits and the same page stores, bit for bit."""
        fn, args = self._programs(engine)[kind]
        import jax

        # (k_pages, v_pages), the logits (and a dense prefill's no shares)
        want = jax.tree.leaves(fn(self._float32_tree(engine.cfg), *args))
        got = jax.tree.leaves(fn(engine.params, *args))
        assert len(got) == len(want) == 3
        for g, w in zip(got, want):
            assert g.dtype == w.dtype == np.float32
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert np.abs(np.asarray(got[2])).max() > 0

    def test_engine_holds_matmul_weights_in_compute_dtype(self, engine):
        """By name: every leaf the programs multiply is ``cfg.dtype`` and
        is the float32 leaf rounded once; every norm is float32 (the
        stacked norms are two-dimensional too); the gauge reads the
        tree's bytes by dtype."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.util.metrics import registry

        full = self._float32_tree(engine.cfg)
        assert jax.tree.structure(engine.params) == jax.tree.structure(full)
        matmul = self._matmul_leaves(engine.params)
        assert len(matmul) == 9  # debug() unties the head
        for name, leaf in matmul.items():
            assert leaf.dtype == jnp.bfloat16 == engine.cfg.dtype, name
            np.testing.assert_array_equal(
                np.asarray(leaf.astype(jnp.float32)),
                np.asarray(self._matmul_leaves(full)[name].astype(
                    jnp.bfloat16).astype(jnp.float32)))
        norms = [engine.params["final_norm"],
                 engine.params["layers"]["attn_norm"],
                 engine.params["layers"]["mlp_norm"]]
        assert all(n.dtype == jnp.float32 for n in norms)
        assert len(jax.tree.leaves(engine.params)) == len(matmul) + 3
        got = registry().local_values("ray_tpu_serve_engine_weight_bytes")
        assert got[(("dtype", "bfloat16"),)] \
            == sum(leaf.nbytes for leaf in matmul.values()) \
            == 2 * (engine.cfg.num_params()
                    - sum(n.size for n in norms))
        assert got[(("dtype", "float32"),)] \
            == sum(n.nbytes for n in norms)

    @pytest.mark.parametrize("given", ["float32", "bfloat16", "mixed"])
    def test_given_tree_is_converted_once_or_passed_through(self, given):
        """``params=``: float32 matmul leaves are converted and not kept,
        leaves already in ``cfg.dtype`` and the norms are the very arrays
        that were passed."""
        import jax.numpy as jnp

        from ray_tpu.models.llama import LlamaConfig, LlamaDecodeEngine

        cfg = LlamaConfig.debug()
        tree = self._float32_tree(cfg, seed=3)
        if given != "float32":
            ready = LlamaDecodeEngine(cfg, tree, n_pages=4,
                                      page_size=4).params
            if given == "mixed":
                ready = dict(ready, lm_head=tree["lm_head"], layers=dict(
                    ready["layers"], wk=tree["layers"]["wk"]))
            tree = ready
        engine = LlamaDecodeEngine(cfg, tree, n_pages=4, page_size=4)
        if given == "bfloat16":
            assert engine.params is tree
        was, now = self._matmul_leaves(tree), \
            self._matmul_leaves(engine.params)
        for name in was:
            assert now[name].dtype == jnp.bfloat16, name
            if was[name].dtype == jnp.bfloat16:
                assert now[name] is was[name], name
            else:
                np.testing.assert_array_equal(
                    np.asarray(now[name].astype(jnp.float32)),
                    np.asarray(was[name].astype(jnp.bfloat16).astype(
                        jnp.float32)))
        for name in ("attn_norm", "mlp_norm"):
            assert engine.params["layers"][name] is tree["layers"][name]
        assert engine.params["final_norm"] is tree["final_norm"]
        assert engine.prefill([1, 2, 3], [0]).shape == (cfg.vocab_size,)

    def test_float32_config_converts_nothing(self):
        """The engine decides from what it observes, a leaf's name and
        dtype against ``cfg.dtype``: at ``dtype=float32`` the tree is the
        one that was given and the gauge reads no bfloat16 byte."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        from ray_tpu.models.llama import LlamaConfig, LlamaDecodeEngine
        from ray_tpu.util.metrics import registry

        cfg = dataclasses.replace(LlamaConfig.debug(), dtype=jnp.float32)
        tree = self._float32_tree(cfg)
        engine = LlamaDecodeEngine(cfg, tree, n_pages=4, page_size=4)
        assert engine.params is tree
        built = LlamaDecodeEngine(cfg, n_pages=4, page_size=4, seed=0)
        for got, want in zip(jax.tree.leaves(built.params),
                             jax.tree.leaves(tree)):
            assert got.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        got = registry().local_values("ray_tpu_serve_engine_weight_bytes")
        assert got == {(("dtype", "float32"),): 4.0 * cfg.num_params(),
                       (("dtype", "bfloat16"),): 0.0}

    @pytest.mark.parametrize("kind", ["prefill", "decode"])
    def test_no_weight_is_converted_inside_a_call(self, engine, kind):
        """The lowered program for the engine's own ``params`` holds no
        float32 tensor of any matmul weight's shape, stacked or one
        layer's: nothing is left to convert in a call. The float32 tree's
        program holds them all (the check can see what it looks for)."""
        fn, args = self._programs(engine)[kind]

        def f32_weights_in(params):
            text = fn.lower(params, *args).as_text()
            shapes = set()
            for leaf in self._matmul_leaves(params).values():
                shapes.add(leaf.shape)
                shapes.add(leaf.shape[1:] if leaf.ndim == 3 else leaf.shape)
            return {s for s in shapes
                    if f"tensor<{'x'.join(map(str, s))}xf32>" in text}

        assert f32_weights_in(engine.params) == set()
        assert len(f32_weights_in(self._float32_tree(engine.cfg))) >= 9

    def test_reference_reads_the_converted_tree(self, engine):
        """The benchmark's float32 reference (it casts every leaf up
        itself) runs on ``engine.params``, the weights the engine
        multiplies, and the engine's teacher-forced rows stay within the
        serving configuration's tolerance of it."""
        import json
        import os
        from functools import partial

        import jax

        import benchmarks
        from benchmarks.reference.llama_decoder import logits_one

        with open(os.path.join(os.path.dirname(benchmarks.__file__),
                               "configs", "internlm2-1.8b.json")) as f:
            tol = json.load(f)["correct"]["serve_logits_rel_tol"]
        c = engine.cfg
        file_cfg = {"num_attention_heads": c.n_heads,
                    "num_key_value_heads": c.n_kv_heads,
                    "head_dim": c.head_dim, "rms_norm_eps": c.norm_eps,
                    "rope_theta": c.rope_theta,
                    "tie_word_embeddings": c.tie_embeddings}
        toks = np.random.RandomState(13).randint(
            0, c.vocab_size, size=10).astype(np.int32)
        want = np.asarray(jax.jit(partial(logits_one, file_cfg))(
            engine.params, toks))[5:]
        assert want.dtype == np.float32
        got = self._teacher_forced(engine, toks, 6, [5, 0, 8])
        rel = np.max(np.abs(got - want), axis=-1) / np.max(np.abs(want))
        assert rel.shape == (5,) and float(rel.max()) < tol
