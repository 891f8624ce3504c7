"""Checks of the benchmark's own arithmetic. They run on the CPU in seconds:

    python -m pytest benchmarks/checks -q

They are not part of the repository's tier-1 tests (which collect ``tests/``).
"""

import json
import math
import os

import pytest

from benchmarks.lib import flops, reducers, spec, stats, trace, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = spec.load_benchmark()
MISTRAL = spec.load_config(BENCH, "mistral-7b-v0.3")
INTERN = spec.load_config(BENCH, "internlm2-1.8b")


# ---- FLOPs, operations and bytes against hand-worked values

def test_param_counts_by_hand():
    # Mistral-7B layer: q 4096*4096 + k,v 2*4096*1024 + o 4096*4096 = 41,943,040
    # mlp 3 * 4096 * 14336 = 176,160,768 -> 218,103,808
    assert flops.layer_matmul_params(MISTRAL) == 218_103_808
    # 2 layers + head 4096 * 32768 = 134,217,728
    assert flops.matmul_params(MISTRAL) == 570_425_344
    # + embedding 134,217,728 + norms 2*2*4096 + 4096
    assert flops.total_params(MISTRAL) == 704_663_552
    # InternLM2-1.8B layer: 2*2048*2048 + 2*2048*1024 + 3*2048*8192 = 62,914,560
    assert flops.layer_matmul_params(INTERN) == 62_914_560
    # 24 layers + 2 * 92544 * 2048 + norms 24*2*2048 + 2048
    assert flops.total_params(INTERN) == 1_889_110_016


def test_train_flops_per_token_by_hand():
    # 6 * 570,425,344 = 3,422,552,064; attention 6 * 2049 * 32 * 128 * 2
    # layers = 100,712,448 -> 3,523,264,512
    assert flops.train_flops_per_token(MISTRAL, 2048) == 3_523_264_512
    # the head's share of the matmul FLOPs with two layers
    head = 4096 * 32768
    assert round(100 * head / flops.matmul_params(MISTRAL), 1) == 23.5


def test_flash_ops_and_bytes_by_hand():
    # B=8, T=2048, 32 heads on 8 kv heads, D=128, bf16
    pairs = 2048 * 2049 // 2                       # 2,098,176 unmasked pairs
    per_matmul = 2 * pairs * 128 * 8 * 32          # 137,506,062,336
    fwd = flops.flash_ops_bytes("fwd", 8, 2048, 32, 8, 128)
    assert fwd["ops"] == 2 * per_matmul
    q = 8 * 32 * 2048 * 128 * 2                    # 134,217,728 B
    kv = 8 * 8 * 2048 * 128 * 2                    # 33,554,432 B
    row = 8 * 32 * 2048 * 4                        # 2,097,152 B
    assert fwd["bytes"] == q + 2 * kv + q + row    # 337,641,472
    assert flops.flash_ops_bytes("dq", 8, 2048, 32, 8, 128)["ops"] == 3 * per_matmul
    dkv = flops.flash_ops_bytes("dkv", 8, 2048, 32, 8, 128)
    assert dkv["ops"] == 4 * per_matmul
    assert dkv["bytes"] == q + 2 * kv + q + 2 * row + 2 * (8 * 32 * 2048 * 128 * 4)
    # compute-bound on a v5e: 275.0 GFLOP / 197 TFLOP/s = 1.396 ms > 0.412 ms
    r = flops.roofline_seconds(fwd["ops"], fwd["bytes"], "TPU v5 lite")
    assert r["bound"] == "compute" and abs(r["seconds"] - 1.39600e-3) < 1e-7


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")


# ---- percentiles and open-loop lateness

def test_percentile_and_median():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 0.95) == pytest.approx(95.05)
    assert stats.median([3, 1, 2]) == 2
    assert stats.percentile([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_open_loop_counts_from_due_time():
    # a stall: the generator sent the second request 0.4 s late, and the
    # answer came 0.1 s after it was sent: the user waited 0.5 s
    lat = stats.open_loop_latencies(due=[0.0, 1.0], sent=[0.001, 1.4],
                                    first=[0.2, 1.5])
    assert lat["ttft"] == pytest.approx([0.2, 0.5])
    assert lat["lateness"] == pytest.approx([0.001, 0.4])


def test_p95_with_unanswered_requests():
    ttft = [float(i) for i in range(1, 96)]          # 95 answers
    assert stats.percentile_with_misses(ttft, 0.95, 0) == stats.percentile(ttft, 0.95)
    # 5 more never answered: rank 0.95 * 99 = 94.05 falls on the last answer
    assert stats.percentile_with_misses(ttft, 0.95, 5) == 95.0


# ---- the seeded schedule

def test_every_seed_replays_the_one_schedule_with_its_own_tokens():
    tr = spec.load_traffic("prefill-open-256-1024")
    n = traffic.request_count(tr, 50)
    assert n == 36
    a = traffic.RequestStream(tr, 92544, 11, n)
    b = traffic.RequestStream(tr, 92544, 3_000_000_019, n)  # beyond 32 signed bits
    assert a.sizes == b.sizes and sorted(a.sizes) != a.sizes
    assert sorted(p for p, _ in a.sizes) == traffic.stratified(tr["prompt_tokens"], n)
    assert min(p for p, _ in a.sizes) >= 256 and max(p for p, _ in a.sizes) <= 1024
    assert {o for _, o in a.sizes} == {4}
    # the same seed gives the same request, token for token
    assert a.request(5) == traffic.RequestStream(tr, 92544, 11, n).request(5)
    assert a.request(5)["prompt"] != b.request(5)["prompt"]
    assert len(a.request(5)["prompt"]) == a.sizes[5][0]
    # another schedule_seed is another order of the same sizes
    c = traffic.RequestStream(dict(tr, schedule_seed=1), 92544, 11, n)
    assert c.sizes != a.sizes and sorted(c.sizes) == sorted(a.sizes)


def test_arrivals_fill_the_window():
    tr = dict(spec.load_traffic("prefill-open-256-1024"), rate_per_s=3.0)
    one = traffic.arrival_times(tr, 40)
    assert len(one) == 120 and one[0] == 0.0
    assert all(x <= y for x, y in zip(one, one[1:])) and one[-1] < 40
    gaps = lambda d: sorted(round(y - x, 9) for x, y in zip(d, d[1:] + [40.0]))
    two = traffic.arrival_times(dict(tr, schedule_seed=1), 40)
    assert gaps(one) == gaps(two) and one != two
    # exponential gaps: the mean is 1/rate, the median ln 2 / rate
    g = gaps(one)
    assert sum(g) / len(g) == pytest.approx(1 / 3.0)
    assert g[len(g) // 2] == pytest.approx(math.log(2) / 3.0, rel=0.05)


# ---- the trace reduction, on a recorded step

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "train_step_trace.json")) as f:
        return json.load(f)


def test_reduce_recorded_step(recorded):
    d = trace.reduce(recorded["extracted"], recorded["host_spans"],
                     recorded["mono_at_open"])
    assert d["chips"] == 1
    assert d["window_s"] == pytest.approx(0.539011, abs=1e-6)
    assert 0.99 < d["busy_s"] / d["window_s"] <= 1.0
    # two layers: forward and its recomputation, dq, dkv each
    k = d["kernels"]
    assert (k["flash_fwd"]["calls"], k["flash_dq"]["calls"],
            k["flash_dkv"]["calls"]) == (4, 2, 2)
    assert k["flash_fwd"]["seconds"] == pytest.approx(0.013622, abs=1e-5)
    # self times: a loop is charged only what its children do not cover,
    # so the table sums to the busy time
    ops = d["device_ops"]
    assert len(ops) == 10 and not ops[0][0].startswith("while")
    full = trace.self_times(recorded["extracted"]["devices"][0]["ops"])
    assert sum(v[0] for v in full.values()) == pytest.approx(d["busy_s"], rel=1e-3)
    # the idle time is the step's sync on the host
    assert d["idle_gaps"][0][0] == "spmd.compute"
    assert sum(s for _, s in d["idle_gaps"]) <= d["window_s"] - d["busy_s"] + 1e-9


def test_roofline_reader_on_recorded_step(recorded):
    d = trace.reduce(recorded["extracted"])
    ev = {"trace": d, "config": MISTRAL, "device_kind": "TPU v5 lite",
          "traffic": spec.load_traffic("train-steps-8x2048")}
    share = reducers.read_metric(
        spec.load_layer_metric("flash_roofline"), ev)
    # least: 2 layers x (2 x 1.396 + 2.094 + 2.792) ms = 15.36 ms of 32.37 ms
    assert share == pytest.approx(47.4, abs=0.3)
    assert share <= 100


def test_trace_arithmetic_small():
    ops = [["outer", 0.0, 10.0, ""], ["a", 1.0, 2.0, "k"], ["b", 4.0, 3.0, ""],
           ["late", 12.0, 1.0, ""]]
    st = trace.self_times(ops)
    assert st["outer"][0] == pytest.approx(5.0) and st["a"] == [2.0, 1]
    assert trace.merge_intervals([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    ex = {"markers": {}, "devices": [{"plane": "p", "ops": ops}]}
    d = trace.reduce(ex, [["host.busy", 100.0 + 10.5, 1.0]], None)
    assert d["busy_s"] == pytest.approx(11.0) and d["window_s"] == pytest.approx(13.0)
    assert d["idle_gaps"] == [["(no span)", pytest.approx(2.0)]]
    assert d["kernels"]["k"] == {"seconds": 2.0, "calls": 1.0}
    assert trace.reduce({"markers": {}, "devices": []}) is None


def test_op_label():
    hlo = ("%fusion.3 = (bf16[8,256]{1,0:T(8,128)(2,1)S(1)}, f32[4]{0}) "
           "fusion(bf16[4096,32768]{1,0} %x), kind=kOutput")
    assert trace.op_label(hlo) == "fusion.3 (bf16[8,256], f32[4])"


# ---- span and counter readers

def test_span_readers():
    ev = {"spans": {"spmd.compute": [[0, 0.5], [1, 0.5]],
                    "spmd.ingest_wait": [[0.5, 0.01]],
                    "serve.prefill": [[0, 0.1], [1, 0.3], [2, 0.2]]},
          "counters": {"compile_s": 1.5}, "e2e": {}}
    rd = lambda name: reducers.read_metric(spec.load_layer_metric(name), ev)
    assert rd("train.ingest_wait_share") == pytest.approx(100 * 0.01 / 1.01)
    assert rd("serve.prefill_ms") == pytest.approx(200)
    assert rd("compile_s") == 1.5
    empty = dict(ev, spans={}, counters={})
    assert all(reducers.read_metric(spec.load_layer_metric(n), empty) is None
               for n in ("train.ingest_wait_share", "serve.prefill_ms",
                         "compile_s"))


def test_mfu_reader():
    ev = {"e2e": {"train_tokens_per_s_chip": 30000.0}, "config": MISTRAL,
          "traffic": {"seq": 2048}, "device_kind": "TPU v5 lite"}
    got = reducers.read_metric(spec.load_layer_metric("train.mfu"), ev)
    assert got == pytest.approx(100 * 3_523_264_512 * 30000 / 197e12)


# ---- every file BENCHMARK.json names is there and fits together

def test_benchmark_files_fit_together():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for cell in BENCH["workloads"]:
        b = spec.cell_bundle(cell["name"])
        assert b["traffic"]["kind"] in ("train_steps", "open_loop")
        names = {m["name"] for m in b["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert b["per_layer"], cell["name"]
        for m in b["per_layer"]:
            s = spec.load_layer_metric(m["name"])
            assert s["reader"] in reducers.READERS
            assert (s["layer"], s["unit"], s["moves"], s["source"]) == (
                m["layer"], m["unit"], m["moves"], m["source"])
            assert m["moves"] in names and m["moves"] in e2e


def test_serving_shapes():
    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    opn = shapes_of(spec.load_traffic("prefill-open-256-1024"), 128)
    assert opn == {"prefill": [2, 3, 4, 5, 6, 7, 8], "decode": [3, 4, 5, 6, 7, 8, 9]}
    assert check_prompt_len(opn, 128) == 382
    short = {"prompt_tokens": {"dist": "log_uniform", "min": 32, "max": 256},
             "output_tokens": {"dist": "log_uniform", "min": 32, "max": 128}}
    sat = shapes_of(short, 128)
    assert sat == {"prefill": [1, 2], "decode": [1, 2, 3]}
    assert check_prompt_len(sat, 128) == 126
