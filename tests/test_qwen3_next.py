"""Qwen3-Next-80B-A3B's layers through the program: gated delta-rule blocks
("D": a state and a convolution tail A SEQUENCE, by the table rule
``"state"``) beside gated full-attention blocks ("A": keys and values by
page id), each with a routed MLP, a held range of the router's experts and a
gated shared expert, all at small widths on the CPU against the plain
reference (``benchmarks/reference/qwen3_next_decoder.py``: the recurrence
token by token), seeded weights."""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import qwen3_next_decoder as ref
from jitted import forward, init_params, reference
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import gdn
from ray_tpu.ops.moe import routed_mlp

CELL = "serve-qwen3next-prefill-open"
PAGE = 8
# the file's keys at test widths: 4 of the router's 16 experts held, from 4;
# two whole periods of three delta layers and one attention layer
FILE = {
    "full_attention_interval": 4, "head_dim": 16, "hidden_size": 64,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 8, "max_position_embeddings": 128,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 8, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "vocab_size": 128,
    "layer_pattern": "DDDA" * 3, "router_experts": 16, "first_expert": 4,
}
REAL = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "Qwen3-Next-80B-A3B-Instruct.json"))
FIELDS = REAL["program"]["fields"]


def program_cfg(dtype=jnp.float32, **file_keys):
    """The program's config from the file's keys, as the harness maps them."""
    file = dict(FILE, **file_keys)
    return dataclasses.replace(
        LlamaConfig(**{field: file[key] for field, key in FIELDS.items()}),
        dtype=dtype, remat=False, lin_chunk=8)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every norm gain off its start, so that one left
    out, swapped or not zero-centred shows."""
    p = init_params(program_cfg(), jax.random.PRNGKey(7))
    layers = {name: dict(tree) for name, tree in p["layers"].items()}
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 16))
    for name, tree in layers.items():
        for leaf in ("attn_norm", "mlp_norm", "q_norm", "k_norm",
                     "gate_norm"):
            if leaf in tree:
                tree[leaf] = tree[leaf] + 0.2 * jax.random.normal(
                    next(keys), tree[leaf].shape)
    final = p["final_norm"] + 0.2 * jax.random.normal(next(keys), (64,))
    return dict(p, layers=layers, final_norm=final)


def off(got, want) -> float:
    """Largest difference over the reference's largest value."""
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want)))
                 / jnp.max(jnp.abs(jnp.asarray(want))))


def close(got, want, rtol=5e-5):
    assert off(got, want) < rtol, off(got, want)


def logits_one(params, toks, **wrong):
    return reference(lambda p, t: ref.logits_one(FILE, p, t, **wrong),
                     params, jnp.asarray(toks))


# --- (a) the whole model ----------------------------------------------------- #


def test_forward_is_the_references_logits(params):
    """Every position's logits, at a length that is no multiple of the
    chunk (8), against the token-by-token recurrence."""
    toks = np.random.RandomState(3).randint(0, 128, size=(2, 37))
    got = forward(program_cfg(), params, toks)
    for row in range(2):
        close(got[row], logits_one(params, toks[row]))


@pytest.mark.parametrize("wrong", [
    "decay", "l2norm", "conv", "norm_before_gate", "out_gate", "partial",
    "zero_centered", "shared_gate", "renormalised"])
def test_the_references_wrong_ways_are_wrong(params, wrong):
    """Each switch of the reference changes the logits by far more than the
    program differs from the sound one: a fault of that kind would show."""
    toks = np.random.RandomState(3).randint(0, 128, size=37)
    want = logits_one(params, toks)
    assert off(logits_one(params, toks, **{wrong: False}), want) > 5e-3
    assert off(logits_one(params, toks, beta_one=True), want) > 5e-3


# --- (b) the chunked form against the one-token step ------------------------- #


def _operands(T, seed=0, B=2, H=3, K=8, V=4):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    return (unit(jax.random.normal(k[0], (B, T, H, K))) * K ** -0.5,
            unit(jax.random.normal(k[1], (B, T, H, K))),
            jax.random.normal(k[2], (B, T, H, V)),
            -jax.nn.softplus(jax.random.normal(k[3], (B, T, H))),
            jax.nn.sigmoid(jax.random.normal(k[4], (B, T, H))),
            jax.random.normal(k[5], (B, H, K, V)))


@jax.jit
def _by_steps(q, k, v, g, beta, state):
    def step(state, row):
        o, state = gdn.gated_delta_step(*row, state)
        return state, o

    state, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


@pytest.mark.parametrize("T,last,with_state", [
    (32, None, False), (37, None, True), (5, None, True), (37, 20, True),
    (32, 31, False), (40, 0, True)])
def test_chunked_is_the_step_token_by_token(T, last, with_state):
    """Lengths that are and are not multiples of the chunk (8), shorter
    than it, from a given state, and with ``last`` short of the end: the
    positions after it change neither the state nor what came before."""
    q, k, v, g, beta, state0 = _operands(T)
    n = T if last is None else last + 1
    state0 = state0 if with_state else None
    o, state = jax.jit(gdn.gated_delta_chunked, static_argnums=5)(
        q, k, v, g, beta, 8, state0, None if last is None else jnp.int32(last))
    want_o, want_state = _by_steps(
        *(a[:, :n] for a in (q, k, v, g, beta)),
        jnp.zeros_like(_operands(T)[5]) if state0 is None else state0)
    close(o[:, :n], want_o, 1e-5)
    close(state, want_state, 1e-5)


# --- (c), (d) through the engine --------------------------------------------- #


def _engine(params, n_pages=24):
    return llama.LlamaDecodeEngine(program_cfg(), params, n_pages=n_pages,
                                   page_size=PAGE)


def test_unaligned_prefill_then_decode_across_a_page_boundary(params):
    """A prompt of 2 pages less two, then four decoded positions across the
    boundary with a growing table, as the harness's check walks it: the
    test that fails if the pad positions advance the state, if the
    convolution's tail is taken at the page's end, or if the state stays
    behind in the page the sequence leaves."""
    engine = _engine(params)
    n = 2 * PAGE - 2
    toks = np.random.RandomState(5).randint(0, 128, size=n + 4)
    want = logits_one(params, toks)
    pages = engine.pool.alloc(3)
    got = [engine.prefill([int(t) for t in toks[:n]], pages[:2])]
    for j in range(n, n + 4):
        got.append(engine.decode(j, int(toks[j]), pages[:j // PAGE + 1]))
    for j, row in enumerate(got):
        close(row, want[n - 1 + j], 1e-4)


def test_a_short_prompt_and_a_decode_from_position_zero(params):
    """A prompt shorter than the convolution (its tail holds zeros before
    the sequence's first row), and a decode at position 0 on a page that an
    EARLIER sequence left its state in: it starts from zeros."""
    engine = _engine(params)
    toks = np.random.RandomState(6).randint(0, 128, size=6)
    want = logits_one(params, toks)
    pages = engine.pool.alloc(1)
    engine.prefill([int(t) for t in toks], pages)   # leaves a state behind
    got = [engine.prefill([int(t) for t in toks[:2]], pages)]
    got += [engine.decode(j, int(toks[j]), pages) for j in range(2, 6)]
    for j, row in enumerate(got):
        close(row, want[1 + j], 1e-4)
    first = engine.decode(0, int(toks[0]), pages)
    close(first, want[0], 1e-4)


def test_two_sequences_in_turns_keep_their_states_apart(params):
    engine = _engine(params)
    rs = np.random.RandomState(7)
    seqs = [rs.randint(0, 128, size=n + 3) for n in (10, 13)]
    want = [logits_one(params, toks) for toks in seqs]
    tables = [engine.pool.alloc(2) for _ in seqs]
    got = [[engine.prefill([int(t) for t in toks[:-3]], table)]
           for toks, table in zip(seqs, tables)]
    for j in (3, 2, 1):  # a step of one, a step of the other
        for toks, table, rows in zip(seqs, tables, got):
            at = len(toks) - j
            rows.append(engine.decode(at, int(toks[at]), table))
    for toks, rows, ref_rows in zip(seqs, got, want):
        for j, row in enumerate(rows):
            close(row, ref_rows[len(toks) - 4 + j], 1e-4)


@pytest.mark.parametrize("n_prompt", [2 * PAGE - 3, 2 * PAGE])
def test_a_prefix_hit_shares_pages_and_never_a_written_state(params,
                                                             n_prompt):
    """The same prompt twice through the scheduler, once with a copied tail
    page (unaligned) and once page-aligned (no page is copied: the second
    sequence reads the shared last page's state and writes its own new
    page's). The second is a prefix hit (ONE prefill; its first token comes
    from the first's logits, the entry's very bytes), both decode to the
    reference's own continuation, and the first's continued decode is what
    it is alone."""
    import json

    from ray_tpu.serve.decode import DecodeScheduler

    prompt = [int(t) for t in np.random.RandomState(8).randint(
        0, 128, size=n_prompt)]
    toks = prompt + [0] * 6
    for at in range(n_prompt, n_prompt + 6):  # the reference's greedy
        # continuation: a position's logits read nothing behind it
        toks[at] = int(np.argmax(logits_one(params, np.asarray(toks))[at - 1]))

    def finals(twice: bool) -> dict:
        engine = _engine(params)
        sched = DecodeScheduler(engine, max_batch=4)
        sched.submit("a", {"prompt": prompt, "max_tokens": 6})
        # admitted, then two decode steps: a is two tokens ahead
        steps = [sched.step() for _ in range(3)]
        if twice:
            sched.submit("b", {"prompt": prompt, "max_tokens": 6})
        steps += [sched.step() for _ in range(8)]
        assert engine.prefill_calls == 1
        assert (engine.prefix_cache.hit_rate > 0) == twice
        return {corr: json.loads(payload) for replies, _ in steps
                for corr, kind, payload in replies if kind == "final"}

    both = finals(True)
    assert both["a"]["tokens"] == both["b"]["tokens"] == toks[n_prompt:]
    assert both["b"]["cached_prefix"] and not both["a"]["cached_prefix"]
    assert finals(False)["a"]["tokens"] == toks[n_prompt:]


# --- (e) the share ----------------------------------------------------------- #


def test_the_four_held_ranges_add_up_to_the_uncut_layer(params):
    """One chip's share of the routed MLP: over the four held ranges of the
    router's 16 experts, the shared expert counted ONCE, the parts add up
    to the layer that holds every expert."""
    p = jax.tree.map(lambda a: a[0], params["layers"]["delta"])
    rs = jax.random.split(jax.random.PRNGKey(11), 4)
    h = jax.random.normal(rs[0], (2, 9, 64))
    full = {w: jax.random.normal(k, (16, *p[w].shape[1:])) / 8
            for w, k in zip(("w_gate", "w_up", "w_down"), rs[1:])}
    shared = tuple(p[w] for w in ("w_sg", "ws_gate", "ws_up", "ws_down"))

    def part(held, with_shared):
        lo, n = held if held else (0, 16)
        return routed_mlp(
            h, p["router"], *(full[w][lo:lo + n] for w in (
                "w_gate", "w_up", "w_down")), top_k=3, norm_topk_prob=True,
            held=held, shared_gated=shared if with_shared else None)[0]

    whole = jax.jit(lambda: part(None, True))()
    parts = jax.jit(lambda: sum(part((lo, 4), lo == 0)
                                for lo in (0, 4, 8, 12)))()
    close(parts, whole, 1e-5)
    # and the uncut layer is the reference's with every expert held
    file = dict(FILE, num_experts=16, first_expert=0)
    stacked = jax.tree.map(lambda a: a[None], {**p, **full})
    want = reference(lambda m, q: ref.experts(
        file, m, ref.route(file, m, q, 0), q, 0)
        + ref.shared_expert(file, m, q, 0), h[0], stacked)
    close(whole[0], want, 1e-5)


def test_routed_half_is_the_references_mlp(params):
    """The block's routed half (held experts 4-7, the gated shared expert,
    the zero-centred norm) against the reference's, on one layer."""
    cfg = program_cfg()
    h = jax.random.normal(jax.random.PRNGKey(12), (1, 11, 64))
    got = jax.jit(lambda h, layers: llama._routed_half(
        cfg, h, layers, 1, ())[0])(h, params["layers"]["delta"])
    want = reference(lambda h, p: h + ref.mlp(FILE, h, p, 1), h[0],
                     params["layers"]["delta"])
    close(got[0], want)


# --- (f) the cut's parameters ------------------------------------------------ #


def test_num_params_counts_the_tree_and_the_file():
    cfg = program_cfg()
    tree = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(tree))
    assert jax.tree.structure(llama.param_logical_axes(cfg), is_leaf=lambda
                              x: isinstance(x, tuple)) == jax.tree.structure(
                                  tree)
    real = spec.program_config(spec.cell_bundle(CELL)["config"])
    delta = (2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128
             + 4096 * 2048)
    gated = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    routed = 2048 * 512 + 128 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048
    assert (delta, gated, routed) == (33_718_464, 27_263_488, 406_849_536)
    period = 3 * (delta + routed + 4096) + gated + routed + 4096
    assert period == 1_755_833_408
    assert real.kinds == "DDDADDDA" and real.full_attention_interval == 4
    assert real.num_params() == 2 * period + 2 * 37_984 * 2048 + 2048 \
        == 3_667_251_328
    whole = dataclasses.replace(real, n_layers=48, num_experts=512,
                                router_experts=0, vocab_size=151_936)
    assert whole.num_params() == 79_674_391_296


# --- (g) refusals ------------------------------------------------------------ #


def test_the_trainer_refuses_the_family_by_name():
    from ray_tpu.train.spmd import build_train_mesh, make_spmd_train_step

    with pytest.raises(NotImplementedError, match="'D' / 'A' layer"):
        make_spmd_train_step(program_cfg(),
                             build_train_mesh("", jax.devices()[:1]))


def test_the_engine_refuses_a_mix_and_a_part():
    mix = dataclasses.replace(
        program_cfg(), layer_pattern="DDDADDDA")
    llama.LlamaDecodeEngine(mix, n_pages=4, page_size=PAGE)  # the family
    with pytest.raises(NotImplementedError,
                       match=r"has A, of which the table lacks none; for "
                             r"the 'M' / 'E' / '\*' halves"):
        llama.LlamaDecodeEngine(
            dataclasses.replace(program_cfg(), layer_pattern="AAAAAAAA"),
            n_pages=4, page_size=PAGE)
    with pytest.raises(ValueError, match="every built layer is one of"):
        dataclasses.replace(program_cfg(), layer_pattern="DDDIDDDI")


# --- (h) the table rule ------------------------------------------------------ #


def _gauge(name):
    from ray_tpu.util.metrics import registry

    return {k[0][1]: v for k, v in registry().local_values(name).items()}


def test_the_state_goes_by_its_table_rule(params):
    """``served_stores`` / ``page_rows`` of the family, the engine's stores
    as the rule lays them out (ONE row a page), the two gauges (bytes a
    position apart from bytes a sequence), and ``copy_page`` copying the
    state with the page."""
    cfg = program_cfg()
    layout = llama.served_stores(cfg)
    assert [(s.kind, s.tag, s.layers, s.row, s.table) for s in layout] == [
        ("D", "state", 6, (4, 8, 8), "state"),
        ("D", "conv", 6, (3, 2 * 16 + 32), "state"),
        ("A", "gated", 2, (2, 16), "page"), ("A", "gated", 2, (2, 16), "page")]
    assert llama.page_rows(cfg) == ("delta", [(s.layers, s.row)
                                              for s in layout])
    assert llama.TABLES["state"] == ("page", 1)
    engine = _engine(params, n_pages=12)
    assert [s.shape for s in engine.stores] == [
        (6, 12, 1, 4, 8, 8), (6, 12, 1, 3, 64), (2, 12, PAGE, 2, 16),
        (2, 12, PAGE, 2, 16)]
    assert engine.n_slots == 0
    assert _gauge("ray_tpu_serve_engine_state_bytes") == {
        "state": 4.0 * 6 * 4 * 8 * 8, "conv": 4.0 * 6 * 3 * 64,
        "ssm_state": 0.0, "ssm_conv": 0.0, "s6_state": 0.0,
        "s6_conv": 0.0}  # every tag of the table, always
    page_bytes = _gauge("ray_tpu_serve_engine_page_bytes")
    assert page_bytes["gated"] == 2 * 4.0 * 2 * 2 * 16
    assert "state" not in page_bytes and "conv" not in page_bytes
    assert _gauge("ray_tpu_serve_engine_expert_groups")["layer"] == 4.0
    pages = engine.pool.alloc(3)
    engine.prefill(list(range(PAGE + 3)), pages[:2])
    state, conv, keys, _ = (np.asarray(s) for s in engine.stores)
    assert np.abs(state[:, pages[1]]).max() > 0
    assert np.abs(state[:, pages[0]]).max() == 0   # only the last page's row
    assert np.abs(state[:, pages[2]]).max() == 0
    engine.copy_page(pages[1], pages[2])
    after = [np.asarray(s) for s in engine.stores]
    for before, now in zip((state, conv, keys), after):
        np.testing.assert_array_equal(now[:, pages[2]], before[:, pages[1]])
    paths = [p for p in llama.prefill_attend_paths() if p["kind"] == "gated"]
    assert paths and all(p["path"] == "tiles" and "cpu" in p["reason"]
                         for p in paths)
    assert math.isclose(sum(_gauge(
        "ray_tpu_serve_moe_assignment_share").values()), 1.0, rel_tol=1e-6)


# --- the benchmark's files --------------------------------------------------- #


def test_benchmark_files_fit_together_with_the_new_cell():
    from benchmarks.checks import test_yardstick
    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    test_yardstick.test_benchmark_files_fit_together()
    bench = spec.load_benchmark()
    b = spec.cell_bundle(CELL)
    assert (b["cell"]["chips"], b["cell"]["traffic"], b["cell"]["config"]) \
        == (1, "prefill-open-6144-32000", "Qwen3-Next-80B-A3B-Instruct")
    assert b["cell"] in bench["workloads"]
    assert sorted(m["name"] for m in b["end_to_end"]) == [
        "setup_s", "ttft_p95_ms"]
    names = {m["name"] for m in b["per_layer"]}
    assert {"serve.decode_program_ms", "compile_s"} <= names
    assert "serve.window_slots_ms" not in names
    assert len([n for n in names if n.startswith("serve.")]) == 12
    tr, dep = b["traffic"], b["config"]["deployment"]
    assert (tr["kind"], tr["prompt_tokens"], tr["output_tokens"],
            tr["schedule_seed"], tr["trace_seconds"]) == (
        "open_loop", {"dist": "log_uniform", "min": 6144, "max": 32000},
        {"dist": "const", "value": 16}, 0, 11.0)
    other = spec.load_traffic("prefill-open-6400-32000")
    assert tr["schedule_why"] == other["schedule_why"]
    assert (dep["page_size"], dep["decode_max_batch"], dep["max_inflight"]) \
        == (2048, 4, 32)
    shapes = shapes_of(tr, dep["page_size"])
    assert shapes == {"prefill": list(range(3, 17)),
                      "decode": list(range(4, 17))}
    # the check: 8,190 tokens, three decodes across the page boundary
    assert check_prompt_len(shapes, dep["page_size"]) == 8190
    assert dep["n_pages"] >= dep["decode_max_batch"] * (shapes["decode"][-1]
                                                        + 1)
    assert spec.resolve(b["config"]["reference"] + ":logits_one")
    entry, = [c for c in bench["configs"]
              if c["name"] == b["cell"]["config"]]
    assert entry["reduced"] == b["config"]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert b["config"]["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    # the catalog's row, key for key, but for the three reduced
    row = {"decoder_sparse_step": 1, "full_attention_interval": 4,
           "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
           "linear_key_head_dim": 128, "linear_num_key_heads": 16,
           "linear_num_value_heads": 32, "linear_value_head_dim": 128,
           "max_position_embeddings": 262144, "mlp_only_layers": [],
           "model_type": "qwen3_next", "moe_intermediate_size": 512,
           "norm_topk_prob": True, "num_attention_heads": 16,
           "num_experts_per_tok": 10, "num_key_value_heads": 2,
           "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
           "rope_scaling": None, "rope_theta": 10000000,
           "shared_expert_intermediate_size": 512,
           "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: b["config"][k] for k in row} == row
