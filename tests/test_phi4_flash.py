"""Phi-4-mini-flash-reasoning's layers through the program: a
decoder-hybrid-decoder (Mamba-1 "m", differential attention over a window "w"
and over everything "f", gated memory units "g" and cross attention "c",
which KEEP NOTHING and read what the layers in front handed on), the
published pattern whole at small widths on the CPU against the plain
reference (``benchmarks/reference/phi4flash_decoder.py``: all layers over all
positions, the recurrence a position a step), seeded weights."""

import dataclasses
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import phi4flash_decoder as ref
from jitted import (assert_served_alike, init_params, reference,
                    walked_both_ways)
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import s6
from ray_tpu.util.metrics import registry

CELL = "serve-phi4miniflash-prefill-open"
PAGE = 8
REAL = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "Phi-4-mini-flash-reasoning.json"))
FIELDS = REAL["program"]["fields"]
# the file's keys at test widths: ALL 32 layers of the published pattern, 4
# query heads of 16 on 2 (two query pairs on one key pair), 128 channels of
# 16 states, a window of 8 on pages of 8
FILE = dict(
    REAL, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=96, vocab_size=128, sliding_window=8,
    mamba_dt_rank=8, max_model_len=64)
SOUND, SHOWS = 5e-5, 5e-3


def program_cfg(dtype=jnp.float32, **file_keys):
    """The program's config from the file's keys, as the harness maps them."""
    file = dict(FILE, **file_keys)
    return dataclasses.replace(
        LlamaConfig(**{field: file[key] for field, key in FIELDS.items()}),
        dtype=dtype, remat=False)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every norm's gain and bias, the sub-norm and the
    skip off their start, so that one left out or swapped shows."""
    p = init_params(program_cfg(), jax.random.PRNGKey(7))
    layers = {name: dict(tree) for name, tree in p["layers"].items()}
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 40))
    for tree in layers.values():
        for leaf in ("attn_norm", "attn_norm_b", "mlp_norm", "mlp_norm_b",
                     "sub_norm", "D"):
            if leaf in tree:
                tree[leaf] = tree[leaf] + 0.2 * jax.random.normal(
                    next(keys), tree[leaf].shape)
    return dict(p, layers=layers, **{
        leaf: p[leaf] + 0.2 * jax.random.normal(next(keys), (64,))
        for leaf in ("final_norm", "final_norm_b")})


def off(got, want) -> float:
    """Largest difference over the reference's largest value."""
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want)))
                 / jnp.max(jnp.abs(jnp.asarray(want))))


def logits_one(params, toks, file=FILE, **wrong):
    return reference(lambda p, t: ref.logits_one(file, p, t, **wrong),
                     params, jnp.asarray(toks))


def engine_of(params, cfg=None, n_pages=24):
    return llama.LlamaDecodeEngine(cfg or program_cfg(), params,
                                   n_pages=n_pages, page_size=PAGE)


def through_pages(engine, toks, n, spoil=None):
    """``toks[:n]`` prefilled and the rest decoded through pages, a row of
    logits each; ``spoil(engine)`` between the two."""
    pages = engine.pool.alloc(-(-len(toks) // PAGE))
    got = [engine.prefill([int(t) for t in toks[:n]], pages[:-(-n // PAGE)])]
    if spoil:
        spoil(engine)
    for j in range(n, len(toks)):
        got.append(engine.decode(j, int(toks[j]), pages[:j // PAGE + 1]))
    return np.stack(got)


TOKS = np.random.RandomState(3).randint(0, 128, size=34)
N = 21  # a prompt of three pages less three; thirteen decoded positions


# --- (a) the whole model through the engine ---------------------------------- #


def test_the_pattern_is_the_published_one():
    cfg = program_cfg()
    assert cfg.kinds == "mw" * 8 + "mf" + "gc" * 7 == REAL["layer_pattern"]
    assert cfg.layer_types == REAL["layer_types"] and cfg.mb_per_layer == 2
    assert llama._serve_segments(cfg) == [
        ("mw", 8, True), ("mf", 1, False), ("gc", 7, True)]
    assert llama.traced_layers(cfg) == 6 and llama.stream_cut(cfg) == 18
    assert [round(v, 4) for v in cfg.lambda_init("wf")[:2]] == [0.3555, 0.5561]
    assert len(set(cfg.lambda_init("c"))) == 7


def test_engine_is_the_references_logits(params, monkeypatch):
    """Prefill into pages (the stream cut behind layer 17), then decode
    across two page boundaries and past the window's slots (a window of 8 on
    pages of 8: the slots of the first pages are left behind), against the
    reference's full forward; the walker's scanned stretches against every
    layer in line."""
    served, in_line = walked_both_ways(lambda: engine_of(params), TOKS, N,
                                       monkeypatch)
    want = logits_one(params, TOKS)[N - 1:]
    assert off(served["logits"], want) < SOUND
    assert (served["traced"], in_line["traced"]) == (6, 32)
    assert_served_alike(served, in_line)


def test_what_the_engine_keeps_and_counts(params):
    """Six stores, none of a "g" or "c" layer; the cut's gauge reads 18 and
    14; a state a SEQUENCE under ``state_bytes``, a row a position under
    ``page_bytes``."""
    def gauge(name):
        return {k[0][1]: v for k, v in registry().local_values(name).items()}

    cfg = program_cfg()
    engine = engine_of(params)
    assert [(s.kind, s.tag, s.layers, s.row, s.table)
            for s in llama.served_stores(cfg)] == [
        ("m", "s6_state", 9, (128, 16), "state"),
        ("m", "s6_conv", 9, (3, 128), "state"),
        ("w", "memory_window", 8, (2, 16), "slot"),
        ("w", "memory_window", 8, (2, 16), "slot"),
        ("f", "memory_full", 1, (2, 16), "page"),
        ("f", "memory_full", 1, (2, 16), "page")]
    assert engine.n_slots == min(24, 3 * (2 + 2))
    assert gauge("ray_tpu_serve_engine_prefill_layers") == {
        "all": 18.0, "one": 14.0}
    state, page = (gauge(f"ray_tpu_serve_engine_{n}_bytes")
                   for n in ("state", "page"))
    assert state["s6_state"] == 4.0 * 9 * 128 * 16
    assert state["s6_conv"] == 4.0 * 9 * 3 * 128
    assert page["memory_window"] == 2 * 4.0 * 8 * 32
    assert page["memory_full"] == 2 * 4.0 * 32
    dense = llama.LlamaDecodeEngine(LlamaConfig.debug(), n_pages=4,
                                    page_size=4)
    assert gauge("ray_tpu_serve_engine_prefill_layers") == {
        "all": float(dense.cfg.n_layers), "one": 0.0}


def test_the_first_layers_alone_are_served(params):
    """``m w``, the cell's rehearsal: a part of the family, no cut."""
    cfg = program_cfg(num_hidden_layers=2)
    assert cfg.kinds == "mw" and llama.stream_cut(cfg) == 2
    few = init_params(cfg, jax.random.PRNGKey(5))
    file = dict(FILE, num_hidden_layers=2)
    got = through_pages(engine_of(few, cfg), TOKS[:27], N)
    assert off(got, logits_one(few, TOKS[:27], file)[N - 1:]) < SOUND


@pytest.mark.parametrize("pattern,why", [
    ("wfgc", "below every 'g'"), ("mwc", "below every 'c'"),
    ("wmcf", "below every 'c'"), ("mwF", "every built layer is")])
def test_a_pattern_whose_readers_have_nothing_to_read_is_refused(pattern,
                                                                 why):
    with pytest.raises(ValueError, match=why):
        program_cfg(layer_pattern=pattern, num_hidden_layers=len(pattern))


def test_the_full_forward_refuses_the_kinds_by_name(params):
    with pytest.raises(NotImplementedError, match="no 'm' layer yet"):
        llama.forward(program_cfg(), params, jnp.zeros((1, 8), jnp.int32))


# --- (b) the cut --------------------------------------------------------------- #


@pytest.mark.parametrize("n", [17, 24])
def test_the_cut_prefill_is_the_uncut_stack_at_last(params, n, monkeypatch):
    """A right-padded prompt's first-token logits with layers 18-31 run on
    position ``last`` alone EQUAL those of all 32 layers run over all
    positions (``stream_cut`` patched to the stack's depth: no cut), and the
    stores they write are the same."""
    cfg = program_cfg()
    layout = llama.served_stores(cfg)
    stores = [jnp.zeros(s.shape(4, 4, PAGE), jnp.float32) for s in layout]
    toks = np.zeros((1, 3 * PAGE), np.int32)
    toks[0, :n] = TOKS[:n]
    args = (toks, np.arange(3, dtype=np.int32), np.int32(n - 1),
            np.arange(2, dtype=np.int32) + 1)

    def run():
        return jax.jit(partial(llama.prefill_with_cache, cfg,
                               page_size=PAGE))(params, *stores, *args)

    *cut_stores, cut, _ = run()
    monkeypatch.setattr(llama, "stream_cut", lambda cfg: len(cfg.kinds))
    *stores_, uncut, _ = run()
    assert off(cut, uncut) < 1e-5
    assert off(cut, logits_one(params, TOKS[:n])[-1]) < SOUND
    for a, b in zip(cut_stores, stores_):
        np.testing.assert_allclose(a, b, atol=1e-6)


# --- (c) ops/s6.py and its kernel ---------------------------------------------- #


def _operands(T, seed=0, batch=1, D=1024, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    p = {"A_log": jnp.log(jax.random.uniform(k[0], (D, N), minval=0.25,
                                             maxval=4.0)),
         "dt_bias": jax.random.normal(k[1], (D,)) - 2.0,
         "D": 1.0 + 0.2 * jax.random.normal(k[2], (D,))}
    u, r = jax.random.normal(k[3], (2, batch, T, D))
    b_in, c_in = jax.random.normal(k[4], (2, batch, T, N))
    return u, r, b_in, c_in, p, jax.random.normal(k[5], (batch, D, N))


@pytest.mark.parametrize("case,T,last,rows", [
    ("a start state, last inside a block", 256, 170, 128),
    ("last the final position", 128, 127, 128),
    ("one position", 128, 0, 128)])
def test_s6_kernel_is_the_scan(case, T, last, rows):
    """``ops/s6_prefill.py`` interpreted against ``ops/s6.py scan``, from a
    START state, the positions behind ``last`` identity updates."""
    from ray_tpu.ops.s6_prefill import s6_prefill

    u, r, b_in, c_in, p, start = _operands(T)
    y, state = jax.jit(partial(s6_prefill, rows=rows, interpret=True))(
        u, r, b_in, c_in, p, start, jnp.int32(last))
    want_y, want = jax.jit(s6.scan)(u, r, b_in, c_in, p, start,
                                    jnp.int32(last))
    assert off(y[:, :last + 1], want_y[:, :last + 1]) < 1e-5
    assert off(state, want) < 1e-5


def test_s6_kernel_in_two_segments_is_one_call():
    """A prompt in two calls, the second from the first's state: one call
    (a chunked prefill can use the kernel as it stands)."""
    from ray_tpu.ops.s6_prefill import s6_prefill

    u, r, b_in, c_in, p, start = _operands(256, seed=1)
    run = jax.jit(partial(s6_prefill, rows=128, interpret=True))
    y, state = run(u, r, b_in, c_in, p, start, jnp.int32(255))
    y0, mid = run(u[:, :128], r[:, :128], b_in[:, :128], c_in[:, :128], p,
                  start, jnp.int32(127))
    y1, end = run(u[:, 128:], r[:, 128:], b_in[:, 128:], c_in[:, 128:], p,
                  mid, jnp.int32(127))
    assert off(jnp.concatenate([y0, y1], 1), y) < 1e-6
    assert off(end, state) < 1e-6


def test_s6_step_by_step_is_the_scan_and_the_references_recurrence():
    """The decode step run a token at a time (state and tail carried) is
    the scan over the positions, and both are the reference's mixer."""
    cfg = program_cfg()
    p = {w: a[0] for w, a in init_params(
        cfg, jax.random.PRNGKey(2))["layers"]["memory_mamba"].items()}
    a = jax.random.normal(jax.random.PRNGKey(3), (1, 19, 64))
    u, _ = s6.project_in(a, p["w_in"])

    def by_steps():
        def one(carry, row):
            y, state, tail = s6.step(row[:, None], p, *carry, jnp.float32)
            return (state, tail), y[:, 0]

        (state, _), ys = jax.lax.scan(
            one, (jnp.zeros((1, 128, 16)), jnp.zeros((1, 3, 128))),
            jnp.moveaxis(u, 1, 0))
        return jnp.moveaxis(ys, 0, 1), state

    y_step, state_step = jax.jit(by_steps)()
    y, state, _ = jax.jit(lambda: llama.attend_s6(cfg, None, u, p))()
    assert off(y_step, y) < SOUND and off(state_step, state[:, 0]) < SOUND
    stack = {w: v[None] for w, v in p.items()}
    _, want = reference(lambda: ref.mamba(FILE, a[0], stack, 0))
    assert off(y[0], want) < SOUND


def test_the_steered_kernel_path_is_the_scan_path(params, monkeypatch):
    """A prefill whose "m" layers take the kernel (steered, interpreted)
    gives the XLA path's logits, and the path is counted where the others
    are."""
    monkeypatch.setattr(llama, "_prefill_attend_taken", {})
    cfg = program_cfg(mamba_expand=16)  # 1,024 channels: a whole block
    p = init_params(cfg, jax.random.PRNGKey(4))
    toks = np.random.RandomState(5).randint(0, 128, size=128)
    want = engine_of(p, cfg, n_pages=40).prefill(list(toks), list(range(16)))
    monkeypatch.setattr(llama, "s6_prefill_path",
                        lambda cfg, u: ("kernel", "steered by a test"))
    got = engine_of(p, cfg, n_pages=40).prefill(list(toks), list(range(16)))
    assert off(got, want) < 1e-5
    paths = {r["path"]: r["calls"] for r in llama.prefill_attend_paths()
             if r["kind"] == "s6"}
    assert paths == {"scan": 2, "kernel": 2}  # "m" of "mw" x 8, "m" of "mf"


# --- (d) differential attention ------------------------------------------------- #


def _written_out(q, k, v, lam, window=0, at=None):
    """Two softmax maps over one value, written out: ``q`` [T, 4, 16], ``k``
    / ``v`` [S, 2, 16]; rows at positions ``at`` (default: their own)."""
    T, S = q.shape[0], k.shape[0]
    at = jnp.arange(T) if at is None else at
    ahead = at[:, None] - jnp.arange(S)[None, :]
    seen = (ahead >= 0) & ((ahead < window) if window else True)
    v = jnp.repeat(v.reshape(S, 1, 32), 2, axis=1)

    def one(q_j, k_j):
        s = jnp.einsum("qhd,khd->hqk", q_j, jnp.repeat(k_j, 2, axis=1)) / 4.0
        return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(
            jnp.where(seen[None], s, -jnp.inf), -1), v)

    return one(q[:, :2], k[:, :1]), one(q[:, 2:], k[:, 1:])


@pytest.mark.parametrize("kind", ["w", "f", "c", "c behind the cut",
                                  "decode, slots", "decode, pages"])
def test_differential_attention_is_two_softmax_maps(kind):
    cfg = program_cfg()
    T = 24
    q, k, v = (jax.random.normal(key, shape) for key, shape in zip(
        jax.random.split(jax.random.PRNGKey(6), 3),
        [(1, T, 4, 16), (1, T, 2, 16), (1, T, 2, 16)]))
    if kind in "wfc":
        got = jax.jit(lambda: llama.attend_diff_tiles(cfg, kind, q, k, v))()
        want = _written_out(q[0], k[0], v[0], 0, 8 if kind == "w" else 0)
    elif kind == "c behind the cut":
        got = jax.jit(lambda: llama.attend_cross(
            cfg, jnp.int32(17), q[:, 17:18], k, v))()
        want = _written_out(q[0, 17:18], k[0], v[0], 0, at=jnp.array([17]))
    else:  # the token at position 20 against views of 24 rows
        pos, base = 20, 8 if "slots" in kind else 0
        got = jax.jit(lambda: llama._attend_diff_cached(
            cfg, "test", k[0, base:], v[0, base:], pos - base,
            q[:, pos:pos + 1], k[:, pos:pos + 1], v[:, pos:pos + 1],
            lowest=(pos - 8 + 1 - base) if base else None))()
        want = _written_out(q[0, pos:pos + 1], k[0, :pos + 1], v[0, :pos + 1],
                            0, 8 if base else 0, at=jnp.array([pos]))
    for g, w in zip(got, want):
        assert off(g[0], w) < 1e-5


# --- (e) every fault shows ------------------------------------------------------- #


WRONG = {
    "skip D u dropped": dict(skip=False),
    "lam at 0": dict(lam=0.0),
    "lam0 one layer off": dict(depth_off=1),
    "the subtraction's norm left out": dict(sub_norm=False),
    "a window of 7": dict(window=7),
    "a window of 9": dict(window=9),
    "the memory of the state-space layer in front": dict(memory_back=1),
    "layer 15's keys for layer 17's": dict(cross_reads_window_keys=True),
    "the cross pass at position last - 1": dict(shift=1),
}


@pytest.mark.parametrize("wrong", list(WRONG))
def test_the_references_wrong_ways_are_wrong(params, wrong):
    """Each switch of the reference changes the logits by far more than the
    engine differs from the sound one: a fault of that kind would show."""
    toks = TOKS[:N + 3]
    assert off(logits_one(params, toks, **WRONG[wrong])[N - 1:],
               logits_one(params, toks)[N - 1:]) > SHOWS


def _stores(engine, tag):
    return [i for i, s in enumerate(llama.served_stores(engine.cfg))
            if s.tag == tag]


def _change(tag, fn):
    def spoil(engine):
        stores = list(engine.stores)
        for i in _stores(engine, tag):
            stores[i] = fn(stores[i])
        engine.stores = tuple(stores)
    return spoil


SPOILS = {
    "the state read from zeros": _change("s6_state", jnp.zeros_like),
    "the tail zeroed": _change("s6_conv", jnp.zeros_like),
    "the window's slots zeroed": _change("memory_window", jnp.zeros_like),
    "the one full layer's pages zeroed": _change("memory_full",
                                                 jnp.zeros_like),
}


@pytest.mark.parametrize("spoil", list(SPOILS))
def test_a_spoiled_store_shows(params, spoil):
    toks = TOKS[:N + 3]
    got = through_pages(engine_of(params), toks, N, SPOILS[spoil])
    want = logits_one(params, toks)[N - 1:]
    assert off(got[0], want[0]) < SOUND  # the prefill came before
    assert off(got[1:], want[1:]) > SHOWS


def test_bfloat16_where_the_file_says_float32_shows(params):
    """The float32 engine is within 5e-5 of the reference; the same weights
    served in bfloat16 are a hundred times as far."""
    got = through_pages(engine_of(params, program_cfg(jnp.bfloat16)),
                        TOKS[:N + 3], N)
    assert off(got, logits_one(params, TOKS[:N + 3])[N - 1:]) > SHOWS


# --- (f) the published size -------------------------------------------------------- #


def test_num_params_is_the_published_3_8b():
    cfg = LlamaConfig(**{field: REAL[key] for field, key in FIELDS.items()})
    assert cfg.num_params() == 3_852_562_944
    assert not REAL["reduced"] and cfg.kinds == REAL["layer_pattern"]
    tree = jax.eval_shape(lambda key: llama.init_params(cfg, key),
                          jax.random.PRNGKey(0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(tree)) \
        == cfg.num_params()
    axes = llama.param_logical_axes(cfg)
    assert jax.tree.structure(tree) == jax.tree.structure(
        axes, is_leaf=lambda a: isinstance(a, tuple))
    assert (cfg.s6_inner, cfg.s6_dt_rank, cfg.head_dim) == (5120, 160, 64)
