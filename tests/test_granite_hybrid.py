"""granite-4.0-h-micro's layers through the program: Mamba-2 blocks ("H": a
state and a convolution tail A SEQUENCE, by the table rule ``"state"``)
beside unrotated grouped-query attention blocks ("N": keys and values by
page id), a dense SwiGLU in every layer, four fixed multipliers, all at small
widths on the CPU against the plain reference
(``benchmarks/reference/granite_hybrid_decoder.py``: the recurrence token by
token), seeded weights."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import granite_hybrid_decoder as ref
from jitted import forward, init_params, reference
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import ssm

CELL = "serve-granite4hmicro-prefill-open"
PAGE = 8
REAL = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "granite-4.0-h-micro.json"))
FIELDS = REAL["program"]["fields"]
# the file's keys at test widths: the published pattern's first period and two
# layers of the second, 8 Mamba heads of 16 in 2 groups, state 16, chunks of
# 8; 4 query heads of 16 on 2
FILE = dict(
    REAL, hidden_size=64, num_hidden_layers=12, num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    mamba_n_groups=2, mamba_chunk_size=8, shared_intermediate_size=96,
    vocab_size=128, max_position_embeddings=256)
DIMS = dict(heads=8, head_dim=16, groups=2, state=16, chunk=8)


def program_cfg(dtype=jnp.float32, **file_keys):
    """The program's config from the file's keys, as the harness maps them."""
    file = dict(FILE, **file_keys)
    return dataclasses.replace(
        LlamaConfig(**{field: file[key] for field, key in FIELDS.items()}),
        dtype=dtype, remat=False)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every norm gain and the skip off their start, so
    that one left out or swapped shows."""
    p = init_params(program_cfg(), jax.random.PRNGKey(7))
    layers = {name: dict(tree) for name, tree in p["layers"].items()}
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 16))
    for tree in layers.values():
        for leaf in ("attn_norm", "mlp_norm", "gate_norm", "D"):
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


def logits_one(params, toks, file=FILE, **wrong):
    return reference(lambda p, t: ref.logits_one(file, p, t, **wrong),
                     params, jnp.asarray(toks))


# --- (a) the whole model ----------------------------------------------------- #


def test_forward_is_the_references_logits(params):
    """Every position's logits, at a length that is no multiple of the
    chunk (8), against the token-by-token recurrence."""
    cfg = program_cfg()
    assert cfg.kinds == "HHHHHNHHHHHH"
    toks = np.random.RandomState(3).randint(0, 128, size=(2, 37))
    got = forward(cfg, params, toks)
    for row in range(2):
        close(got[row], logits_one(params, toks[row]))


WRONG = {"conv_bias": False, "skip": False, "rotated": True,
         "attention_multiplier": 0.125, "residual_multiplier": 1.0,
         "embedding_multiplier": 1.0, "logits_scaling": 1.0}


@pytest.mark.parametrize("wrong", list(WRONG))
def test_the_references_wrong_ways_are_wrong(params, wrong):
    """Each switch of the reference changes the logits by far more than the
    program differs from the sound one: a fault of that kind would show."""
    toks = np.random.RandomState(3).randint(0, 128, size=37)
    assert off(logits_one(params, toks, **{wrong: WRONG[wrong]}),
               logits_one(params, toks)) > 5e-3


@pytest.mark.parametrize("field,left_out", [
    ("embedding_multiplier", 1.0), ("attention_multiplier", 0.0),
    ("residual_multiplier", 1.0), ("logit_scale", 1.0)])
def test_each_multiplier_moves_the_logits(params, field, left_out):
    """The program with ONE multiplier left out (at its neutral value) is
    off the reference by far more than rounding: every one of the four is
    read where the equations have it."""
    toks = np.random.RandomState(4).randint(0, 128, size=(1, 29))
    cfg = program_cfg()
    assert getattr(cfg, field) != left_out
    want = logits_one(params, toks[0])
    close(forward(cfg, params, toks)[0], want)
    without = dataclasses.replace(cfg, **{field: left_out})
    assert off(forward(without, params, toks)[0], want) > 5e-3


# --- (b) ops/ssm.py: the scan from a state, ``last``, the step, segments ----- #


def _operands(T, seed=0, batch=2):
    """``xbc``, ``dt`` as ``project_in`` leaves them and one layer's small
    leaves, random."""
    H, P, G, N = (DIMS[k] for k in ("heads", "head_dim", "groups", "state"))
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    width = H * P + 2 * G * N
    p = {"conv_w": jax.random.normal(k[0], (4, width)) / 2,
         "conv_b": 0.3 * jax.random.normal(k[1], (width,)),
         "dt_bias": jax.random.normal(k[2], (H,)) - 2.0,
         "A_log": jnp.log(jax.random.uniform(k[3], (H,), minval=0.05,
                                             maxval=2.0)),
         "D": 1.0 + 0.2 * jax.random.normal(k[4], (H,))}
    xbc, dt = jnp.split(jax.random.normal(k[5], (batch, T, width + H)),
                        [width], -1)
    return xbc, dt, p


@pytest.mark.parametrize("T,last", [(40, None), (37, None), (40, 26),
                                    (40, 2), (5, 1)])
def test_right_padding_behind_last_is_the_unpadded_scan(T, last):
    """Positions behind ``last`` change nothing: outputs up to ``last``, the
    state and the tail are those of the prompt cut at ``last``; the tail of
    a prompt shorter than the convolution holds zeros in front."""
    xbc, dt, p = _operands(T)
    scan = jax.jit(lambda *a, last=None: ssm.scan_positions(
        *a, last=last, **DIMS))
    y, state, tail = scan(xbc, dt, p, last=None if last is None
                          else jnp.int32(last))
    n = T if last is None else last + 1
    y_cut, state_cut, tail_cut = scan(xbc[:, :n], dt[:, :n], p)
    np.testing.assert_allclose(y[:, :n], y_cut, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(state, state_cut, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(tail, tail_cut)
    want = np.zeros((2, 3, xbc.shape[-1]), np.float32)
    want[:, max(0, 3 - n):] = np.asarray(xbc[:, max(0, n - 3):n])
    np.testing.assert_array_equal(tail, want)


def test_a_scan_in_two_pieces_from_a_state_is_one_piece():
    """``ssd_scan`` TAKES a start state and RETURNS the state it ends in: the
    first 24 positions, then the rest from their state, are all 40 at once."""
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(k[0], (2, 40, 8, 16))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, 40, 8)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (8,), minval=-2.0, maxval=1.0))
    b_in = jax.random.normal(k[3], (2, 40, 2, 16))
    c_in = jax.random.normal(k[4], (2, 40, 2, 16))
    scan = jax.jit(ssm.ssd_scan, static_argnums=5)
    y, state = scan(x, dt, a, b_in, c_in, 8)
    y1, s1 = scan(*(v[:, :24] for v in (x, dt)), a,
                  *(v[:, :24] for v in (b_in, c_in)), 8)
    y2, s2 = scan(*(v[:, 24:] for v in (x, dt)), a,
                  *(v[:, 24:] for v in (b_in, c_in)), 8, s1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(s2, state, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("last", [None, 43, 20])
def test_the_segmented_path_is_the_unsegmented(last):
    """48 positions in segments of 16 (each from the state and the
    convolution's rows the one before left) against one piece; ``last`` in the last
    segment and in the middle one (the third is then all padding)."""
    xbc, dt, p = _operands(48, seed=2)
    at = None if last is None else jnp.int32(last)
    whole = jax.jit(lambda *a: ssm.scan_positions(*a, last=at, **DIMS))(
        xbc, dt, p)
    pieces = jax.jit(lambda *a: ssm.scan_positions(
        *a, last=at, segment=16, **DIMS))(xbc, dt, p)
    n = 48 if last is None else last + 1
    np.testing.assert_allclose(pieces[0][:, :n], whole[0][:, :n], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(pieces[1], whole[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(pieces[2], whole[2])
    # 40 positions: 16 does not divide them, so they go in one piece
    xbc, dt, p = _operands(40, seed=2)
    a, b = (jax.jit(lambda *v, s=s: ssm.scan_positions(
        *v, segment=s, **DIMS))(xbc, dt, p) for s in (0, 16))
    np.testing.assert_array_equal(a[0], b[0])


def test_the_step_token_by_token_is_the_scan():
    """``ssm.step`` run 21 times from the state and tail the scan leaves at
    position 18 gives the scan's outputs, its final state and its tail."""
    xbc, dt, p = _operands(40, seed=3)
    y, state, tail = jax.jit(lambda *a: ssm.scan_positions(*a, **DIMS))(
        xbc, dt, p)
    _, s0, t0 = jax.jit(lambda *a: ssm.scan_positions(
        *a, last=jnp.int32(18), **DIMS))(xbc, dt, p)

    def one(carry, row):
        y_t, s, t = ssm.step(row[0][:, None], row[1][:, None], p, *carry,
                             groups=DIMS["groups"])
        return (s, t), y_t[:, 0]

    (s, t), ys = jax.jit(lambda c, rows: jax.lax.scan(one, c, rows))(
        (s0, t0.astype(jnp.float32)),
        (jnp.moveaxis(xbc[:, 19:], 1, 0), jnp.moveaxis(dt[:, 19:], 1, 0)))
    np.testing.assert_allclose(jnp.moveaxis(ys, 0, 1), y[:, 19:], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(s, state, rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(t, tail)


# --- (c) prefill and decode THROUGH THE STORES ------------------------------ #


def _engine(params, n_pages=16, **file_keys):
    return llama.LlamaDecodeEngine(program_cfg(**file_keys), params,
                                   n_pages=n_pages, page_size=PAGE)


def _walk(engine, toks, n, pages, spoil=None):
    """Prefill ``n`` tokens, decode the rest with a growing table."""
    got = [engine.prefill([int(t) for t in toks[:n]],
                          pages[:-(-n // PAGE)])]
    if spoil:
        spoil()
    for j in range(n, len(toks)):
        got.append(engine.decode(j, int(toks[j]), pages[:j // PAGE + 1]))
    return got


def test_unaligned_prefill_then_decode_across_a_page_boundary(params):
    """A prompt of 3 pages less two (no multiple of the page or the chunk),
    then four decoded positions across the boundary with a growing table, as
    the harness's check walks it: the test that fails if the pad positions
    advance the state, if the convolution's tail is taken at the page's end,
    or if the state stays behind in the page the sequence leaves (the third
    decode opens a page: its state is read from the page before)."""
    engine = _engine(params)
    n = 3 * PAGE - 2
    toks = np.random.RandomState(5).randint(0, 128, size=n + 4)
    want = logits_one(params, toks)
    for j, row in enumerate(_walk(engine, toks, n, engine.pool.alloc(4))):
        close(row, want[n - 1 + j], 1e-4)


def test_a_sequence_starts_clean_on_a_page_another_left_dirty(params):
    """A prompt shorter than the convolution (its tail holds zeros before
    the sequence's first row) on a page an EARLIER sequence left its state
    and tail in, and a decode at position 0 there: both start from zeros."""
    engine = _engine(params)
    toks = np.random.RandomState(6).randint(0, 128, size=6)
    want = logits_one(params, toks)
    pages = engine.pool.alloc(1)
    engine.prefill([int(t) for t in toks], pages)   # leaves a state behind
    for j, row in enumerate(_walk(engine, toks, 2, pages)):
        close(row, want[1 + j], 1e-4)
    close(engine.decode(0, int(toks[0]), pages), want[0], 1e-4)


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
    page (unaligned: ``copy_page`` takes the state and the tail with the
    page) and once page-aligned (no page is copied: the second sequence
    reads the shared last page's slab and writes its own new page's). The
    second is a prefix hit (ONE prefill), both decode to the reference's own
    continuation, and the first's continued decode is what it is alone: the
    shared slab was never written again."""
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


@pytest.mark.parametrize("fault", ["state_from_zeros", "tail_zeroed",
                                   "state_in_bfloat16"])
def test_a_spoiled_state_store_shows_in_float32(params, fault):
    """What the engine keeps A SEQUENCE carries the logits: the state store
    zeroed, the tail zeroed, or the state rounded to bfloat16 between the
    prefill and the decodes moves the decoded rows by far more than the
    sound engine differs (the last by a ten-thousandth of a logit: what the
    cell's bfloat16 comparison cannot see, and this one can)."""
    n = 2 * PAGE - 2
    toks = np.random.RandomState(9).randint(0, 128, size=n + 3)
    want = logits_one(params, toks)
    engine = _engine(params)
    which = {"state_from_zeros": (0, jnp.zeros_like),
             "tail_zeroed": (1, jnp.zeros_like),
             "state_in_bfloat16": (0, lambda a: a.astype(
                 jnp.bfloat16).astype(jnp.float32))}[fault]

    def spoil():
        stores = list(engine.stores)
        stores[which[0]] = which[1](stores[which[0]])
        engine.stores = tuple(stores)

    got = _walk(engine, toks, n, engine.pool.alloc(3), spoil)
    close(got[0], want[n - 1], 5e-6)   # the prefill's own row is sound
    sound = _walk(_engine(params), toks, n, [0, 1, 2])
    assert max(off(row, want[n - 1 + j]) for j, row in enumerate(sound)) < 5e-6
    assert max(off(row, want[n + j]) for j, row in enumerate(got[1:])) \
        > (5e-5 if fault == "state_in_bfloat16" else 1e-2)


# --- (d) the table, the file, the refusals ----------------------------------- #


def _gauge(name):
    from ray_tpu.util.metrics import registry

    return {k[0][1]: v for k, v in registry().local_values(name).items()}


@pytest.mark.parametrize("n_layers,traced", [
    (12, 3),     # fewer than two periods: H x 5, N, H x 6, a body a run
    (20, 10),    # two periods (the cell has four): ONE period's ten layers
    (22, 11)])   # and the rest's run of two behind them
def test_a_program_traces_a_periods_body_once(n_layers, traced):
    """``ray_tpu_serve_engine_traced_layers``: the bodies of the walker's
    segments, set where the engine is built."""
    from ray_tpu.util.metrics import registry

    cfg = program_cfg(num_hidden_layers=n_layers)
    llama.LlamaDecodeEngine(cfg, n_pages=4, page_size=PAGE)
    assert llama.traced_layers(cfg) == traced
    assert registry().local_values(
        "ray_tpu_serve_engine_traced_layers")[()] == traced


def test_the_state_goes_by_its_table_rule(params):
    """``served_stores`` / ``page_rows`` of the family, the engine's stores
    as the rule lays them out (ONE row a page), the gauges under the new
    tags, ``copy_page`` copying the state with the page, and the prefill
    path's counter under a kind of its own."""
    cfg = program_cfg()
    layout = llama.served_stores(cfg)
    assert [(s.kind, s.tag, s.layers, s.row, s.table) for s in layout] == [
        ("H", "ssm_state", 11, (8, 16, 16), "state"),
        ("H", "ssm_conv", 11, (3, 128 + 64), "state"),
        ("N", "hybrid", 1, (2, 16), "page"), ("N", "hybrid", 1, (2, 16), "page")]
    assert llama.page_rows(cfg)[0] == "hybrid"
    engine = _engine(params, n_pages=12)
    assert [s.shape for s in engine.stores] == [
        (11, 12, 1, 8, 16, 16), (11, 12, 1, 3, 192), (1, 12, PAGE, 2, 16),
        (1, 12, PAGE, 2, 16)]
    assert all(s.dtype == jnp.float32 for s in engine.stores)
    assert engine.n_slots == 0
    state_bytes = _gauge("ray_tpu_serve_engine_state_bytes")
    assert state_bytes["ssm_state"] == 4.0 * 11 * 8 * 16 * 16
    assert state_bytes["ssm_conv"] == 4.0 * 11 * 3 * 192
    assert state_bytes["state"] == state_bytes["conv"] == 0.0
    page_bytes = _gauge("ray_tpu_serve_engine_page_bytes")
    assert page_bytes["hybrid"] == 2 * 4.0 * 1 * 2 * 16
    assert "ssm_state" not in page_bytes
    pages = engine.pool.alloc(3)
    engine.prefill(list(range(PAGE + 3)), pages[:2])
    state, conv, keys, _ = (np.asarray(s) for s in engine.stores)
    assert np.abs(state[:, pages[1]]).max() > 0
    assert np.abs(state[:, pages[0]]).max() == 0   # only the last page's row
    engine.copy_page(pages[1], pages[2])
    after = [np.asarray(s) for s in engine.stores]
    for before, now in zip((state, conv, keys), after):
        np.testing.assert_array_equal(now[:, pages[2]], before[:, pages[1]])
    paths = {p["kind"]: p for p in llama.prefill_attend_paths()
             if p["kind"] in ("ssm", "hybrid")}
    assert paths["ssm"]["path"] == "chunks" \
        and "'cpu', not tpu" in paths["ssm"]["reason"]
    assert paths["hybrid"]["path"] == "tiles"
    # the attention's heads were filled up to the kernel's 128 lanes
    assert paths["hybrid"]["q_shape"][-1] == 128
    counted = _gauge("ray_tpu_serve_engine_prefill_attend")
    assert counted  # {kind, path}: the first tag is the kind
    engine.decode(PAGE + 3, 1, pages[:2])
    assert any(f["form"] == "grouped" and f["reason"].startswith("hybrid")
               for f in llama.decode_attend_forms())


def test_state_space_layers_alone_are_served(params):
    """The cell's ``--rehearsal`` cuts the stack to two layers, both
    Mamba-2, and leaves the ``mamba_*`` keys as published beside a 64-wide
    stream: the engine serves such a part of the family (``alone``), and a
    stack of attention layers alone it refuses."""
    tiny = spec.cell_bundle(CELL, rehearsal=True)["config"]
    cfg = dataclasses.replace(spec.program_config(tiny), dtype=jnp.float32)
    assert cfg.kinds == "HH" and (cfg.ssm_heads, cfg.ssm_head_dim) == (64, 64)
    two = init_params(cfg, jax.random.PRNGKey(2))
    engine = llama.LlamaDecodeEngine(cfg, two, n_pages=8, page_size=PAGE)
    toks = np.random.RandomState(1).randint(0, 256, size=PAGE + 5)
    want = logits_one(two, toks, file=tiny)
    for j, row in enumerate(_walk(engine, toks, PAGE - 1,
                                  engine.pool.alloc(2))):
        close(row, want[PAGE - 2 + j], 1e-4)
    with pytest.raises(NotImplementedError, match="this stack has N"):
        llama.LlamaDecodeEngine(program_cfg(layer_pattern="N" * 12),
                                n_pages=4, page_size=PAGE)
    with pytest.raises(ValueError, match="every built layer is one of"):
        program_cfg(layer_pattern="HHHHHDHHHHHH")


def test_the_pattern_and_the_scaling_are_held_to_the_file():
    """``layer_types`` names the new kinds as the published config does, so
    ``program.check`` holds the 40-letter pattern to the published list and
    ``logit_scale`` to ``logits_scaling``; window and full layers keep their
    names."""
    real = spec.program_config(dict(REAL))
    assert real.layer_types == REAL["layer_types"]
    assert real.layer_types.count("attention") == 4
    assert [i for i, t in enumerate(real.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    assert real.logits_scaling == REAL["logits_scaling"] == 8
    assert REAL["program"]["check"] == {"layer_types": "layer_types",
                                        "logits_scaling": "logits_scaling"}
    with pytest.raises(ValueError, match="layer_types"):
        spec.program_config(dict(REAL, layer_pattern="H" * 40))
    with pytest.raises(ValueError, match="logits_scaling"):
        spec.program_config(dict(REAL, logit_scale=0.25))
    # what the check does NOT hold, this does: the inner width both ways
    assert REAL["mamba_expand"] * REAL["hidden_size"] \
        == REAL["mamba_n_heads"] * REAL["mamba_d_head"]
    window = __import__("test_command_a_plus").program_cfg()
    assert set(window.layer_types) == {"sliding_attention", "full_attention"}


def test_num_params_counts_the_tree_and_the_file():
    cfg = program_cfg()
    tree = jax.eval_shape(lambda: llama.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == cfg.num_params()
    real = spec.program_config(dict(REAL))
    assert (real.kinds.count("H"), real.kinds.count("N")) == (36, 4)
    # 36 x 76,182,976 + 4 x 60,821,504 + 100,352 x 2,048 + 2,048
    assert real.num_params() == 3_191_396_096
    tree = jax.eval_shape(lambda: llama.init_params(real,
                                                   jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == 3_191_396_096
    assert tree["layers"]["hybrid_mamba"]["w_in"].shape == (36, 2048, 8512)
    assert tree["layers"]["hybrid_attn"]["wk"].shape == (4, 2048, 512)
    assert "lm_head" not in tree
    served = jax.eval_shape(lambda: llama.serving_params(real, llama.init_params(
        real, jax.random.PRNGKey(0))))
    mamba = served["layers"]["hybrid_mamba"]
    assert {w for w, a in mamba.items() if a.dtype == jnp.float32} == {
        "attn_norm", "mlp_norm", "conv_w", "conv_b", "dt_bias", "A_log", "D",
        "gate_norm"}
    assert served["embedding"].dtype == jnp.bfloat16


def test_the_trainers_refuse_the_family_and_others_the_multipliers():
    from ray_tpu.train.spmd import build_train_mesh, make_spmd_train_step

    with pytest.raises(NotImplementedError, match="'H' / 'N' layer"):
        make_spmd_train_step(program_cfg(),
                             build_train_mesh("", jax.devices()[:1]))
    for field, value in (("embedding_multiplier", 12.0),
                         ("attention_multiplier", 0.015625),
                         ("residual_multiplier", 0.22), ("logit_scale", 0.125)):
        with pytest.raises(ValueError, match="only a stack of"):
            dataclasses.replace(LlamaConfig.debug(), **{field: value})


# --- the benchmark's files --------------------------------------------------- #


def test_benchmark_files_fit_together_with_the_new_cell():
    from benchmarks.checks import test_yardstick
    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    test_yardstick.test_benchmark_files_fit_together()
    bench = spec.load_benchmark()
    # how many cells there are: tests/test_benchmark_cells.py, and there alone
    b = spec.cell_bundle(CELL)
    assert (b["cell"]["chips"], b["cell"]["traffic"], b["cell"]["config"]) \
        == (1, "prefill-open-2048-16000-granite4h", "granite-4.0-h-micro")
    assert sorted(m["name"] for m in b["end_to_end"]) == [
        "setup_s", "ttft_p95_ms"]
    names = {m["name"] for m in b["per_layer"]}
    assert {"serve.decode_program_ms", "compile_s"} <= names
    assert "serve.window_slots_ms" not in names
    assert len([n for n in names if n.startswith("serve.")]) == 12
    tr, dep = b["traffic"], b["config"]["deployment"]
    other = spec.load_traffic("prefill-open-2048-16000")
    for key in ("kind", "prompt_tokens", "output_tokens", "schedule_seed",
                "trace_after_s", "trace_seconds", "stream_item_timeout_s",
                "drain_timeout_s", "schedule_why"):
        assert tr[key] == other[key], key   # the same schedule, two models
    assert (dep["page_size"], dep["n_pages"], dep["decode_max_batch"],
            dep["max_inflight"]) == (2048, 40, 4, 32)
    shapes = shapes_of(tr, dep["page_size"])
    assert shapes == {"prefill": list(range(1, 9)),
                      "decode": list(range(2, 9))}
    # the check: 4,094 tokens, three decodes across the page boundary
    assert check_prompt_len(shapes, dep["page_size"]) == 4094
    assert dep["n_pages"] >= dep["decode_max_batch"] * (shapes["decode"][-1]
                                                        + 1)
    assert spec.resolve(b["config"]["reference"] + ":logits_one")
    entry, = [c for c in bench["configs"]
              if c["name"] == b["cell"]["config"]]
    assert entry["reduced"] == b["config"]["reduced"] == []
    assert entry["source"] == b["config"]["source"]
    # the catalog's row, key for key: nothing is cut
    row = {"attention_bias": False, "attention_multiplier": 0.015625,
           "embedding_multiplier": 12, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 8192,
           "logits_scaling": 8, "mamba_chunk_size": 256,
           "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
           "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
           "mamba_n_heads": 64, "mamba_proj_bias": False,
           "max_position_embeddings": 131072,
           "model_type": "granitemoehybrid",
           "normalization_function": "rmsnorm", "num_attention_heads": 32,
           "num_experts_per_tok": 0, "num_hidden_layers": 40,
           "num_key_value_heads": 8, "num_local_experts": 0,
           "position_embedding_type": "nope", "residual_multiplier": 0.22,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "shared_intermediate_size": 8192, "tie_word_embeddings": True,
           "vocab_size": 100352}
    assert {k: b["config"][k] for k in row} == row
    assert b["config"]["layer_types"] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    # the bytes the file states: a page's slab of state beside its keys and
    # values
    real = spec.program_config(dict(REAL))
    by_table = {"state": 0, "page": 0}
    for s in llama.served_stores(real):
        by_table[s.table] += 4 * s.layers * int(np.prod(s.row)) * (
            dep["page_size"] if s.table == "page" else 1)
    assert by_table == {"state": 36 * (2_097_152 + 52_224),
                        "page": 33_554_432}
    assert "77.4 MB" in dep["why"] and "33.6 MB" in dep["why"]
