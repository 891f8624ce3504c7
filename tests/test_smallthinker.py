"""SmallThinker-21BA3B-Instruct's layer through the program: whole routed
blocks whose attention is full and unrotated ("F") or windowed and rotated
("W") in one stack, a router that reads the attention's input, ReGLU
experts, and the decode engine's page store of two kinds (the window
layers' rows in SLOTS), all at small widths on the CPU against the plain
reference (``benchmarks/reference/smallthinker_decoder.py``), seeded
weights. The window (11) is shorter than the sequences and the page size (5)
does not divide it."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from benchmarks.reference import smallthinker_decoder as ref
from jitted import forward, init_params, loss_fn, reference, value_and_grad
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.moe import routed_mlp
from ray_tpu.serve.kv_cache import CacheOOM, WindowSlotsOOM

CELL = "serve-smallthinker-prefill-open"
WINDOW, PAGE = 11, 5
# the file's keys at test widths: 6 layers cut inside the second period
FILE = {
    "head_dim": 16, "hidden_size": 64, "max_position_embeddings": 60,
    "moe_ffn_hidden_size": 32, "moe_num_active_primary_experts": 3,
    "moe_num_primary_experts": 8, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_hidden_layers": 6,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1] * 3, "rope_theta": 10000.0,
    "sliding_window_layout": [0, 1, 1, 1] * 3, "sliding_window_size": WINDOW,
    "tie_word_embeddings": False, "vocab_size": 128,
    "layer_pattern": "FWWW" * 3, "mlp_act": "reglu",
}
REAL = spec.load_json(os.path.join(
    spec.BENCH_DIR, "configs", "SmallThinker-21BA3B-Instruct.json"))
FIELDS = REAL["program"]["fields"]


def program_cfg(dtype=jnp.float32, **file_keys):
    """The program's config from the file's keys, as the harness maps them;
    no router loss, as the reference's ``loss`` has none."""
    file = dict(FILE, **file_keys)
    return dataclasses.replace(
        LlamaConfig(**{field: file[key] for field, key in FIELDS.items()}),
        dtype=dtype, remat=False, lb_loss_coef=0.0, z_loss_coef=0.0)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with the norms off their defaults, so that a norm left
    out or swapped shows."""
    p = init_params(program_cfg(), jax.random.PRNGKey(7))
    k = jax.random.split(jax.random.PRNGKey(8), 2)
    block = dict(p["layers"]["block"])
    for key, name in zip(k, ("attn_norm", "mlp_norm")):
        block[name] = 1 + 0.2 * jax.random.normal(key, block[name].shape)
    return dict(p, layers={"block": block})


def hidden(seed, seq=24):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, seq, 64))


def off(got, want) -> float:
    """Largest difference over the reference's largest value."""
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want)))
                 / jnp.max(jnp.abs(jnp.asarray(want))))


def close(got, want, rtol=2e-5):
    assert off(got, want) < rtol, off(got, want)


def logits_one(params, toks, **file_keys):
    return reference(lambda p, t: ref.logits_one(dict(FILE, **file_keys), p,
                                                 t), params,
                     jnp.asarray(toks))


# --- (a) the whole model ----------------------------------------------------- #


def test_forward_is_the_references_logits(params):
    toks = np.random.RandomState(3).randint(0, 128, size=(2, 37))
    got = forward(program_cfg(), params, toks)
    for row in range(2):
        close(got[row], logits_one(params, toks[row]), 5e-5)


def test_loss_and_every_gradient_leaf_are_the_references(params):
    cfg = program_cfg()
    toks = jnp.asarray(np.random.RandomState(4).randint(0, 128, size=(2, 30)))
    got, d_got = value_and_grad(lambda p: llama.loss_fn(cfg, p, toks), params)
    want, d_want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(FILE, p, toks)))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(loss_fn(cfg, params, toks)) == pytest.approx(float(want),
                                                              rel=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(d_want)
    assert len(flat) == 13
    for (path, w), g in zip(flat, jax.tree.leaves(d_got)):
        assert float(jnp.max(jnp.abs(w))) > 0, path
        assert off(g, w) < 2e-4, (path, off(g, w))


def test_num_params_counts_the_tree_and_the_file():
    cfg = program_cfg()
    tree = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(tree))
    assert jax.tree.structure(llama.param_logical_axes(cfg), is_leaf=lambda
                              x: isinstance(x, tuple)) == jax.tree.structure(
                                  tree)
    file = spec.cell_bundle(CELL)["config"]
    real = spec.program_config(file)
    layer = 398_627_840
    assert real.num_params() == len(real.kinds) * layer + 777_914_880
    assert dataclasses.replace(real, n_layers=12).num_params() \
        == 5_561_448_960
    assert dataclasses.replace(real, n_layers=8).num_params() == 3_966_937_600
    assert dataclasses.replace(real, n_layers=52).num_params() \
        == 21_506_562_560
    assert real.kinds == ("FWWW" * 13)[:file["num_hidden_layers"]]
    assert (real.window, real.mlp_act, real.window_pages(1024)) == (
        4096, "reglu", 5)
    # every published key, unchanged but the depth
    catalog = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": [0, 1, 1, 1] * 13,
        "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": [0, 1, 1, 1] * 13,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    assert {k: file[k] for k in catalog} == catalog
    assert file["published"] == {"num_hidden_layers": 52}
    # the program's pattern is CHECKED against the published lists
    with pytest.raises(ValueError, match="sliding_window_layout"):
        spec.program_config(dict(file, layer_pattern="FWWF" * 13))


def test_seeded_weights_start_where_block_init_says():
    """The matrices ``BLOCK_INIT`` names start that many times fan-in
    scaling, every other one at it (the configuration file's ``assumed``
    says why)."""
    block = init_params(program_cfg(num_hidden_layers=8, hidden_size=128),
                        jax.random.PRNGKey(3))["layers"]["block"]
    assert set(llama.BLOCK_INIT) == {"wo", "router"}
    for name, fan_in in (("wo", 64), ("w_down", 32), ("wq", 128),
                         ("w_up", 128), ("router", 128)):
        std = float(jnp.std(block[name])) * fan_in ** 0.5
        assert std == pytest.approx(llama.BLOCK_INIT.get(name, 1.0),
                                    rel=0.05), (name, std)


@pytest.mark.parametrize("keys,why", [
    (dict(window=0), "needs a window"),
    (dict(layer_pattern="F" * 6), "only a 'W' layer"),
    (dict(num_experts=0), "softmax-routed"),
    (dict(mlp_act="relu2"), "swiglu or reglu"),
    (dict(qk_norm=True), "no QK-norm"),
    (dict(mlp_act="gelu"), "mlp_act")])
def test_config_refuses_what_is_inconsistent(keys, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(program_cfg(), **keys)


def test_the_steps_that_cannot_band_refuse_the_kinds_by_name():
    from ray_tpu.train.spmd import build_train_mesh, make_spmd_train_step

    cfg = program_cfg()
    with pytest.raises(NotImplementedError, match="flash kernel.*no window"):
        make_spmd_train_step(cfg, build_train_mesh(""))
    with pytest.raises(NotImplementedError, match="flash kernel.*no window"):
        make_spmd_train_step(cfg, build_train_mesh("fsdp=2"))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("pipe",))
    with pytest.raises(NotImplementedError, match="no 'F' / 'W' layer"):
        llama.make_pipeline_train_step(cfg, mesh, 2)


# --- (b) each part alone ----------------------------------------------------- #


def block_layer(cfg, kind, x, block, i):
    """The program's layer ``i`` as kind ``kind``, attended in tiles."""
    return jax.jit(lambda x, p: llama.window_block(
        cfg, kind, x, p, i, llama.positions_of(1, x.shape[1]),
        lambda *a: llama.attend_window_tiles(cfg, kind, *a)))(x, block)


@pytest.mark.parametrize("i", [0, 1, 4, 5])
def test_a_layer_is_the_references(params, i):
    cfg, x, block = program_cfg(), hidden(1, seq=29), params["layers"]["block"]
    got, _, (k, v) = block_layer(cfg, cfg.kinds[i], x, block, i)
    close(got[0], reference(lambda x, p: ref.layer(FILE, x, p, i), x[0],
                            block))
    assert k.shape == v.shape == (1, 29, 2, 16)


# what the comparison must refuse: the layer computed ANOTHER way lies far
# from the reference, where the program's own lies within 2e-5
WRONG = {
    "a full layer rotated": (0, dict(rope_layout=[1, 1, 1, 1] * 3)),
    "a window layer not rotated": (1, dict(rope_layout=[0, 0, 0, 0] * 3)),
    "a window layer without its window": (
        1, dict(sliding_window_layout=[0, 0, 0, 0] * 3)),
    "a full layer windowed": (0, dict(sliding_window_layout=[1, 1, 1, 1] * 3)),
    "a window a page shorter": (1, dict(sliding_window_size=WINDOW - PAGE)),
    "a window a page longer": (1, dict(sliding_window_size=WINDOW + PAGE)),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(params, what):
    i, keys = WRONG[what]
    cfg, x, block = program_cfg(), hidden(2, seq=29), params["layers"]["block"]
    got = block_layer(cfg, cfg.kinds[i], x, block, i)[0][0]
    wrong = reference(lambda x, p: ref.layer(dict(FILE, **keys), x, p, i),
                      x[0], block)
    assert off(got, wrong) > 1e-2, off(got, wrong)


def routed(h, block, i, **kw):
    args = dict(top_k=3, norm_topk_prob=True, act="reglu")
    args.update(kw)
    return jax.jit(lambda h, a, p: routed_mlp(
        h, p["router"][i], p["w_gate"][i], p["w_up"][i], p["w_down"][i],
        router_input=a, **args))


def test_routed_half_reads_its_routers_own_input(params):
    """The router reads ``a`` and the experts ``m``: the reference's routed
    sum; fed ``m`` for both, or SwiGLU for ReGLU, it lies far off."""
    block = params["layers"]["block"]
    a, m = hidden(5, seq=40)[0], hidden(6, seq=40)[0]
    want = reference(lambda a, m, p: ref.experts(
        FILE, m, ref.route(FILE, a, p, 2), p, 2), a, m, block)
    got, stats = routed(m, block, 2)(m, a, block)
    close(got, want)
    assert float(stats["dropped"]) == 0.0
    assert off(routed(m, block, 2)(m, m, block)[0], want) > 0.1
    assert off(routed(m, block, 2, act="swiglu")(m, a, block)[0], want) > 0.1
    # no router_input: the router reads what the experts read, as before
    same, _ = jax.jit(lambda m, p: routed_mlp(
        m, p["router"][2], p["w_gate"][2], p["w_up"][2], p["w_down"][2],
        top_k=3, norm_topk_prob=True, act="reglu"))(m, block)
    close(same, routed(m, block, 2)(m, m, block)[0], 1e-6)
    silu = reference(lambda a, m, p: ref.experts(
        FILE, m, ref.route(FILE, a, p, 2), p, 2, gate=jax.nn.silu), a, m,
        block)
    close(routed(m, block, 2, act="swiglu")(m, a, block)[0], silu)


@pytest.mark.parametrize("window,seq", [(0, 24), (11, 24), (11, 37), (3, 16),
                                        (100, 24)])
def test_tiles_with_a_band_are_plain_banded_attention(window, seq,
                                                      monkeypatch):
    """The tile loop against explicit ``[T, T]`` scores under the band, at
    tile sizes that cut the band anywhere (``seq`` 37: one row a tile)."""
    k = jax.random.split(jax.random.PRNGKey(seq + window), 3)
    q = jax.random.normal(k[0], (1, seq, 4, 16))
    kk, vv = (jax.random.normal(a, (1, seq, 2, 16)) for a in k[1:])

    def plain(q, kk, vv):
        K, V = (jnp.repeat(a, 2, axis=2) for a in (kk, vv))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, K) / 4.0
        i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
        seen = (j <= i) & ((j > i - window) if window else True)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
            jnp.where(seen, s, -jnp.inf), axis=-1), V)

    want = reference(plain, q, kk, vv)
    for block in (4, 512):
        monkeypatch.setattr(llama, "LATENT_QUERY_BLOCK", block)
        got = reference(lambda *a: llama.attend_tiles(
            *a, jnp.float32, window=window), q, kk, vv)
        close(got, want, 1e-5)


@pytest.mark.parametrize("kinds,unit,times", [
    ("FWWWFW", "FWWW", 1), ("FWWWFWWW", "FWWW", 2), ("FW", "FW", 1),
    ("FWWWFWWWFW", "FWWW", 2), ("WWW", "W", 3)])
def test_a_pattern_is_whole_periods_and_a_rest(kinds, unit, times):
    assert llama._period(kinds) == (unit, times)


@pytest.mark.parametrize("kinds,segments", [
    # fewer than two periods: every run of one kind scanned, the rest in line
    ("GLLLL", ["G", ("L", 4)]),
    ("GGLLLL", [("G", 2), ("L", 4)]),
    ("RRRP", [("R", 3), "P"]),
    ("FWWWFW", ["F", ("W", 3), "FW"]),
    ("HHHHHNHHHHHH", [("H", 5), "N", ("H", 6)]),
    ("GL", ["GL"]),
    # two periods or more: scanned by periods, a period's BODY stays in
    # line, and the rest behind them is walked by runs
    ("FWWWFWWW", [("FWWW", 2)]),
    ("FWWWFWWWFW", [("FWWW", 2), "FW"]),
    ("HHHHHNHHHH" * 2 + "HH", [("HHHHHNHHHH", 2), ("H", 2)]),
    ("HHHHHNHHHH" * 4, [("HHHHHNHHHH", 4)]),
    ("DDDA" * 2, [("DDDA", 2)]),
    # a period of ONE layer, whatever the depth: one layer is a scan of one
    ("b" * 24, [("b", 24)]), ("S", [("S", 1)]), ("WWW", [("W", 3)])])
def test_a_stack_is_walked_by_segments(kinds, segments):
    """``(unit, times, scanned)`` in layer order, here a pair where scanned
    and the letters alone where in line: scanned from two repetitions on,
    in line at one; together they are the stack; and with ``least`` out of
    reach nothing but a period of one layer is scanned."""
    got = llama._segments(kinds)
    assert [(unit, times) if scanned else unit
            for unit, times, scanned in got] == segments
    assert "".join(unit * times for unit, times, _ in got) == kinds
    assert all(times >= 2 or kinds == unit for unit, times, scanned in got
               if scanned)
    assert all(times == 1 for _, times, scanned in got if not scanned)
    in_line = llama._segments(kinds, least=10 ** 6)
    assert in_line == ([(kinds[0], len(kinds), True)] if len(set(kinds)) == 1
                       else [(kinds, 1, False)])


# --- (c) the engine: two kinds of store, slots ------------------------------ #


def new_engine(params, n_layers=6, n_pages=24, dtype=jnp.float32, **kw):
    cfg = program_cfg(dtype, num_hidden_layers=n_layers, **kw)
    block = jax.tree.map(lambda a: a[:n_layers], params["layers"]["block"])
    return llama.LlamaDecodeEngine(cfg, dict(params, layers={"block": block}),
                                   n_pages=n_pages, page_size=PAGE)


@pytest.fixture(scope="module")
def engine(params):
    return new_engine(params)


def served(engine, toks, n, pages):
    """Prefill ``n`` tokens, then decode the rest: a row of logits each."""
    ps = engine.page_size
    got = [engine.prefill([int(t) for t in toks[:n]], pages[:-(-n // ps)])]
    for j in range(n, len(toks)):
        got.append(engine.decode(j, int(toks[j]), pages[:j // ps + 1]))
    return np.stack(got)


def gauge(name):
    from ray_tpu.util.metrics import registry

    return {k[0][1]: v for k, v in registry().local_values(name).items()}


def test_prefill_says_which_way_each_kind_attended(engine):
    """On the CPU a prefill program's full and window layers both attend in
    XLA tiles, and say so where the program is traced."""
    from ray_tpu.util.metrics import registry

    def gauge():  # the process's: another file's tests may have counted
        return {tuple(v for _, v in sorted(tags)): n for tags, n in
                registry().local_values(
                    "ray_tpu_serve_engine_prefill_attend").items()}

    before = gauge()
    pages = engine.pool.alloc(7)  # seven pages: a program of its own
    engine.prefill(list(range(33)), pages)
    engine.pool.release(pages)
    got = gauge()
    for kind in ("full", "window"):
        assert got[(kind, "tiles")] >= before.get((kind, "tiles"), 0.0) + 1.0
        assert got[(kind, "kernel")] == before.get((kind, "kernel"), 0.0)
    mine = {(r["kind"], r["window"]): (r["path"], r["reason"])
            for r in llama.prefill_attend_paths()
            if r["q_shape"] == [1, 35, 4, 16]}
    why = ("tiles", "backend is 'cpu', not tpu")
    assert mine == {("full", 0): why, ("window", WINDOW): why}


@pytest.mark.parametrize("n,more", [(23, 14), (7, 17), (15, 3)])
def test_prefill_then_decode_through_both_stores_is_the_references(
        engine, params, n, more):
    """A prompt longer than the window and one shorter, decoded across at
    least two page boundaries and PAST the window, over scattered pages:
    the engine's logits are ``logits_one``'s of the whole sequence."""
    toks = np.random.RandomState(n).randint(0, 128, size=n + more)
    pages = [9, 2, 17, 6, 11, 4, 20, 1]
    got = served(engine, toks, n, pages)
    close(got, logits_one(params, toks)[n - 1:], 5e-5)
    engine.pool.release([])  # the hook takes an empty list
    assert len(engine._slot_of) <= len(pages)


@pytest.mark.parametrize("n_layers", [8, 10, 2])
def test_a_scanned_stack_and_a_cut_one_serve_the_same_logits(params, n_layers):
    """Two whole periods scanned, two and a rest, and a stack cut inside its
    first period (the rehearsal's): all the reference's."""
    many = jax.jit(llama.init_params, static_argnums=0)(
        program_cfg(num_hidden_layers=n_layers), jax.random.PRNGKey(11))
    eng = new_engine(many, n_layers)
    toks = np.random.RandomState(n_layers).randint(0, 128, size=22)
    got = served(eng, toks, 18, [3, 1, 4, 7, 5])
    close(got, logits_one(many, toks, num_hidden_layers=n_layers)[17:], 5e-5)


def test_a_decode_that_reads_a_wrong_slot_lies_far_off(engine, params):
    toks = np.random.RandomState(31).randint(0, 128, size=26)
    pages = [5, 6, 7, 8, 9, 10]
    want = logits_one(params, toks)[22:]
    close(served(engine, toks, 23, pages), want, 5e-5)
    # the same table with page 8's slot naming page 7's rows
    engine._slot_of[8], keep = engine._slot_of[7], engine._slot_of[8]
    wrong = np.stack([engine.decode(j, int(toks[j]), pages[:j // PAGE + 1])
                      for j in range(23, 26)])
    engine._slot_of[8] = keep
    assert off(wrong, want[1:]) > 1e-2


def test_the_stores_are_two_pairs_and_the_window_pair_counts_slots(params):
    from test_olmoe import no_page_bytes

    engine = new_engine(params)  # its gauges are the last engine's
    cfg = engine.cfg
    assert llama.page_rows(cfg) == ("window", [(2, (2, 16))] * 2
                                    + [(4, (2, 16))] * 2)
    # k = ceil(11 / 5) + 1 = 4 pages of window; the longest sequence is 12
    # pages: ceil(24 / 12) * (4 + 2) slots
    assert (engine.window_pages, engine.n_slots) == (4, 12)
    assert [s.shape for s in engine.stores] == [(2, 24, 5, 2, 16)] * 2 \
        + [(4, 12, 5, 2, 16)] * 2
    assert gauge("ray_tpu_serve_engine_page_bytes") == {
        **no_page_bytes(cfg), "full": 2 * 2 * 2 * 16 * 4.0,
        "window": 2 * 4 * 2 * 16 * 4.0}
    assert gauge("ray_tpu_serve_engine_expert_groups") == {
        "program": 6 * 8.0, "layer": 8.0}
    assert gauge("ray_tpu_serve_engine_window_slots")["total"] == 12.0
    # a window longer than any context and more window pages than pages:
    # a slot a page
    wide = new_engine(jax.jit(llama.init_params, static_argnums=0)(
        program_cfg(num_hidden_layers=2), jax.random.PRNGKey(1)), 2,
        n_pages=7, sliding_window_size=4096)
    assert (wide.window_pages, wide.n_slots) == (821, 7)


def test_a_long_prompt_holds_k_slots_and_release_frees_them(params):
    eng = new_engine(params)
    pages = eng.pool.alloc(8)
    eng.prefill(list(range(37)), pages)  # 8 pages: 4 slots, the last pages'
    assert set(eng._slot_of) == set(pages[-4:])
    assert gauge("ray_tpu_serve_engine_window_slots") == {
        "total": 12.0, "used": 4.0}
    assert gauge("ray_tpu_serve_moe_assignment_share")["held"] == 1.0
    more = eng.pool.alloc(1)
    eng.decode(40, 1, pages + more)  # a ninth page: first touch, a slot
    assert set(eng._slot_of) == set(pages[-4:] + more)
    eng.pool.release(pages[:6])
    assert set(eng._slot_of) == set(pages[6:] + more)
    eng.pool.release(pages[6:] + more)
    assert not eng._slot_of and sorted(eng._free_slots) == list(range(12))
    assert gauge("ray_tpu_serve_engine_window_slots")["used"] == 0.0


def test_copy_page_carries_the_slot(params):
    eng = new_engine(params)
    eng.prefill(list(range(12)), [3, 4, 5])
    eng.copy_page(5, 10)
    full, win = np.asarray(eng.stores[0]), np.asarray(eng.stores[2])
    assert np.abs(full[:, 5]).max() > 0
    np.testing.assert_array_equal(full[:, 5], full[:, 10])
    a, b = eng._slot_of[5], eng._slot_of[10]
    assert a != b and np.abs(win[:, a]).max() > 0
    np.testing.assert_array_equal(win[:, a], win[:, b])
    # a page without a slot is copied in the full stores alone
    eng.prefill(list(range(40)), list(range(12, 20)))
    eng.copy_page(12, 21)
    assert 12 not in eng._slot_of and 21 not in eng._slot_of


def test_slot_pressure_evicts_an_idle_prefix_and_then_refuses(params):
    eng = new_engine(params)  # 12 slots
    cache = eng.prefix_cache
    held = []
    for i in range(3):  # three prompts of four pages: every slot taken
        pages = eng.pool.alloc(4)
        eng.prefill(list(range(i, i + 18)), pages)
        held.append(cache.insert((i,), 18, pages))
    assert len(eng._free_slots) == 0
    cache.release(held[0])  # idle: the one an engine may evict
    pages = eng.pool.alloc(4)
    eng.prefill(list(range(50, 68)), pages)
    assert cache.evictions == 1 and len(cache) == 2
    assert set(eng._slot_of) >= set(pages)
    more = eng.pool.alloc(2)
    with pytest.raises(WindowSlotsOOM, match="2 pages need a window slot"):
        eng.prefill(list(range(9)), more)
    assert issubclass(WindowSlotsOOM, CacheOOM)
    assert not set(more) & set(eng._slot_of)  # nothing was assigned


def test_a_prefill_with_no_slot_stays_queued_until_a_sequence_retires(params):
    from ray_tpu.serve.decode import DecodeScheduler

    eng = new_engine(params)  # 12 slots: 20-token prompts take 4 + a tail
    sched = DecodeScheduler(eng, max_batch=4)
    rs = np.random.RandomState(5)
    reqs = [(f"r{i}", {"prompt": [int(t) for t in rs.randint(0, 128, 18)],
                       "max_tokens": 4}) for i in range(4)]
    for corr, req in reqs:
        assert sched.submit(corr, req) is None
    # a step admits one: two fit (4 slots and a copied tail's each); the
    # third step finds no slot for the third, which holds no page and
    # waits, and decodes the two that run in that same step
    replies = [r for _ in range(3) for r in sched.step()[0]]
    assert len(sched.running) == 2 and len(sched.waiting) == 2
    assert not [r for r in replies if r[1] == "error"]
    assert [(c, json.loads(p)["i"]) for c, _, p in replies] \
        == [("r0", 0), ("r1", 0), ("r0", 1), ("r1", 1)]
    out = {}
    for _ in range(60):
        replies += sched.step()[0]
    for corr, kind, payload in replies:
        out.setdefault(corr, []).append((kind, payload))
    done = {c: json.loads(v[-1][1]) for c, v in out.items()
            if v[-1][0] == "final"}
    assert sorted(done) == ["r0", "r1", "r2", "r3"]
    assert all(d["n_generated"] == 4 for d in done.values())
    assert eng.pool.used == sum(len(e.pages) for e in
                                eng.prefix_cache._entries.values())


def test_many_short_sequences_wait_for_slots_and_none_fails_in_decode(params):
    """Short sequences need a slot for EVERY page: by pages six of these
    fit (4 pages each of 24), by slots three (4 each of 12). Admitted by
    pages alone, the fourth to sixth would take the slots that the first
    three's decode calls need to open their pages."""
    from ray_tpu.serve.decode import DecodeScheduler
    from test_kv_cache import _run_all

    eng = new_engine(params)  # 24 pages, 12 slots
    assert eng.window_slots_needed(4, 8) == 4  # a page, its copy, two opened
    assert eng.window_slots_needed(18, 4) == 6 and eng.window_pages == 4
    assert eng.window_slots_needed(58, 1) == 4 + 1  # never more than k + ...
    sched = DecodeScheduler(eng, max_batch=8)
    rs = np.random.RandomState(6)
    reqs = [(f"s{i}", {"prompt": [int(t) for t in rs.randint(0, 128, 4)],
                       "max_tokens": 8}) for i in range(8)]
    for corr, req in reqs:
        assert sched.submit(corr, req) is None
    for _ in range(4):  # three admissions, then no slot: a decode step
        sched.step()
    assert len(sched.running) == 3 and len(sched.waiting) == 5
    out = _run_all(sched, [])
    assert not [f for frames in out.values() for f in frames
                if f[0] == "error"]
    assert sorted(c for c, n in sched.retired) == sorted(c for c, _ in reqs)
    assert all(n == 8 for _, n in sched.retired)
    # what can never fit is refused, as a prompt over the pool's pages is
    err = sched.submit("big", {"prompt": [1] * 4, "max_tokens": 56})
    assert err is None
    replies = [r for r in sched.step()[0] if r[0] == "big"]
    assert replies[0][1] == "error" and "window slots" in str(replies[0][2])


def test_a_whole_prompt_hit_decodes_to_the_first_times_logits(params):
    from ray_tpu.serve.decode import DecodeScheduler
    from test_kv_cache import _run_all

    eng = new_engine(params)
    sched = DecodeScheduler(eng)
    req = {"prompt": [int(t) for t in np.random.RandomState(2).randint(
        0, 128, size=23)], "max_tokens": 9}
    cold = json.loads(_run_all(sched, [("c", req)])["c"][-1][1])
    warm = json.loads(_run_all(sched, [("w", req)])["w"][-1][1])
    assert warm["cached_prefix"] is True and cold["cached_prefix"] is False
    assert warm["tokens"] == cold["tokens"] and len(cold["tokens"]) == 9
    toks = req["prompt"] + cold["tokens"]
    logits = np.asarray(forward(eng.cfg, eng.params, np.asarray([toks])))[0]
    assert [int(t) for t in logits[22:31].argmax(-1)] == cold["tokens"]


def test_engine_converts_the_leaves_it_multiplies(params):
    tree = llama.serving_params(program_cfg(jnp.bfloat16),
                                params)["layers"]["block"]
    f32 = {"attn_norm", "mlp_norm", "router"}
    for name, leaf in tree.items():
        assert leaf.dtype == (jnp.float32 if name in f32 else jnp.bfloat16)
    assert tree["w_up"].ndim == 4


def test_bfloat16_engine_stays_near_the_reference(params):
    """As the cell runs it: bfloat16 products against the float32 reference
    on the engine's own (rounded) weights."""
    eng = new_engine(params, dtype=jnp.bfloat16)
    toks = np.random.RandomState(21).randint(0, 128, size=30)
    got = served(eng, toks, 23, [5, 1, 3, 8, 13, 2])
    assert off(got, logits_one(eng.params, toks)[22:]) < 0.15


@pytest.mark.parametrize("keys,why", [
    (dict(layer_pattern="W" * 6), "both full and window"),
    (dict(layer_pattern="E*E*E*", window=0, mlp_act="swiglu"), "halves")])
def test_engine_refuses_the_stacks_it_has_no_stores_for(keys, why):
    with pytest.raises(NotImplementedError, match=why):
        llama.LlamaDecodeEngine(dataclasses.replace(program_cfg(), **keys))


def test_pool_release_calls_its_hooks_after_the_pages_are_free():
    from ray_tpu.serve.kv_cache import PagePool

    pool, seen = PagePool(4, 2), []
    pool.release_hooks.append(lambda pages: seen.append(
        (list(pages), pool.free_count)))
    pages = pool.alloc(3)
    pool.release(pages[:2])
    assert seen == [(pages[:2], 3)]
    with pytest.raises(ValueError, match="double free"):
        pool.release(pages[:1])
    assert len(seen) == 1


# --- (d) the benchmark's files ---------------------------------------------- #


def test_benchmark_files_fit_together_with_the_new_cell():
    from benchmarks.checks import test_yardstick
    from benchmarks.lib.serve_cell import check_prompt_len, shapes_of

    test_yardstick.test_benchmark_files_fit_together()
    b = spec.cell_bundle(CELL)
    assert (b["cell"]["chips"], b["cell"]["traffic"]) == (
        1, "prefill-open-4608-16000")
    assert sorted(m["name"] for m in b["end_to_end"]) == [
        "setup_s", "ttft_p95_ms"]
    names = {m["name"] for m in b["per_layer"]}
    assert {"serve.window_slots_ms", "serve.decode_program_ms",
            "compile_s"} <= names
    assert len([n for n in names if n.startswith("serve.")]) == 13
    # the new metric is the new cell's alone: no other cell's line changes
    for other in ("serve-internlm2-prefill-open",
                  "serve-longcatflash-prefill-open"):
        assert "serve.window_slots_ms" not in {
            m["name"] for m in spec.cell_bundle(other)["per_layer"]}
    tr, dep = b["traffic"], b["config"]["deployment"]
    assert (tr["kind"], tr["prompt_tokens"], tr["output_tokens"]) == (
        "open_loop", {"dist": "log_uniform", "min": 4608, "max": 16000},
        {"dist": "const", "value": 16})
    shapes = shapes_of(tr, dep["page_size"])
    assert shapes == {"prefill": list(range(5, 17)),
                      "decode": list(range(5, 17))}
    # the check's prompt lies beyond the window: `correct` meets the band
    n = check_prompt_len(shapes, dep["page_size"])
    assert n == 5118 > b["config"]["sliding_window_size"]
    # the running sequences of the longest context, a copied tail each
    assert dep["n_pages"] >= dep["decode_max_batch"] * (shapes["decode"][-1]
                                                        + 1)
    assert spec.resolve(b["config"]["reference"] + ":logits_one")


@pytest.mark.deadline(170)
def test_the_cells_rehearsal_runs_end_to_end():
    """``--rehearsal`` of the new cell on the CPU: a pattern cut inside a
    period, a window longer than any context, more window pages than pool
    pages, through ``serve.run``, the scheduler and the harness's check."""
    import rehearse

    tiny = spec.cell_bundle(CELL, rehearsal=True)
    cfg = spec.program_config(tiny["config"])
    assert (cfg.kinds, cfg.window, cfg.mlp_dim) == ("FW", 4096, 768)
    line = rehearse.run_cell(CELL, 3800000038)
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
