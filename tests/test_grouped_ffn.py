"""The decode engine's forward-only grouped feed-forward kernel
(``ops/grouped_ffn.py``) against its oracle, ``ops/moe.py _expert_ffn``'s XLA
path (three ``ragged_dot`` products), at small sizes with the kernel
interpreted: the CPU, so values and paths, never a time. What the compiler of
the chip says of the real shapes is in ``test_tpu_compile.py``."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jitted import init_params
from ray_tpu.ops import grouped_ffn as gf
from ray_tpu.ops import moe

TILE = 16  # rows a grid step here; whole sublanes of either type
D = 128


def operands(counts, rows, f=256, layers=1, dtype=jnp.float32, seed=0):
    """``xs, w_gate, w_up, w_down, counts`` of a stack of ``layers``."""
    k = jax.random.split(jax.random.PRNGKey(seed + rows + f), 4)
    E = len(counts)

    def w(key, *shape):
        return (jax.random.normal(key, (layers, E, *shape))
                / np.sqrt(shape[0])).astype(dtype)

    return (jax.random.normal(k[0], (rows, D)).astype(dtype),
            w(k[1], D, f), w(k[2], D, f), w(k[3], f, D),
            jnp.asarray(counts, jnp.int32))


def xla(xs, w_gate, w_up, w_down, counts, layer, act="swiglu"):
    return jax.jit(lambda *a: moe._expert_xla(*a, layer, act))(
        xs, w_gate, w_up, w_down, counts)


def kernel(xs, w_gate, w_up, w_down, counts, layer, act="swiglu", tile=TILE,
           columns=None):
    return jax.jit(lambda *a: gf.grouped_ffn(
        *a, layer, act=act, tile=tile, columns=columns, interpret=True))(
            xs, w_gate, w_up, w_down, counts)


# (rows a group, rows of the call, width f, layers of the stack, the layer
# read, gate function, columns a grid step or None for f whole)
CASES = {
    "uneven groups with empty ones": (
        [13, 0, 40, 0, 0, 7, 36], 96, 256, 1, 0, "swiglu", None),
    "every boundary on a tile's edge": (
        [16, 32, 16, 48], 112, 256, 1, 0, "swiglu", None),
    "three boundaries inside one tile": (
        [3, 4, 2, 5, 50], 64, 256, 1, 0, "reglu", None),
    "rows of no group behind the sum (held path)": (
        [9, 30, 0, 11], 160, 256, 1, 0, "swiglu", None),
    "no row at all": ([0, 0, 0], 32, 256, 1, 0, "swiglu", None),
    "first layer of a stack": ([20, 12, 31], 63, 256, 3, 0, "reglu", None),
    "last layer of a stack": ([20, 12, 31], 63, 256, 3, 2, "reglu", None),
    "a traced layer": ([20, 12, 31], 63, 256, 3, "traced", "swiglu", None),
    "width 768, reglu": ([25, 60, 11], 96, 768, 2, 1, "reglu", None),
    "width 768, swiglu": ([25, 60, 11], 96, 768, 2, 1, "swiglu", None),
    "width 2048": ([40, 1, 23], 64, 2048, 1, 0, "swiglu", None),
    "width 2048 in column blocks of 512": (
        [40, 1, 23], 64, 2048, 2, 1, "swiglu", 512),
    "a row count that is no multiple of the tile": (
        [33, 9, 58], 100, 256, 1, 0, "swiglu", None),
    "fewer rows than the groups could cut tiles": (
        [1, 1, 1, 1, 1, 1, 1, 10], 17, 256, 1, 0, "swiglu", None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_xla_path(case):
    """The rows of a group are the XLA path's; a neighbour layer's or a
    neighbour group's weights leak into none."""
    counts, rows, f, layers, layer, act, columns = CASES[case]
    args = operands(counts, rows, f, layers)
    live = sum(counts)
    if layer == "traced":  # as the engine's scan hands it over
        got = jax.jit(lambda i, *a: gf.grouped_ffn(
            *a, i, act=act, tile=TILE, interpret=True))(
                jnp.int32(1), *args)
        layer = 1
    else:
        got = kernel(*args, layer, act, columns=columns)
    want = xla(*args, layer, act)
    assert got.shape == want.shape == (rows, D) and got.dtype == want.dtype
    np.testing.assert_allclose(got[:live], want[:live], atol=5e-6, rtol=5e-6)


@pytest.mark.parametrize("tile", [8, 16, 32, 128])
def test_the_row_tile_changes_nothing(tile):
    counts, rows = [13, 0, 40, 7, 36], 100
    args = operands(counts, rows, seed=1)
    np.testing.assert_allclose(kernel(*args, 0, tile=tile)[:96],
                               xla(*args, 0)[:96], atol=5e-6, rtol=5e-6)


@pytest.mark.parametrize("act", ["swiglu", "reglu"])
def test_the_rounding_points_are_expert_ffns(act):
    """bfloat16 in and out: gate and up rounded, the gate function in
    float32, the product rounded once, the down product rounded once. What
    differs is the order of a float32 sum: a last bit of few results."""
    counts, rows = [30, 0, 50, 16], 96
    args = operands(counts, rows, f=768, layers=2, dtype=jnp.bfloat16, seed=2)
    got = kernel(*args, 1, act).astype(jnp.float32)
    want = xla(*args, 1, act).astype(jnp.float32)
    assert kernel(*args, 1, act).dtype == jnp.bfloat16
    assert float(jnp.mean(got == want)) > 0.98
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -7)
    # and not the products of the float32 operands, rounded at the end
    f32 = [a.astype(jnp.float32) for a in args[:4]]
    assert float(jnp.abs(xla(*f32, args[4], 1, act) - want).max()) \
        > 4 * float(jnp.abs(got - want).max())


@pytest.mark.parametrize("counts,rows,tile", [
    ([13, 0, 40, 0, 0, 7, 36], 96, 16), ([0, 0, 5], 64, 16),
    ([16, 16, 16], 48, 16), ([1] * 9, 9, 8), ([0, 0], 40, 8),
    ([100, 3], 200, 128)])
def test_visits_cover_every_row_of_a_group_once(counts, rows, tile):
    group, row_tile, edges, n = (np.asarray(a) for a in jax.jit(
        lambda c: gf.visits(c, rows, tile))(jnp.asarray(counts, jnp.int32)))
    tiles = -(-rows // tile)
    assert len(group) == len(row_tile) == tiles + len(counts) - 1
    assert list(edges) == [0, *np.cumsum(counts)]
    seen = np.zeros(rows, int)
    for g, t in zip(group[:n], row_tile[:n]):
        lo, hi = max(edges[g], t * tile), min(edges[g + 1], (t + 1) * tile)
        assert lo < hi  # no visit without rows
        seen[lo:hi] += 1
    assert (seen[:sum(counts)] == 1).all() and not seen[sum(counts):].any()
    # a tile's visits follow one another (its block stays in VMEM between
    # them), and so do a group's (its weights stay)
    for ids in (row_tile[:n], group[:n]):
        assert (np.diff(ids) >= 0).all()
    assert ((0 <= row_tile) & (row_tile < tiles)).all()


def test_visits_hold_no_gather():
    """A gather from a table of the groups (``first[group]``, ``jnp.repeat``)
    is what the TPU compiler unrolls into a slice and a select a group once
    the visits pass some 320: 5 MB more of every long prefill program and
    half a second more to load it (PERF.md, PR 43)."""
    text = str(jax.make_jaxpr(lambda c: gf.visits(c, 98304, 256))(
        jnp.zeros(64, jnp.int32)))
    assert "gather" not in text and "scatter" not in text


def test_columns_are_picked_from_the_widths():
    assert gf.pick_columns(2560, 768, 2) == 768  # SmallThinker: 11.8 MB
    assert gf.pick_columns(2048, 768, 2) == 768  # Keye
    assert gf.pick_columns(128, 256, 4) == 256
    assert gf.pick_columns(2560, 800, 2) is None
    assert gf.pick_columns(100, 768, 2) is None


def test_the_kernel_refuses_what_it_cannot_read():
    xs, w_gate, w_up, w_down, counts = operands([8, 8], 16)
    with pytest.raises(ValueError, match="column blocks of 96"):
        kernel(xs, w_gate, w_up, w_down, counts, 0, columns=96)
    with pytest.raises(ValueError, match="bfloat16"):
        kernel(xs, w_gate.astype(jnp.bfloat16), w_up, w_down, counts, 0)
    with pytest.raises(ValueError, match="w_down"):
        kernel(xs, w_gate, w_up, w_down[:, :1], counts, 0)


# --- the path: which, why, and what it leaves to jax.grad ------------------ #


def test_the_path_is_read_from_backend_and_shapes(monkeypatch):
    bf = jnp.bfloat16
    xs, w_gate, w_up, _, _ = operands([0] * 4, 512, f=768, layers=2,
                                      dtype=bf)
    assert moe.expert_product_path(xs, w_gate, w_up, 1) == (
        "xla", "backend is 'cpu', not tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.expert_product_path(xs, w_gate, w_up, 1) == (
        "kernel", "tpu backend")
    assert moe.expert_product_path(xs, w_gate, w_up, jnp.int32(0))[0] \
        == "kernel"
    # a decode call's rows
    path, why = moe.expert_product_path(xs[:6], w_gate, w_up, 1)
    assert path == "xla" and "6 rows" in why and "row tile" in why
    # one layer's experts, as the trainer's scan hands them over
    path, why = moe.expert_product_path(xs, w_gate[0], w_up[0])
    assert path == "xla" and "one layer's experts" in why
    # two-matrix experts
    path, why = moe.expert_product_path(xs, None, w_up, 1)
    assert path == "xla" and "two-matrix" in why
    # a stack in another type than the rows'
    path, why = moe.expert_product_path(
        xs, w_gate.astype(jnp.float32), w_up.astype(jnp.float32), 1)
    assert path == "xla" and "float32" in why and "bfloat16" in why
    # a width that is filled up; widths that are not whole lanes
    path, why = moe.expert_product_path(xs, w_gate, jnp.zeros(
        (2, 4, D, 1856), bf), 1)
    assert path == "xla" and "1856" in why
    path, why = moe.expert_product_path(xs[:, :64], w_gate, jnp.zeros(
        (2, 4, 64, 256), bf), 1)
    assert path == "xla" and "whole lanes" in why


@contextlib.contextmanager
def on_the_kernel_path(monkeypatch, tile=TILE):
    """Steer this CPU process onto the kernel's path where the rule, asked
    as on a TPU backend, takes it (the kernel is interpreted here), at a row
    tile a test's few rows fill."""
    real = moe.expert_product_path

    def as_on_a_tpu(*a):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            return real(*a)

    with monkeypatch.context() as m:
        m.setattr(gf, "ROW_TILE", tile)
        m.setattr(gf, "grouped_ffn",
                  functools.partial(gf.grouped_ffn, interpret=True))
        m.setattr(moe, "expert_product_path", as_on_a_tpu)
        yield


def routed(held):
    """``routed_mlp`` over a stack of three layers' experts at layer 1: all
    8 experts here, or 4 of the router's 8 held (rows of no group behind
    the held experts', a second block of places under ``lax.cond``)."""
    k = jax.random.split(jax.random.PRNGKey(11), 5)
    count = 4 if held else 8
    h = jax.random.normal(k[0], (2, 32, D))
    router = jax.random.normal(k[1], (D, 8))
    w = [jax.random.normal(k[2 + i], (3, count, *shape)) / np.sqrt(shape[0])
         for i, shape in enumerate([(D, 256), (D, 256), (256, D)])]

    def loss(h, router, *w):
        y, _ = moe.routed_mlp(h, router, *w, top_k=2, norm_topk_prob=True,
                              held=(2, 4) if held else None, layer=1,
                              act="reglu")
        return jnp.sum(jnp.sin(y))

    return loss, (h, router, *w)


@pytest.mark.parametrize("held", [False, True], ids=["all here", "held"])
def test_grad_through_the_kernels_path_is_the_xla_paths(held, monkeypatch):
    """``routed_mlp`` with its layer's stack on the kernel's path: the
    value is the kernel's, ``jax.grad`` runs ``_grouped_dot``'s transposes,
    and both are what the XLA path gives, for rows, router and the whole
    stack (a neighbour layer's gradient is zero)."""
    loss, args = routed(held)
    n = tuple(range(len(args)))
    want = jax.jit(jax.value_and_grad(loss, argnums=n))(*args)
    told = []  # what a watcher of the stacked calls hears
    monkeypatch.setattr(moe, "_stacked_call_watchers", [])
    moe.watch_stacked_calls(lambda xs, w_up, *way: told.append(
        (w_up.shape[:2], *way)))
    with on_the_kernel_path(monkeypatch):
        got = jax.jit(jax.value_and_grad(loss, argnums=n))(*args)
    assert ((3, 4 if held else 8), "kernel", "tpu backend") in told
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[1][2][0]).any()  # layer 0 of the stack


def products_gauge():
    from ray_tpu.util.metrics import registry

    return {tags[0][1]: n for tags, n in registry().local_values(
        "ray_tpu_serve_engine_expert_products").items()}


def test_a_smallthinker_prefill_takes_the_kernel_and_its_decode_xla(
        monkeypatch):
    """A SmallThinker engine at widths of whole lanes: steered as on a TPU,
    its prefill program's layers take the kernel (read from the stack by the
    scan's traced layer number), its decode program's six rows XLA's
    products; the gauge counts both where the programs are traced, the
    records keep why, and the logits are the unsteered engine's."""
    import test_smallthinker as st
    from ray_tpu.models import llama

    # a record of the test's own: tests/test_tpu_compile.py traces the same
    # widths steered, and a worker that ran it first would read its rows here
    monkeypatch.setattr(llama, "_expert_products_taken", {})
    keys = dict(hidden_size=128, moe_ffn_hidden_size=128, head_dim=32,
                num_hidden_layers=4)
    cfg = st.program_cfg(**keys)
    params = init_params(cfg, jax.random.PRNGKey(5))

    def serve():
        engine = llama.LlamaDecodeEngine(cfg, params, n_pages=12,
                                         page_size=st.PAGE)
        pages = engine.pool.alloc(8)
        toks = np.random.RandomState(3).randint(0, 128, size=36)
        return st.served(engine, toks, 33, pages)

    want = serve()
    before = products_gauge()
    assert before["xla"] >= 2 and set(before) == {"kernel", "xla"}
    with on_the_kernel_path(monkeypatch):
        got = serve()
    after = products_gauge()
    # a program's scan traces its period once, the rest of a cut stack again
    assert after["kernel"] > before["kernel"]
    assert after["xla"] > before["xla"]
    mine = {(r["rows"][0], r["path"], r["reason"])
            for r in llama.expert_product_paths()
            if r["stack"][2:] == [128, 128]}
    # 7 pages of 5 positions x 3 choices; a decode call's 3 rows
    assert mine == {
        (105, "kernel", "tpu backend"),
        (3, "xla", "3 rows are under one row tile of 16"),
        (105, "xla", "backend is 'cpu', not tpu"),
        (3, "xla", "backend is 'cpu', not tpu")}
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_the_gauge_leaves_one_layers_experts_out():
    """The trainer's calls (``layer=None``) have one path and are not
    counted: the gauge is the engine's."""
    from ray_tpu.models import llama

    moe.watch_stacked_calls(llama._note_expert_products)  # as an engine does
    xs, w_gate, w_up, w_down, counts = operands([8, 8], 16)
    before = (products_gauge(), len(llama.expert_product_paths()))
    jax.jit(lambda *a: moe._expert_ffn(*a))(
        xs, w_gate[0], w_up[0], w_down[0], counts)
    assert (products_gauge(), len(llama.expert_product_paths())) == before
    jax.jit(lambda *a: moe._expert_ffn(*a, 0))(
        xs, w_gate, w_up, w_down, counts)
    assert len(llama.expert_product_paths()) == before[1] + 1
