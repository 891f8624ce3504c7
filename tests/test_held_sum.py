"""The held experts' sum as one Pallas call a chunk (``ops/row_sum.py``,
interpreted on the CPU) against the plain float32 scatter-add it replaces;
``ops/moe.py _held_rows`` whole with the kernel's path forced against the
XLA path; which calls take which path and why; the counter."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from ray_tpu.ops import moe, row_sum  # noqa: E402

COUNT = 4  # held experts
TILE = 16  # tokens a grid step here: a few dozen tokens are several tiles


def _places(n_tokens, c, live, seed, tokens=None, count=COUNT):
    """``c`` places of which the first ``live`` fell on ``count`` held
    experts of uneven load, sorted by expert and within one by token (as the
    router's stable sort leaves them): ``(token [c], edges [count + 1])``.
    ``tokens``: the tokens a live place may fall on (all of them)."""
    rng = np.random.default_rng(seed)
    pool = np.arange(n_tokens) if tokens is None else np.asarray(tokens)
    load = np.asarray([0.55, 0.05, 0.3, 0.1][:count])
    expert = np.sort(rng.choice(count, size=live, p=load / load.sum()))
    token = rng.choice(pool, size=live)
    by = np.lexsort((token, expert))
    edges = np.append(np.searchsorted(expert, np.arange(count)), live)
    # what a dead place's token reads: any token at all
    tail = rng.integers(0, n_tokens, c - live)
    return (np.concatenate([token[by], tail]).astype(np.int32),
            edges.astype(np.int32))


def _rows(c, d, live, dtype, seed, dead=np.inf):
    rng = np.random.default_rng(seed)
    ys = rng.normal(size=(c, d)).astype(np.float32)
    ys[live:] = dead  # what the grouped kernel may leave behind ``end``
    return (jnp.asarray(ys).astype(dtype),
            jnp.asarray(rng.uniform(0.05, 1.0, c), jnp.float32))


def _plain(y, ys, token, w, live):
    return y.at[token[:live]].add(
        w[:live, None] * ys[:live].astype(jnp.float32))


def _check(n_tokens, d, c, live, dtype, seed=0, carried=False, **places):
    token, edges = _places(n_tokens, c, live, seed, **places)
    ys, w = _rows(c, d, live, dtype, seed)
    y = (jnp.asarray(np.random.default_rng(seed + 1).normal(
        size=(n_tokens, d)), jnp.float32) if carried
         else jnp.zeros((n_tokens, d), jnp.float32))
    got = row_sum.held_sum(y, ys, jnp.asarray(token), w, jnp.asarray(edges),
                           tile=TILE, interpret=True)
    want = _plain(y, ys, jnp.asarray(token), w, live)
    assert got.dtype == jnp.float32 and got.shape == (n_tokens, d)
    assert bool(jnp.isfinite(got).all())
    # the same float32 products; a token's adds in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)
    return got


WIDTHS = [(2048, jnp.bfloat16), (6144, jnp.bfloat16), (2048, jnp.float32),
          (6144, jnp.float32)]


@pytest.mark.parametrize("d,dtype", WIDTHS,
                         ids=[f"{d}-{t.__name__}" for d, t in WIDTHS])
def test_the_kernel_is_the_plain_scatter_add(d, dtype):
    """Random tokens with repeats, a dead tail of ``inf`` rows."""
    _check(48, d, 80, 61, dtype)


@pytest.mark.parametrize("dead", [np.inf, np.nan, -np.inf],
                         ids=["inf", "nan", "-inf"])
def test_a_dead_places_row_never_reaches_the_sum(dead):
    token, edges = _places(48, 64, 37, 3)
    ys, w = _rows(64, 256, 37, jnp.bfloat16, 3, dead=dead)
    y = jnp.zeros((48, 256), jnp.float32)
    got = row_sum.held_sum(y, ys, jnp.asarray(token), w, jnp.asarray(edges),
                           tile=TILE, interpret=True)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_plain(y, ys, jnp.asarray(token), w, 37)),
        rtol=2e-6, atol=2e-6)


LOADS = {  # live places of 96 on 64 tokens, and what narrows them
    "tiles with no place": dict(live=70, carried=True, tokens=[
        *range(0, 16), *range(32, 48)]),  # the second and the last tile
    "one token": dict(live=70, tokens=[21]),
    "no live place": dict(live=0, carried=True),
    "one live place": dict(live=1),
    "every place live": dict(live=96),
    "one expert has all": dict(live=70, count=1),
}


@pytest.mark.parametrize("case", list(LOADS))
def test_the_shapes_of_a_routers_load(case):
    load = dict(LOADS[case])
    _check(64, 256, 96, load.pop("live"), jnp.bfloat16, **load)


def test_a_tile_with_no_place_keeps_what_the_sum_held():
    token, edges = _places(64, 96, 70, 5, tokens=range(0, 16))
    ys, w = _rows(96, 256, 70, jnp.bfloat16, 5)
    y = jnp.asarray(np.random.default_rng(6).normal(size=(64, 256)),
                    jnp.float32)
    got = row_sum.held_sum(y, ys, jnp.asarray(token), w, jnp.asarray(edges),
                           tile=TILE, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[16:]), np.asarray(y[16:]))
    assert np.abs(np.asarray(got[:16] - y[:16])).max() > 0.1


def test_a_second_chunk_adds_onto_the_first():
    """Two chunks of one call, the second onto the carried sum, are the
    plain sum over both."""
    n, d, c = 48, 256, 64
    y = jnp.zeros((n, d), jnp.float32)
    want = y
    for seed, live in ((1, c), (2, 23)):  # the last chunk has the dead tail
        token, edges = _places(n, c, live, seed)
        ys, w = _rows(c, d, live, jnp.bfloat16, seed)
        y = row_sum.held_sum(y, ys, jnp.asarray(token), w,
                             jnp.asarray(edges), tile=TILE, interpret=True)
        want = _plain(want, ys, jnp.asarray(token), w, live)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=4e-6,
                               atol=4e-6)


@pytest.mark.parametrize("depth,group", [(1, 8), (2, 4), (3, 8), (16, 16),
                                         (64, 1), (2, 80)])
def test_the_ring_and_the_copies_size_change_nothing(depth, group):
    token, edges = _places(48, 80, 66, 7)
    ys, w = _rows(80, 256, 66, jnp.bfloat16, 7)
    y = jnp.zeros((48, 256), jnp.float32)
    args = (y, ys, jnp.asarray(token), w, jnp.asarray(edges))
    np.testing.assert_array_equal(
        np.asarray(row_sum.held_sum(*args, tile=TILE, depth=depth,
                                    group=group, interpret=True)),
        np.asarray(row_sum.held_sum(*args, tile=TILE, interpret=True)))


def test_the_kernel_refuses_what_it_cannot_read():
    token, edges = _places(48, 64, 40, 0)
    ys, w = _rows(64, 256, 40, jnp.bfloat16, 0)
    y = jnp.zeros((48, 256), jnp.float32)
    args = (jnp.asarray(token), w, jnp.asarray(edges))
    with pytest.raises(ValueError, match="token tiles of 32"):
        row_sum.held_sum(y, ys, *args, tile=32, interpret=True)
    with pytest.raises(ValueError, match="rows"):
        row_sum.held_sum(y, ys[:, :192], *args, tile=TILE, interpret=True)
    with pytest.raises(ValueError, match="bfloat16"):
        row_sum.held_sum(y.astype(jnp.bfloat16), ys, *args, tile=TILE,
                         interpret=True)
    with pytest.raises(ValueError, match="copies of 24 rows"):
        row_sum.held_sum(y, ys, *args, tile=TILE, group=24, interpret=True)


@pytest.mark.parametrize("n_tokens,d,tile", [
    (32768, 2048, 1024), (6144, 2048, 1024), (8192, 6144, 256),
    (2048 + 128, 2048, 128), (24, 2048, 8), (100, 2048, None),
    (32768, 2000, None)])
def test_a_tile_is_read_from_the_tokens_and_the_width(n_tokens, d, tile):
    assert row_sum.pick_tile(n_tokens, d) == tile


# --- which calls take it --------------------------------------------------- #


def _struct(c, d, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((c, d), dtype)


REFUSALS = {
    "another backend": (dict(), "cpu", "backend is 'cpu', not tpu"),
    "one layer's experts": (dict(layer=None), "tpu", "one layer's experts"),
    "a straight block": (dict(loop=False), "tpu",
                         "one straight block of 2560 places"),
    "rows of integers": (dict(ys=_struct(2560, 2048, jnp.int8)), "tpu",
                         "rows in int8"),
    "one-byte floats": (dict(ys=_struct(2560, 2048, jnp.float8_e4m3fn)),
                        "tpu", "not a 2- or 4-byte float type"),
    "rows of no whole lanes": (dict(ys=_struct(2560, 2000)), "tpu",
                               "rows of 2000 are not whole lanes of 128"),
    "places of no whole slabs": (dict(ys=_struct(2568, 2048)), "tpu",
                                 "2568 places are not whole slabs of 16"),
    "tokens of no tile": (dict(n_tokens=100), "tpu",
                          "100 tokens do not split into tiles"),
    "too many places": (dict(ys=_struct(131072, 2048)), "tpu",
                        "a chunk of 131072 places"),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_every_refusal_says_why(case, monkeypatch):
    change, backend, why = REFUSALS[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    call = dict(ys=_struct(2560, 2048), n_tokens=8192, layer=1, loop=True)
    call.update(change)
    path, reason = moe.held_sum_path(**call)
    assert path == "xla" and why in reason


@pytest.mark.parametrize("c,d,dtype,n_tokens", [
    (89344, 2048, jnp.bfloat16, 32768), (40960, 2048, jnp.bfloat16, 32768),
    (2560, 6144, jnp.bfloat16, 8192), (16896, 2048, jnp.bfloat16, 6144),
    (2560, 1024, jnp.float32, 8192), (2560, 2560, jnp.float16, 128)])
def test_a_prefills_loop_on_a_tpu_takes_the_kernel(c, d, dtype, n_tokens,
                                                   monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for layer in (0, jnp.int32(3)):
        assert moe.held_sum_path(_struct(c, d, dtype), n_tokens, layer) == (
            "kernel", "tpu backend")


# --- _held_rows whole ------------------------------------------------------ #

N, K, D, F = 64, 4, 256, 128
A, CHUNK, EXPERTS, STACK, LAYER = N * K, 64, 16, 3, 1


def _held_call(end, dtype, seed=0, d=D):
    rng = np.random.default_rng(seed + end)
    group = np.full(A, COUNT, np.int32)
    held = rng.choice(A, size=end, replace=False)
    group[held] = rng.choice(COUNT, size=end, p=[0.55, 0.05, 0.3, 0.1])
    order = np.argsort(group, kind="stable").astype(np.int32)
    starts = np.searchsorted(group[order], np.arange(COUNT)).astype(np.int32)
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    weights = tuple(
        (jax.random.normal(key, (STACK, COUNT, a, b), jnp.float32)
         * a ** -0.5).astype(dtype)
        for key, (a, b) in zip(k, ((d, F), (d, F), (F, d))))
    hf = jax.random.normal(k[3], (N, d), jnp.float32).astype(dtype)
    top_w = jax.random.uniform(k[4], (N, K), jnp.float32, 0.1, 1.0)
    return (hf, top_w, jnp.asarray(order), jnp.asarray(starts),
            jnp.int32(end), weights)


def _held_rows(*args):
    return jax.jit(lambda *a: moe._held_rows(*a, EXPERTS, LAYER, "swiglu"))(
        *args)


@pytest.fixture
def small_chunk(monkeypatch):
    monkeypatch.setattr(moe, "held_chunk", lambda *shape: CHUNK)


def _force(monkeypatch):
    """The kernel's path on the CPU: the rule says ``kernel`` for a served
    kind's loop, and the call is interpreted, several token tiles a call.
    Returns the list the rule's answers are kept in."""
    taken = []

    def rule(ys, n_tokens, layer=None, loop=True):
        way = "kernel" if loop and layer is not None else "xla"
        taken.append(way)
        return way, "forced"

    monkeypatch.setattr(moe, "held_sum_path", rule)
    monkeypatch.setattr(row_sum, "held_sum", functools.partial(
        row_sum.held_sum, tile=TILE, interpret=True))
    return taken


@pytest.fixture
def forced(monkeypatch, small_chunk):
    return _force(monkeypatch)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("end", [0, 1, CHUNK - 1, CHUNK, CHUNK + 1, A // 4,
                                 A // 2 + 44, A])
def test_held_rows_on_the_kernels_path_is_the_xla_paths(end, dtype,
                                                        small_chunk,
                                                        monkeypatch):
    args = _held_call(end, dtype)
    want = _held_rows(*args)
    taken = _force(monkeypatch)
    got = _held_rows(*args)
    assert taken == ["kernel"]
    assert got.dtype == jnp.float32 and got.shape == (N, D)
    # float32 products, a token's adds reordered
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_a_differentiated_call_never_reaches_the_kernel(forced):
    """``jax.grad`` of the loop is the block form (``_held_chunks_jvp``),
    which asks the rule with ``loop=False``."""
    args = _held_call(A // 4, jnp.float32)

    def loss(hf, top_w, weights):
        return moe._held_rows(hf, top_w, *args[2:5], weights, EXPERTS, LAYER,
                              "swiglu").sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(args[0], args[1], args[5])
    assert forced and set(forced) == {"xla"}
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


def test_a_call_of_one_chunk_is_a_straight_block_and_says_so(forced):
    args = _held_call(5, jnp.float32)
    hf, top_w, order, starts, end, weights = args
    # a decode call's shape: no more places than a chunk
    moe._held_rows(hf[:8], top_w[:8], order[:32] % 32, starts, end, weights,
                   EXPERTS, LAYER, "swiglu")
    assert forced == ["xla"]


# --- the counter ----------------------------------------------------------- #


def _sums_gauge():
    from ray_tpu.util.metrics import registry

    return {tags[0][1]: n for tags, n in registry().local_values(
        "ray_tpu_serve_engine_held_sums").items()}


def test_the_counter_reads_both_paths_with_their_reasons(small_chunk,
                                                         monkeypatch):
    """As an engine registers it: a served kind's loop and its straight
    block are counted by path where they are traced, a call with one
    layer's weights (the trainer's) tells nobody."""
    from ray_tpu.models import llama

    llama._watch_routed_calls()
    wide = 384  # any width of whole lanes
    args = _held_call(A // 4, jnp.float32, d=wide)
    hf, top_w, order, starts, end, weights = args
    before = (dict(_sums_gauge()), len(llama.held_sum_paths()))
    one = tuple(w[LAYER] for w in weights)
    jax.jit(lambda *a: moe._held_rows(*a, EXPERTS, None, "swiglu"))(
        hf, top_w, order, starts, end, one)
    assert (dict(_sums_gauge()), len(llama.held_sum_paths())) == before
    monkeypatch.setattr(row_sum, "held_sum", functools.partial(
        row_sum.held_sum, tile=TILE, interpret=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # steered as on a TPU the experts' products would take THEIR kernel
    monkeypatch.setattr(moe, "expert_product_path",
                        lambda *a: ("xla", "not under test"))
    jax.jit(lambda *a: moe._held_rows(*a, EXPERTS, LAYER, "swiglu"))(*args)
    moe._held_rows(hf[:8], top_w[:8], order[:32] % 32, starts, end, weights,
                   EXPERTS, LAYER, "swiglu")
    after = _sums_gauge()
    assert set(after) == {"kernel", "xla"}
    assert after["kernel"] == before[0].get("kernel", 0) + 1
    assert after["xla"] == before[0].get("xla", 0) + 1
    mine = {(tuple(r["rows"]), r["tokens"], r["path"], r["reason"])
            for r in llama.held_sum_paths() if r["rows"][1] == wide}
    assert ((CHUNK, wide), N, "kernel", "tpu backend") in mine
    assert any(path == "xla" and "one straight block of 32 places" in why
               for _, _, path, why in mine)
