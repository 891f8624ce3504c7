"""The held path's forward loop (``ops/moe.py _held_chunks``) against the
block form it replaced and against a plain per-assignment float32 reference,
at a chunk small enough that a few hundred places are many chunks; which
form a call takes; what a differentiated call is; the places' gauge."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from ray_tpu.ops import moe  # noqa: E402

CHUNK = 64
N, K, D, F = 128, 4, 32, 48
A = N * K
EXPERTS, COUNT = 16, 4  # the parent's rule: two blocks of 256 places
OLD_BLOCK = A // 2
# the places that fell on held experts: none, one, around a chunk's edge, a
# quarter of all, more than one OLD block (what the ``cond`` was for), all
ENDS = {"0": 0, "1": 1, "c-1": CHUNK - 1, "c": CHUNK, "c+1": CHUNK + 1,
        "a quarter": A // 4, "past the old block": OLD_BLOCK + 44, "all": A}
ACTS = ("swiglu", "reglu", "relu2")
STACK, LAYER = 3, 1


@pytest.fixture(autouse=True)
def small_chunk(monkeypatch):
    monkeypatch.setattr(moe, "held_chunk", lambda *shape: CHUNK)


def _weights(act, stacked, dtype=jnp.float32):
    lead = (STACK, COUNT) if stacked else (COUNT,)
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    w_gate, w_up, w_down = (
        (jax.random.normal(key, (*lead, a, b), jnp.float32) * a ** -0.5
         ).astype(dtype) for key, (a, b) in zip(k, ((D, F), (D, F), (F, D))))
    return (None if act == "relu2" else w_gate, w_up, w_down)


def _operands():
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    return (jax.random.normal(k[0], (N, D), jnp.float32),
            jax.random.uniform(k[1], (N, K), jnp.float32, 0.1, 1.0))


def _routing(end: int, seed: int = 0):
    """``end`` assignments somewhere among the ``A`` on held experts of
    uneven load, the others elsewhere: ``(group, order, starts)``."""
    rng = np.random.default_rng(seed + end)
    group = np.full(A, COUNT, np.int32)
    held = rng.choice(A, size=end, replace=False)
    group[held] = rng.choice(COUNT, size=end, p=[0.55, 0.05, 0.3, 0.1])
    order = np.argsort(group, kind="stable").astype(np.int32)
    starts = np.searchsorted(group[order], np.arange(COUNT)).astype(np.int32)
    return group, order, starts


def _reference(hf, top_w, group, weights, act):
    """One assignment after the other, float32 on the host."""
    w_gate, w_up, w_down = (None if w is None else np.asarray(w, np.float32)
                            for w in weights)
    h, w = np.asarray(hf, np.float32), np.asarray(top_w).reshape(-1)
    y = np.zeros((N, D), np.float32)
    for a in np.flatnonzero(group < COUNT):
        g, x = group[a], h[a // K]
        up = x @ w_up[g]
        if act == "relu2":
            mid = np.square(np.maximum(up, 0))
        else:
            gate = x @ w_gate[g]
            mid = (np.maximum(gate, 0) if act == "reglu"
                   else gate / (1 + np.exp(-gate))) * up
        y[a // K] += w[a] * (mid @ w_down[g])
    return y


@functools.lru_cache(maxsize=None)
def _forms(act, stacked):
    """``(loop, blocks)`` jitted with ``end`` traced: one compilation a form
    serves every ``end``. The block form under the PARENT's rule."""
    layer = LAYER if stacked else None

    def loop(hf, top_w, order, starts, end, weights):
        assert moe.held_chunk(A, COUNT, EXPERTS) == CHUNK
        return moe._held_rows(hf, top_w, order, starts, end, weights,
                              EXPERTS, layer, act)

    def blocks(hf, top_w, order, starts, end, weights):
        return moe._held_blocks(hf, top_w, order, starts, end, weights, 2,
                                layer, act)

    return jax.jit(loop), jax.jit(blocks)


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one layer", "stacked"])
@pytest.mark.parametrize("act", ACTS)
@pytest.mark.parametrize("end", list(ENDS))
def test_the_loop_is_the_block_form_and_the_plain_sum(end, act, stacked):
    end = ENDS[end]
    group, order, starts = _routing(end)
    hf, top_w = _operands()
    weights = _weights(act, stacked)
    loop, blocks = _forms(act, stacked)
    args = (hf, top_w, order, starts, jnp.int32(end), weights)
    y = loop(*args)
    assert y.dtype == jnp.float32 and y.shape == (N, D)
    if end <= OLD_BLOCK:  # the same rows added onto a token in the same
        # order: the same sum. Past it the blocks add two partial sums
        np.testing.assert_array_equal(np.asarray(y),
                                      np.asarray(blocks(*args)))
    else:
        np.testing.assert_allclose(np.asarray(y), np.asarray(blocks(*args)),
                                   rtol=1e-5, atol=1e-6)
    mine = tuple(None if w is None else (w[LAYER] if stacked else w)
                 for w in weights)
    want = _reference(hf, top_w, group, mine, act)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    untouched = np.ones(N, bool)
    untouched[np.flatnonzero(group < COUNT) // K] = False
    assert not np.asarray(y)[untouched].any()  # dead places add NOTHING


def _count(jaxpr, name):
    """Equations named ``name`` in ``jaxpr`` and every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, name)
    return n


def _routed(hf, router, weights, act="swiglu", **more):
    return moe.routed_mlp(hf, router, *weights, top_k=K, held=(4, COUNT),
                          act=act, **more)[0]


def _router(width=EXPERTS):
    return jax.random.normal(jax.random.PRNGKey(9), (D, width), jnp.float32)


@pytest.mark.parametrize("places,whiles", [(CHUNK, 0), (CHUNK + K, 1),
                                           (A, 1)])
def test_the_calls_own_shape_decides_the_form(places, whiles):
    """``A <= held_chunk(..)`` is the straight block (a decode call), anything
    larger ONE loop, whatever share of the experts is held."""
    hf = _operands()[0][:places // K]
    jaxpr = jax.make_jaxpr(_routed)(hf, _router(), _weights("swiglu", False))
    assert _count(jaxpr.jaxpr, "while") == whiles
    assert _count(jaxpr.jaxpr, "cond") == 0
    assert _count(jaxpr.jaxpr, "scatter-add") == 1


@pytest.mark.parametrize("act", ACTS)
def test_a_differentiated_call_is_the_parents_blocks(act, monkeypatch):
    """``jax.grad`` through ``routed_mlp(held=)``: no loop in the program,
    the parent's blocks and ``cond`` in its place, and its gradients."""
    hf, router, weights = _operands()[0], _router(), _weights(act, False)

    def loss(hf, router, weights):
        return jnp.sum(_routed(hf, router, weights, act) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    jaxpr = jax.make_jaxpr(grad)(hf, router, weights)
    assert _count(jaxpr.jaxpr, "while") == 0
    assert _count(jaxpr.jaxpr, "cond") >= 1  # the second block's
    got = grad(hf, router, weights)

    def parents(hf, top_w, order, starts, end, weights, experts, layer, act):
        blocks = max(1, min(max(experts // (4 * COUNT),
                                min(2, experts // (2 * COUNT))), A // 128))
        return moe._held_blocks(hf, top_w, order, starts, end, weights,
                                blocks, layer, act)

    monkeypatch.setattr(moe, "_held_rows", parents)
    want = grad(hf, router, weights)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # forward-mode too goes through the blocks
    jvp = jax.make_jaxpr(lambda h, t: jax.jvp(
        lambda h: _routed(h, router, weights, act), (h,), (t,)))(hf, hf)
    assert _count(jvp.jaxpr, "while") == 0


@pytest.mark.parametrize("zero_experts", [0, 8])
@pytest.mark.parametrize("form", ["one layer", "stacked", "bfloat16"])
def test_routed_mlp_over_a_held_range_loops_to_the_same_sum(
        form, zero_experts, monkeypatch):
    """Through ``routed_mlp`` itself (the router's own ``end``), with and
    without identity experts: the loop against the straight form."""
    dtype = jnp.bfloat16 if form == "bfloat16" else jnp.float32
    hf = _operands()[0].astype(dtype)
    weights = _weights("swiglu", form != "one layer", dtype)
    more = {"zero_experts": zero_experts, "scale": 2.0,
            "norm_topk_prob": True}
    if form != "one layer":
        more["layer"] = LAYER
    router = _router(EXPERTS + zero_experts)
    call = functools.partial(moe.routed_mlp, hf, router, *weights, top_k=K,
                             held=(4, COUNT), **more)
    y, stats = call()
    assert 0 < float(stats["held_share"]) < 1
    monkeypatch.setattr(moe, "held_chunk", lambda *shape: A)  # one block
    straight, _ = call()
    assert float(jnp.abs(straight).max()) > 0
    np.testing.assert_array_equal(np.asarray(y), np.asarray(straight))


@pytest.mark.parametrize("end", ["1", "c+1", "a quarter"])
def test_a_row_of_nan_behind_end_never_reaches_the_sum(end, monkeypatch):
    """On the TPU the grouped product leaves the rows of no group unwritten:
    here every such row of every chunk comes back NaN."""
    end = ENDS[end]
    _, order, starts = _routing(end)
    hf, top_w = _operands()
    weights = _weights("swiglu", False)
    args = (hf, top_w, order, starts, jnp.int32(end), weights, EXPERTS)
    clean = moe._held_rows(*args)
    real, poisoned = moe._expert_ffn, []

    def unwritten(xs, w_gate, w_up, w_down, counts, layer=None, act="swiglu"):
        ys = real(xs, w_gate, w_up, w_down, counts, layer, act)
        dead = jnp.arange(xs.shape[0]) >= counts.sum()
        poisoned.append(xs.shape[0])
        return jnp.where(dead[:, None], jnp.nan, ys)

    monkeypatch.setattr(moe, "_expert_ffn", unwritten)
    y = moe._held_rows(*args)
    assert poisoned == [CHUNK]  # one body, a chunk's rows
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_array_equal(np.asarray(y), np.asarray(clean))


@pytest.mark.parametrize("places,live,made", [
    (12, 3, 12), (CHUNK, 0, CHUNK), (A, 0, 0), (A, 1, CHUNK),
    (A, CHUNK, CHUNK), (A, CHUNK + 1, 2 * CHUNK), (A, A, A),
    (A + 10, A + 10, A + CHUNK)])
def test_the_places_made_follow_the_live_ones(places, live, made):
    assert moe.held_places_made(places, live, COUNT, EXPERTS) == made
    if places > CHUNK:
        assert 0 <= made - live < CHUNK


def test_a_chunk_is_the_even_share_of_the_places_and_a_margin(monkeypatch):
    monkeypatch.undo()  # the rule itself
    # the three served shapes at 16 pages: Qwen3-Next, Keye, LongCat
    assert moe.held_chunk(327680, 128, 512) == 89344   # 81,920 even
    assert moe.held_chunk(262144, 16, 128) == 40960    # 32,768 even
    assert moe.held_chunk(98304, 16, 768) == 2560      # 2,048 even
    for places, count, experts in [(10, 128, 512), (12, 16, 768),
                                   (8, 16, 128), (6, 8, 128)]:
        c = moe.held_chunk(places, count, experts)  # a decode call
        assert c == 256 and moe.held_places_made(places, 3, count,
                                                 experts) == places
    for places, count, experts in [(61440, 128, 512), (98304, 8, 128),
                                   (4096, 4, 16)]:
        c, even = moe.held_chunk(places, count, experts), (
            places * count / experts)
        assert c % 256 == 0 and even * (1 + count ** -0.5) <= c
        assert c < even * (1 + count ** -0.5) + 256 and c < places
        # the even load is one chunk, twice it two or three
        assert moe.held_places_made(places, even, count, experts) == c
        assert moe.held_places_made(places, 2 * even, count, experts) in (
            2 * c, 3 * c)
    assert moe.held_chunk(4096, 16, 16) > 4096  # all held: one block


# --- the gauge ------------------------------------------------------------- #


def _places():
    from ray_tpu.util.metrics import registry

    return {k[0][1]: v for k, v in registry().local_values(
        "ray_tpu_serve_moe_places").items()}


@pytest.mark.parametrize("chunk", [16, 10 ** 6], ids=["loop", "straight"])
def test_the_gauge_reads_live_and_made_after_a_prefill(chunk, monkeypatch):
    """A small engine whose layers hold 4 of the router's experts: after a
    prefill ``live`` is ``held_share`` of tokens x top_k and ``made`` what
    :func:`held_places_made` says, whole chunks up to it (all the places
    where the call is one straight block)."""
    import test_longcat_flash

    from ray_tpu.models import llama

    monkeypatch.setattr(moe, "held_chunk", lambda *shape: chunk)
    cfg = test_longcat_flash.program_cfg()
    engine = llama.LlamaDecodeEngine(cfg, n_pages=12, page_size=8, seed=2)
    pages = engine.pool.alloc(3)
    engine.prefill(list(range(1, 21)), pages)
    places = 3 * 8 * cfg.experts_per_token
    got = _places()
    assert set(got) == {"live", "made"}
    assert 0 < got["live"] < places and got["made"] >= got["live"]
    wide = cfg.router_experts + cfg.zero_experts
    assert got["made"] == moe.held_places_made(places, got["live"],
                                               cfg.num_experts, wide)
    if chunk < places:
        assert got["made"] - got["live"] < chunk and got["made"] % chunk == 0
    else:
        assert got["made"] == places


@pytest.mark.parametrize("held_share,ratio", [(0.25, 1.0), (0.125, 1.0),
                                              (0.26, 1088 / 1065)])
def test_the_gauge_is_the_pure_function_of_a_planted_share(held_share,
                                                           ratio):
    import test_longcat_flash

    from ray_tpu.models import llama

    cfg = test_longcat_flash.program_cfg()
    places = 64 * CHUNK  # the chunk is 64 places here
    llama._note_assignments({"held_share": np.float32(held_share)}, places,
                            cfg)
    got = _places()
    assert got["live"] == round(held_share * places)
    assert got["made"] == moe.held_places_made(
        places, got["live"], cfg.num_experts,
        cfg.router_experts + cfg.zero_experts)
    assert got["made"] / got["live"] == pytest.approx(ratio)
    before = dict(got)
    llama._note_assignments({}, places, cfg)  # every expert here: not set
    assert _places() == before
