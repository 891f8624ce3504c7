"""The held path's forward loop (``ops/moe.py _held_chunks``) against the
block form it replaced and against a plain per-assignment float32 reference,
at a chunk small enough that a few hundred places are many chunks; which
form a call takes; a differentiated call's tiers (``held_tiers``) against the
one straight block; the places' gauge."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from ray_tpu.ops import moe  # noqa: E402

CHUNK = 64
N, K, D, F = 128, 4, 32, 48
A = N * K
EXPERTS, COUNT = 16, 4  # the even blocks' rule: two blocks of 256 places
OLD_BLOCK = A // 2
TIERS = (0, CHUNK, OLD_BLOCK, A)  # a differentiated call's: held_tiers
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
    serves every ``end``. The block form over the two EVEN blocks that a
    differentiated call made until PR 57."""
    layer = LAYER if stacked else None

    def loop(hf, top_w, order, starts, end, weights):
        assert moe.held_chunk(A, COUNT, EXPERTS) == CHUNK
        return moe._held_rows(hf, top_w, order, starts, end, weights,
                              EXPERTS, layer, act)

    def blocks(hf, top_w, order, starts, end, weights):
        return moe._held_blocks(hf, top_w, order, starts, end, weights,
                                (0, OLD_BLOCK, A), layer, act)

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


def _conds(jaxpr):
    """``[(depth, outputs' shapes)]`` of every ``cond`` in ``jaxpr``, a
    ``cond`` inside a ``cond``'s branch one deeper; what is no ``cond`` (a
    ``checkpoint``, a ``jit``) is looked through."""
    out = []
    for eqn in jaxpr.eqns:
        inner = [c for sub in jax.core.jaxprs_in_params(eqn.params)
                 for c in _conds(sub)]
        if eqn.primitive.name == "cond":
            out.append((0, [v.aval.shape for v in eqn.outvars]))
            inner = [(depth + 1, shapes) for depth, shapes in inner]
        out += inner
    return out


@functools.lru_cache(maxsize=None)
def _differentiated(act):
    """``(tiers, straight)``: value and the three gradients of ``sum(y *
    probe)`` through :func:`moe._held_rows` (a differentiated call: the
    tiers) and through ONE block over every place, ``end`` traced."""

    def through(rows):
        def loss(hf, top_w, weights, order, starts, end, probe):
            return jnp.sum(rows(hf, top_w, order, starts, end, weights)
                           * probe)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    return (through(lambda *a: moe._held_rows(*a, EXPERTS, None, act)),
            through(lambda *a: moe._held_blocks(*a, (0, A), None, act)))


def test_the_tiers_edges_are_a_chunk_then_the_even_blocks(monkeypatch):
    assert moe.held_tiers(A, COUNT, EXPERTS) == TIERS
    # a chunk no smaller than a block: the first tier is the block
    monkeypatch.setattr(moe, "held_chunk", lambda *shape: OLD_BLOCK)
    assert moe.held_tiers(A, COUNT, EXPERTS) == (0, OLD_BLOCK, A)
    monkeypatch.setattr(moe, "held_chunk", lambda *shape: OLD_BLOCK + 64)
    assert moe.held_tiers(A, COUNT, EXPERTS) == (0, OLD_BLOCK, A)
    monkeypatch.undo()  # the rule itself, at the two train cells' shapes
    glm = moe.held_tiers(65536, 8, 64)
    nemotron = moe.held_tiers(98304, 8, 128)
    assert glm == (0, 11264, 32768, 65536)
    assert nemotron == (0, 8448, 24576, 49152, 73728, 98304)
    # the CPU's loss_fn tests: LongCat, Keye, Qwen3-Next, Command A+; half of
    # the experts held (ONE block until PR 57); places the blocks do not divide
    for places, count, experts in [
            (65536, 8, 64), (98304, 8, 128), (3072, 16, 768), (4096, 16, 128),
            (5120, 128, 512), (2048, 16, 128), (4096, 8, 16), (1000, 4, 64)]:
        edges = moe.held_tiers(places, count, experts)
        c = moe.held_chunk(places, count, experts)
        blocks = max(1, min(max(experts // (4 * count),
                                min(2, experts // (2 * count))),
                            places // 128))
        block = places // blocks if places % blocks == 0 else places
        assert edges[0] == 0 and edges[-1] == places
        assert all(lo < hi for lo, hi in zip(edges, edges[1:]))
        assert edges[1] == min(c, block)  # the loop's chunk, or the block
        assert max(hi - lo for lo, hi in zip(edges, edges[1:])) <= block
        assert set(range(block, places + 1, block)) <= set(edges)


@pytest.mark.parametrize("act", ACTS)
@pytest.mark.parametrize("end", list(ENDS))
def test_a_differentiated_call_is_the_tiers(end, act):
    """``jax.grad`` through ``_held_rows``: value and all three gradients
    against ONE straight block over every place, to float32 tolerance (the
    order of a token's additions moves with the tiers, nothing else)."""
    end = ENDS[end]
    group, order, starts = _routing(end)
    hf, top_w = _operands()
    weights = _weights(act, False)
    probe = jax.random.normal(jax.random.PRNGKey(11), (N, D), jnp.float32)
    tiers, straight = _differentiated(act)
    args = (hf, top_w, weights, order, starts, jnp.int32(end), probe)
    (y, got), (y0, want) = tiers(*args), straight(*args)
    np.testing.assert_allclose(float(y), float(y0), rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert (float(jnp.abs(w).max()) > 0) == (end > 0)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)
    # an expert with no place before ``end``, a token with no live
    # assignment: no gradient at all
    counts = np.diff(np.append(starts, end))
    for w in jax.tree.leaves(got[2]):
        assert not np.asarray(w)[counts == 0].any()
    untouched = np.ones(N, bool)
    untouched[np.flatnonzero(group < COUNT) // K] = False
    assert not np.asarray(got[0])[untouched].any()


@pytest.mark.parametrize("act", ACTS)
def test_a_differentiated_calls_program_is_nested_conds_and_no_loop(act):
    """``jax.grad`` and ``jax.jvp`` through ``routed_mlp(held=)``: no loop in
    the program; the tiers behind the first under ``cond`` s of which the
    outer jaxpr holds ONE a pass (a tier's branch holds the next tier's), so
    a common step adds one ``[N, d]`` of zeros however many tiers."""
    hf, router, weights = _operands()[0], _router(), _weights(act, False)

    def loss(hf, router, weights):
        return jnp.sum(_routed(hf, router, weights, act) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    jaxpr = jax.make_jaxpr(grad)(hf, router, weights)
    assert _count(jaxpr.jaxpr, "while") == 0
    conds = _conds(jaxpr.jaxpr)
    # the OUTER jaxpr holds one ``cond`` a pass, forward and transpose; the
    # tier behind (TIERS has three: two levels) is inside their branches: in
    # the forward's, and in the transpose's made again and transposed
    assert sorted(depth for depth, _ in conds) == [0, 0, 1, 1, 1]
    outer = [shapes for depth, shapes in conds if depth == 0]
    assert all(shapes.count((N, D)) >= 1 for shapes in outer)
    # ONE set of the branch's inputs is handed from pass to pass, however
    # many tiers (the held experts' gate and up once each), and ONE set of
    # gradients comes back
    assert [shapes.count((COUNT, D, F)) for shapes in outer] == [
        1 if act == "relu2" else 2] * 2
    got = grad(hf, router, weights)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(got))
    # forward-mode too goes through the tiers
    jvp = jax.make_jaxpr(lambda h, t: jax.jvp(
        lambda h: _routed(h, router, weights, act), (h,), (t,)))(hf, hf)
    assert _count(jvp.jaxpr, "while") == 0
    assert [depth for depth, _ in _conds(jvp.jaxpr)] == [0, 1]


def _leave_dead_rows_unwritten(monkeypatch):
    """On the TPU the grouped product leaves the rows of no group unwritten:
    from here on every such row of ``_expert_ffn`` comes back NaN. Returns
    the list that takes the row count of every call traced."""
    real, poisoned = moe._expert_ffn, []

    def unwritten(xs, w_gate, w_up, w_down, counts, layer=None, act="swiglu"):
        ys = real(xs, w_gate, w_up, w_down, counts, layer, act)
        dead = jnp.arange(xs.shape[0]) >= counts.sum()
        poisoned.append(xs.shape[0])
        return jnp.where(dead[:, None], jnp.nan, ys)

    monkeypatch.setattr(moe, "_expert_ffn", unwritten)
    return poisoned


def test_a_tiers_body_is_traced_once_a_size(monkeypatch):
    """Two differentiated programs in one process (the trainer builds its
    step twice, and GLM's holds two routed stacks): a tier's body (the
    jitted ``_tier_sum``) is traced once a size, six bodies in three."""
    hf, router, weights = _operands()[0], _router(), _weights("swiglu", False)
    real, traced = moe._place_rows, []

    def counted(*args):
        traced.append(args[-1])
        return real(*args)

    monkeypatch.setattr(moe, "_place_rows", counted)
    moe._tier_sum.clear_cache()
    try:
        for scale in (1.0, 2.0):
            jax.make_jaxpr(jax.grad(lambda h: jnp.sum(
                scale * _routed(h, router, weights))))(hf)
    finally:
        moe._tier_sum.clear_cache()
    assert sorted(traced) == [hi - lo for lo, hi in zip(TIERS, TIERS[1:])]


@pytest.mark.parametrize("end", ["1", "c+1", "a quarter",
                                 "past the old block"])
def test_a_row_of_nan_behind_end_never_reaches_a_gradient(end, monkeypatch):
    """As ``test_a_row_of_nan_behind_end_never_reaches_the_sum``, through a
    differentiated call: the dead rows of every tier made come back NaN,
    and neither the value nor a gradient sees one."""
    end = ENDS[end]
    _, order, starts = _routing(end)
    hf, top_w = _operands()
    weights = _weights("swiglu", False)
    probe = jax.random.normal(jax.random.PRNGKey(11), (N, D), jnp.float32)

    def loss(hf, top_w, weights):
        return jnp.sum(moe._held_rows(hf, top_w, order, starts,
                                      jnp.int32(end), weights, EXPERTS)
                       * probe)

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    clean = grad(hf, top_w, weights)
    poisoned = _leave_dead_rows_unwritten(monkeypatch)
    moe._tier_sum.clear_cache()  # a tier's body is traced once a size
    try:
        got = grad(hf, top_w, weights)
    finally:
        moe._tier_sum.clear_cache()
    # every tier's body is traced (a branch is, taken or not)
    assert set(poisoned) == {hi - lo for lo, hi in zip(TIERS, TIERS[1:])}
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(clean)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("end", list(ENDS))
def test_routed_mlp_counts_the_chunks_the_live_places_fill(end):
    """``stats["held_chunks"]`` is ``ceil(end / chunk)`` of the router's own
    ``end``: 1.0 exactly when a differentiated call makes its first tier and
    no other, whichever form runs."""
    end = ENDS[end]
    hf = _operands()[0]
    # planted logits: the first ``end`` assignments (token by token, choice
    # by choice) fall on held experts 4 .. 7, the others on 0 .. 3
    logits = np.full((N, D), -30.0, np.float32)
    for a in range(A):
        t, j = divmod(a, K)
        logits[t, 4 + j if a < end else j] = 10.0 - j
    router = jnp.eye(D, EXPERTS, dtype=jnp.float32)  # logits = the input
    weights = _weights("swiglu", False)

    def call(hf):
        y, stats = moe.routed_mlp(hf, router, *weights, top_k=K,
                                  held=(4, COUNT),
                                  router_input=jnp.asarray(logits))
        return y.sum(), stats

    forward = call(hf)[1]
    (_, stats), _ = jax.value_and_grad(call, has_aux=True)(hf)
    for st in (forward, stats):  # the loop's form and the tiers'
        assert st["held_chunks"].dtype == jnp.float32
        assert float(st["held_share"]) == pytest.approx(end / A)
        assert float(st["dropped"]) == 0.0
        assert float(st["held_chunks"]) == -(-end // CHUNK)
    made = moe.held_places_made(A, end, COUNT, EXPERTS, differentiated=True)
    assert (float(stats["held_chunks"]) <= 1.0) == (made == CHUNK)


@pytest.mark.parametrize("places,live,made", [
    (12, 3, 12), (CHUNK, 0, CHUNK), (A, 0, CHUNK), (A, 1, CHUNK),
    (A, CHUNK, CHUNK), (A, CHUNK + 1, OLD_BLOCK), (A, OLD_BLOCK, OLD_BLOCK),
    (A, OLD_BLOCK + 1, A), (A, A, A)])
def test_a_differentiated_calls_places_are_whole_tiers(places, live, made):
    """``held_places_made(differentiated=True)`` reads ``held_tiers``' edges:
    the first tier whatever ``live`` is, then whole tiers up to it, and never
    more than the even blocks made for the same ``live``."""
    got = moe.held_places_made(places, live, COUNT, EXPERTS,
                               differentiated=True)
    assert got == made
    if places > CHUNK:
        assert got in TIERS[1:] and got >= live
        assert got <= max(1, -(-live // OLD_BLOCK)) * OLD_BLOCK


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
    poisoned = _leave_dead_rows_unwritten(monkeypatch)
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
