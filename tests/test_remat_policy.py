"""What the trainer's step keeps of its forward pass (``models.llama
KEEP_GROUPS``, ``train/spmd.py _KeepingStep``): keeping changes no bit of a
step, the choice as a function of made-up accounts, and a kept step's
backward really makes fewer products."""

import dataclasses
import itertools
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.llama import KEEP_GROUPS, LlamaConfig  # noqa: E402
from ray_tpu.parallel.mesh import make_mesh  # noqa: E402
from ray_tpu.train import spmd  # noqa: E402
from ray_tpu.train.spmd import (  # noqa: E402
    choose_kept,
    keeps_what_it_should,
    kept_group_bytes,
    make_spmd_train_step,
    program_bytes,
)
from ray_tpu.util.metrics import registry  # noqa: E402

SEQ, BATCH = 64, 4
SUBSETS = [s for r in (1, 2, 3) for s in itertools.combinations(KEEP_GROUPS, r)]


def _cfg(kind):
    """Two layers under ``jax.checkpoint`` and a loss of four chunks: every
    group has something to keep (the routed half has no ``mlp`` names)."""
    base = LlamaConfig.debug()
    if kind == "routed":
        base = dataclasses.replace(base, mlp_dim=32, num_experts=8,
                                   experts_per_token=2, qk_norm=True)
    return dataclasses.replace(base, remat=True, loss_chunk=16)


def _mesh():
    return make_mesh(devices=jax.devices()[:1])


def _tokens(cfg, seed=0):
    return np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (BATCH, SEQ + 1)).astype(np.int32)


def _one_step(cfg, **kw):
    """The state and scalars after one step from PRNGKey(0), as numpy."""
    init, step, ds, _ = make_spmd_train_step(cfg, _mesh(), donate=False,
                                             **kw)
    out = step(init(jax.random.PRNGKey(0)),
               jax.device_put(_tokens(cfg), ds))
    return jax.tree.map(np.asarray, out), step


def _assert_same_bits(a, b):
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        assert x.tobytes() == y.tobytes(), jax.tree_util.keystr(path)


# --- (a) keeping changes no bit --------------------------------------------- #


@pytest.fixture(scope="module")
def nothing_kept():
    return {kind: _one_step(_cfg(kind), keep=())[0]
            for kind in ("dense", "routed")}


@pytest.mark.parametrize("kind", ["dense", "routed"])
@pytest.mark.parametrize("keep", SUBSETS, ids="+".join)
def test_a_kept_step_is_the_step_bit_for_bit(kind, keep, nothing_kept):
    """The loss, and every leaf of the parameters and of both adamw moments
    after one step (the first moment is 0.1 g, the second 0.05 g^2: equal
    bits there are equal bits in every gradient leaf)."""
    _assert_same_bits(nothing_kept[kind], _one_step(_cfg(kind), keep=keep)[0])


def test_the_kernels_residuals_are_kept_and_the_forward_kernel_not_rerun(
        monkeypatch):
    """With the flash kernel (interpreted) as ``attend``: ``attn`` keeps its
    output and log-sum-exp, so the step has three kernel calls where the
    step that keeps nothing has four, and not a bit of it changes."""
    from ray_tpu.ops.flash_attention import flash_attention

    monkeypatch.setattr(llama, "flash_causal", partial(
        flash_attention, causal=True, interpret=True))
    cfg = _cfg("dense")
    got = {}
    for keep in ((), ("attn",)):
        out, step = _one_step(cfg, keep=keep)
        state = jax.eval_shape(lambda: out[0])
        jaxpr = jax.make_jaxpr(step._fn)(
            state, jax.ShapeDtypeStruct((BATCH, SEQ + 1), jnp.int32))
        got[keep] = (out, str(jaxpr).count("pallas_call["))
    assert (got[()][1], got[("attn",)][1]) == (4, 3)
    _assert_same_bits(got[()][0], got[("attn",)][0])


# --- (b) the choice --------------------------------------------------------- #

GB = 10 ** 9
GROUPS = {"attn": 1 * GB, "mlp": 2 * GB, "head": 1 * GB}


@pytest.mark.parametrize("layers_room, loss_room, want", [
    (4 * GB, 9 * GB, ("attn", "mlp", "head")),   # everything fits
    (1 * GB, 1 * GB, ("attn",)),    # one group fits; a tie goes to attn
    (2 * GB, 2 * GB, ("attn", "head")),  # 2 GB twice: the one with attn
    (2 * GB, 9 * GB, ("mlp", "head")),  # head needs no room of the layers'
    (0, 9 * GB, ("head",)),
    (GB - 1, GB - 1, ()),           # nothing fits
    (4 * GB, GB - 1, ()),           # nor where the loss has no room
])
def test_the_subset_with_the_most_bytes_that_fits(layers_room, loss_room,
                                                  want):
    assert choose_kept(GROUPS, layers_room, loss_room) == want


def test_a_tie_goes_to_the_subset_with_attn():
    groups = {"attn": 2 * GB, "mlp": 1 * GB, "head": 1 * GB}
    assert choose_kept(groups, 2 * GB, 2 * GB) == ("attn",)
    assert choose_kept({"mlp": GB, "head": GB}, GB, GB) == ("mlp",)
    assert choose_kept({}, 9 * GB, 9 * GB) == ()


def test_groups_follow_from_shapes_and_only_those_the_step_has():
    mistral = LlamaConfig(vocab_size=32768, dim=4096, n_layers=2,
                          n_heads=32, n_kv_heads=8, mlp_dim=14336)
    assert kept_group_bytes(mistral, 8, 2048) == {
        "attn": 2 * 16384 * ((2 * 4096 + 2 * 1024 + 4096) * 2 + 4 * 32),
        "mlp": 2 * 16384 * 2 * 14336 * 2,
        "head": 16384 * 32768 * 2}
    # a config without remat has no checkpoint around its layers
    assert set(kept_group_bytes(dataclasses.replace(mistral, remat=False),
                                8, 2048)) == {"head"}
    # an unchunked loss keeps its logits anyway; routed halves have no names
    assert "head" not in kept_group_bytes(mistral, 8, 256)
    routed = dataclasses.replace(mistral, num_experts=8, experts_per_token=2)
    assert set(kept_group_bytes(routed, 8, 2048)) == {"attn", "head"}
    halves = kept_group_bytes(mistral, 8, 2048, tensor=2)
    assert halves["mlp"] * 2 == kept_group_bytes(mistral, 8, 2048)["mlp"]


def test_the_account_is_the_peak_where_the_compiler_gives_one():
    memory = {"argument": 8, "output": 8, "alias": 7, "temp": 5, "code": 1}
    assert program_bytes(memory) == 8 + 1 + 5 + 1
    assert program_bytes(dict(memory, peak=12)) == 12


def test_the_second_program_is_held_to_its_own_account_and_flops():
    base = {"memory": {"peak": 90 * GB}, "flops": 100.0}
    limit = 100 * GB  # less the margin: 92 GB
    ok = {"memory": {"peak": 91 * GB}, "flops": 90.0}
    assert keeps_what_it_should(base, ok, limit) == ""
    over = {"memory": {"peak": 93 * GB}, "flops": 90.0}
    assert "over the first program's" in keeps_what_it_should(
        base, over, limit)
    # under the first program's account it may run whatever the margin
    tight = {"memory": {"peak": 95 * GB}, "flops": 100.0}
    assert keeps_what_it_should(
        tight, {"memory": {"peak": 94 * GB}, "flops": 90.0}, limit) == ""
    undone = {"memory": {"peak": 91 * GB}, "flops": 101.0}
    assert "recomputes more" in keeps_what_it_should(base, undone, limit)
    assert keeps_what_it_should(base, {"flops": 1.0}, limit)


@pytest.fixture
def counted(monkeypatch):
    """The step built for the dense config with every compile counted and a
    made-up device limit: ``run(limit)`` -> (outputs, kept gauge)."""
    compiles = []
    real = spmd._KeepingStep._compile

    def compile_(self, fn, args, kwargs):
        compiles.append(fn)
        return real(self, fn, args, kwargs)

    monkeypatch.setattr(spmd._KeepingStep, "_compile", compile_)
    monkeypatch.setattr(spmd, "_BUILT", {})

    def run(limit):
        monkeypatch.setattr(spmd, "_device_limit", lambda device: limit)
        del compiles[:]
        out, _ = _one_step(_cfg("dense"))
        gauge = {k[0][1]: v for k, v in registry().local_values(
            "ray_tpu_train_kept_bytes").items()}
        return out, gauge, len(compiles)

    return run


def test_no_limit_reported_keeps_nothing_in_one_compile(counted,
                                                        nothing_kept):
    out, gauge, compiles = counted(None)
    assert compiles == 1
    assert {gauge[g] for g in KEEP_GROUPS} == {0.0}
    _assert_same_bits(nothing_kept["dense"], out)


def test_room_for_everything_keeps_everything_in_two_compiles(
        counted, nothing_kept):
    cfg = _cfg("dense")
    out, gauge, compiles = counted(10 ** 12)
    assert compiles == 2
    assert {g: gauge[g] for g in KEEP_GROUPS} == {
        g: float(b) for g, b in kept_group_bytes(cfg, BATCH, SEQ).items()}
    assert gauge["headroom"] > 0
    _assert_same_bits(nothing_kept["dense"], out)
    # the same step built again in this process: that executable
    out, gauge, compiles = counted(10 ** 12)
    assert compiles == 0 and gauge["attn"] > 0
    _assert_same_bits(nothing_kept["dense"], out)


def test_an_account_within_the_margin_of_the_limit_is_left_alone(counted):
    init, step, ds, _ = make_spmd_train_step(_cfg("dense"), _mesh(),
                                             donate=False, keep=())
    state = init(jax.random.PRNGKey(0))
    account = program_bytes(spmd.analyses(step._fn.lower(
        state, jax.device_put(_tokens(_cfg("dense")), ds)).compile())[
            "memory"])
    _, gauge, compiles = counted(int(account / (1 - spmd.KEEP_MARGIN)))
    assert compiles == 1
    assert {gauge[g] for g in KEEP_GROUPS} == {0.0}
    assert gauge["headroom"] == 0.0


def test_a_second_program_that_fails_its_check_falls_back(
        counted, monkeypatch, nothing_kept):
    monkeypatch.setattr(spmd, "keeps_what_it_should",
                        lambda base, kept, limit: "made up")
    out, gauge, compiles = counted(10 ** 12)
    assert compiles == 2
    assert {gauge[g] for g in KEEP_GROUPS} == {0.0}
    _assert_same_bits(nothing_kept["dense"], out)


def test_a_second_program_the_compiler_refuses_falls_back(
        counted, monkeypatch, nothing_kept):
    counting = spmd._KeepingStep._compile

    def refusing(self, fn, args, kwargs):
        if fn is not self._fn:
            raise RuntimeError("RESOURCE_EXHAUSTED: made up")
        return counting(self, fn, args, kwargs)

    monkeypatch.setattr(spmd._KeepingStep, "_compile", refusing)
    out, gauge, compiles = counted(10 ** 12)
    assert compiles == 1  # the refused one never got as far as a count
    assert {gauge[g] for g in KEEP_GROUPS} == {0.0}
    _assert_same_bits(nothing_kept["dense"], out)


def test_the_choice_does_not_hang_on_the_observatory(counted, nothing_kept):
    from ray_tpu.core.config import global_config
    from ray_tpu.util import xla_observatory as xo

    knobs = global_config()
    xo.reset_for_tests()
    knobs.xla_observatory_enabled = False
    try:
        out, gauge, compiles = counted(10 ** 12)
    finally:
        knobs.xla_observatory_enabled = True
    assert compiles == 2 and gauge["mlp"] > 0
    assert xo.get_program("spmd.train_step") is None  # nothing recorded
    _assert_same_bits(nothing_kept["dense"], out)


def test_the_streamed_fsdp_step_is_left_as_it_was():
    """With a live ``fsdp`` axis and the streamed gather the step is the
    plain observed jit it was: nothing chosen, nothing kept, its loss's
    chunks neither (``tests/test_olmoe.py`` pins its text)."""
    from ray_tpu.util.xla_observatory import ObservedFunction

    mesh = make_mesh(axis_sizes={"fsdp": 4}, devices=jax.devices()[:4])
    step = make_spmd_train_step(_cfg("dense"), mesh)[1]
    assert type(step) is ObservedFunction
    upfront = make_spmd_train_step(_cfg("dense"), mesh, gather="upfront")[1]
    assert type(upfront) is spmd._KeepingStep


# --- (c) a kept step's backward makes fewer products ------------------------- #


def _dots(cfg, keep):
    init, step, _, _ = make_spmd_train_step(cfg, _mesh(), keep=keep)
    state = jax.eval_shape(init._fn, jax.random.PRNGKey(0))
    return step._fn.lower(state, jax.ShapeDtypeStruct(
        (BATCH, SEQ + 1), jnp.int32)).as_text().count("stablehlo.dot_general")


def test_a_kept_steps_backward_has_fewer_products():
    """In the lowered text (a scan's body stands once): q / k / v and wo
    are 4 products a layer, gate and up 2, the logits 1 a chunk."""
    cfg = _cfg("dense")
    nothing = _dots(cfg, ())
    assert nothing - _dots(cfg, ("attn",)) >= 4
    assert nothing - _dots(cfg, ("mlp",)) == 2
    assert nothing - _dots(cfg, ("head",)) == 1
    assert nothing - _dots(cfg, KEEP_GROUPS) >= 7
