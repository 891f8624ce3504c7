"""A patterned stack (``LlamaConfig.layer_pattern``: Mamba-2, routed with a
shared expert, attention without rotation; each layer one half of the
block) against the plain float32 reference the benchmark keeps
(``benchmarks/reference/nemotron_h_decoder.py``), at small widths on the
CPU: the loss and every gradient leaf, the pattern as data, and the
benchmark's files. The layer kinds are held to the reference in
``test_nemotron_h_mamba.py`` and ``test_nemotron_h_routed.py``, the trainer's
steps in ``test_nemotron_h_trainer.py``; ``nemotron_h_small`` is what the
four share."""

import dataclasses
import json

import pytest

from benchmarks.lib import spec
from benchmarks.reference import nemotron_h_decoder as ref
from jitted import loss_fn, reference, value_and_grad
from nemotron_h_small import (CONFIG_FILE, FILE, PUBLISHED_PATTERN,
                              assert_trees_close, jax, jnp, llama, params,
                              program_cfg, tokens)
from ray_tpu.models.llama import LlamaConfig


# --- the whole model ---------------------------------------------------- #


def test_loss_and_every_gradient_leaf_match_reference(params, tokens):
    cfg = program_cfg()
    (loss, report), grads = value_and_grad(
        lambda p: llama.loss_parts(cfg, p, tokens), params, has_aux=True)
    want, g_want = value_and_grad(
        lambda p: ref.loss(FILE, p, jnp.asarray(tokens)), params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert_trees_close(grads, g_want, rtol=2e-3, atol=2e-5)
    assert set(report) == {"max_load_ratio", "dropped", "held_share",
                           "held_chunks"}
    assert float(report["dropped"]) == 0.0
    # remat is the same program's values
    again = loss_fn(dataclasses.replace(cfg, remat=False), params, tokens)
    assert float(again) == pytest.approx(float(loss), rel=1e-6)


def test_loss_bfloat16_near_the_reference(params, tokens):
    cfg = program_cfg(dtype=jnp.bfloat16)
    got = float(loss_fn(cfg, params, tokens))
    want = float(reference(lambda p: ref.loss(FILE, p, jnp.asarray(tokens)),
                           params))
    assert abs(got - want) / want < 5e-3  # widths of 64: a coarse bound


def test_the_pattern_is_data():
    cfg = program_cfg()
    assert cfg.kinds == "MEMEM*EME" and cfg.head_dim == 32
    two = program_cfg(dict(FILE, num_hidden_layers=2))  # --rehearsal's stack
    assert two.kinds == "ME"
    tree = jax.eval_shape(lambda: llama.init_params(two, jax.random.PRNGKey(0)))
    assert sorted(tree["layers"]) == ["mamba", "moe"]
    assert tree["layers"]["moe"]["w_up"].shape == (1, 4, 64, 48)
    assert tree["layers"]["moe"]["router"].shape == (1, 64, 32)
    with pytest.raises(ValueError, match="layer_pattern"):
        program_cfg(dict(FILE, hybrid_override_pattern="MEX"))
    with pytest.raises(ValueError, match="layer_pattern"):
        program_cfg(dict(FILE, hybrid_override_pattern="MEM"))
    with pytest.raises(ValueError, match="patterned stack"):
        LlamaConfig(num_experts=4, experts_per_token=2, shared_mlp_dim=8)
    # no pattern: the block in every layer, its tree and config as they were
    plain = LlamaConfig.debug()
    assert plain.layer_pattern == "" and plain.kinds == ""
    assert plain.head_dim == plain.dim // plain.n_heads


def test_num_params_counts_the_kinds_and_the_file():
    cfg = program_cfg()
    tree = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == cfg.num_params()
    with open(CONFIG_FILE) as f:
        file = json.load(f)
    real = spec.program_config(file)
    assert real.kinds == "MEMEM*EME" and real.head_dim == 128
    assert file["hybrid_override_pattern"] == PUBLISHED_PATTERN
    assert len(PUBLISHED_PATTERN) == file["published"]["num_hidden_layers"]
    # 4 x 38,744,896 + 4 x 100,125,440 + 23,399,040 + 88,080,384 + 2,688
    assert real.num_params() == 666_963_456
    tree = jax.eval_shape(lambda: llama.init_params(real,
                                                   jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(tree)) == 666_963_456
    assert tree["layers"]["mamba"]["w_in"].shape == (4, 2688, 10304)
    assert tree["layers"]["moe"]["w_up"].shape == (4, 8, 2688, 1856)
    assert tree["layers"]["moe"]["router"].shape == (4, 2688, 128)
    assert tree["layers"]["attn"]["wq"].shape == (1, 2688, 4096)
    assert tree["layers"]["attn"]["wk"].shape == (1, 2688, 256)


# --- the programs that were, and the benchmark's files ------------------ #


def test_an_empty_pattern_lowers_to_the_pinned_texts(monkeypatch):
    """The routed and the dense step of ``tests/test_olmoe.py``, whose
    hashes that file pins (with the block's names off, as there): the new
    fields at their defaults build them."""
    import hashlib

    import test_olmoe

    test_olmoe.turn_names_off(monkeypatch)
    for name in ("routed spmd, one device", "dense spmd, one device"):
        sha, lower = test_olmoe.PROGRAMS[name]
        assert hashlib.sha256(lower().encode()).hexdigest() == sha


def test_benchmark_files_fit_together_with_the_new_cell():
    from benchmarks.checks import test_yardstick

    test_yardstick.test_benchmark_files_fit_together()
    b = spec.cell_bundle("train-nemotron3nano-1chip")
    assert b["traffic"]["seq"] == 8192 and b["cell"]["chips"] == 1
    assert sorted(m["name"] for m in b["end_to_end"]) == [
        "setup_s", "train_tokens_per_s_chip"]
    assert "train.mfu" not in {m["name"] for m in b["per_layer"]}
    file = b["config"]
    assert (file["n_routed_experts"], file["router_experts"]) == (8, 128)
    tiny = spec.cell_bundle("train-nemotron3nano-1chip", rehearsal=True)
    assert spec.program_config(tiny["config"]).kinds == "ME"
