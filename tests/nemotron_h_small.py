"""What the parts of the Nemotron-H tests share (``test_nemotron_h.py`` and
``test_nemotron_h_{mamba,routed,trainer}.py``): the published shape at small
widths, the program's config and seeded parameters for it, and how
the reference is run a sequence at a time (``jitted``: one program)."""

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from jitted import init_params, reference  # noqa: E402
from ray_tpu.models import llama  # noqa: E402,F401 (the parts take it from here)
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmarks", "configs",
                           "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16.json")
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# the published shape, small: the pattern's first 9 layers, 8 Mamba heads of
# 16 in 2 groups, state 16, chunk 16; a router over 32 of which this share
# holds 4 (from the 8th on), top-3; 4 query heads on 2, head width 32 (not
# hidden / heads = 16)
FILE = {
    "hidden_size": 64, "num_hidden_layers": 9,
    "hybrid_override_pattern": PUBLISHED_PATTERN,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "n_routed_experts": 4, "router_experts": 32, "first_expert": 8,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "mlp_hidden_act": "relu2",
    "router_scoring": "sigmoid", "rotary": False, "rope_theta": 10000,
    "vocab_size": 256, "max_position_embeddings": 128,
    "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
}
SEQ = 40  # two chunks of 16 and a rest of 8


def program_cfg(file=FILE, **over):
    with open(CONFIG_FILE) as f:
        fields = json.load(f)["program"]["fields"]
    kw = {field: file[key] for field, key in fields.items()}
    kw.update({"dtype": jnp.float32, **over})
    return LlamaConfig(**kw)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(
        0, FILE["vocab_size"], (2, SEQ + 1)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    p = init_params(program_cfg(), jax.random.PRNGKey(11))
    # scales, biases and skips away from their starting values, so that a
    # misplaced or forgotten one shows
    rng = np.random.RandomState(5)

    def jiggle(kind, name, lo, hi):
        leaf = p["layers"][kind][name]
        p["layers"][kind][name] = leaf + jnp.asarray(
            rng.uniform(lo, hi, leaf.shape), jnp.float32)

    for kind in ("mamba", "moe", "attn"):
        jiggle(kind, "norm", -0.5, 0.5)
    jiggle("mamba", "gate_norm", -0.5, 0.5)
    jiggle("mamba", "conv_b", -0.3, 0.3)
    jiggle("mamba", "D", -0.5, 0.5)
    jiggle("moe", "router_bias", -0.2, 0.2)
    return p


def layer(params, kind, row=0):
    return jax.tree.map(lambda a: a[row], params["layers"][kind])


def normed_inputs(seed=0, batch=2, seq=SEQ):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (batch, seq, FILE["hidden_size"]), jnp.float32)


def per_row(fn, h):
    """The reference takes one sequence: the rows in turn, one body."""
    return reference(lambda h: jax.lax.map(fn, h), h)


def assert_trees_close(got, want, rtol, atol):
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_w = jax.tree.leaves(want)
    assert len(flat_g) == len(flat_w)
    for (path, g), w in zip(flat_g, flat_w):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=atol * max(scale, 1e-3),
            err_msg=jax.tree_util.keystr(path))
