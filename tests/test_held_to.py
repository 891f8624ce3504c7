"""``models/llama.py REFUSED`` / ``held_to``: one table says which builder
answers for which kind. Until PR 56 six functions (``_dense_only``,
``_no_window_kinds``, ``_no_delta_kinds``, ``_no_latent_kinds``,
``_no_wide_latent``, ``_no_parallel_kinds``) and twenty call sites said it;
they stand below AS THE TEST'S DATA, each builder's calls in the order it
made them, and the table has to raise what they raised, word for word."""

import dataclasses

import pytest

jax = pytest.importorskip("jax")

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig


def _kinds(cfg, kinds):
    return bool(set(cfg.kinds) & set(kinds))


# what each function tested and said: (refuses, "{who} takes ... yet: {why}")
OLD = {
    "_dense_only": lambda cfg: (
        cfg.num_experts or cfg.qk_norm or cfg.layer_pattern,
        f"no config with num_experts={cfg.num_experts}, "
        f"qk_norm={cfg.qk_norm}, layer_pattern={cfg.layer_pattern!r}"),
    "_no_window_kinds": lambda cfg: (
        _kinds(cfg, "FW"),
        f"no 'F' / 'W' layer (layer_pattern={cfg.layer_pattern!r}, "
        f"window={cfg.window})"),
    "_no_delta_kinds": lambda cfg: (
        _kinds(cfg, "DA"),
        f"no 'D' / 'A' layer (layer_pattern={cfg.layer_pattern!r})"),
    "_no_latent_kinds": lambda cfg: (
        _kinds(cfg, "LG") or cfg.mtp_layers,
        f"no 'L' / 'G' layer and no prediction module (layer_pattern="
        f"{cfg.layer_pattern!r}, mtp_layers={cfg.mtp_layers})"),
    "_no_latent_kinds(blocks=False)": lambda cfg: (
        cfg.mtp_layers,
        f"no prediction module (layer_pattern={cfg.layer_pattern!r}, "
        f"mtp_layers={cfg.mtp_layers})"),
    "_no_wide_latent": lambda cfg: (
        cfg.hc_mult > 1 or (_kinds(cfg, "LG") and cfg.qk_nope_head_dim
                            + cfg.qk_rope_head_dim != cfg.v_head_dim),
        f"no hyper-connections (hc_mult={cfg.hc_mult}) and no latent block "
        f"whose score width (qk_nope_head_dim + qk_rope_head_dim = "
        f"{cfg.qk_nope_head_dim + cfg.qk_rope_head_dim}) is not its "
        f"v_head_dim={cfg.v_head_dim}"),
    "_no_parallel_kinds": lambda cfg: (
        _kinds(cfg, "PR"),
        f"no 'P' / 'R' layer (layer_pattern={cfg.layer_pattern!r})"),
    # PR 58's row, which never was a function: the table's own words
    "_no_hybrid_kinds": lambda cfg: (
        _kinds(cfg, "HN"),
        f"no 'H' / 'N' layer (layer_pattern={cfg.layer_pattern!r}; "
        f"embedding_multiplier, attention_multiplier, residual_multiplier)"),
    # PR 63's row, likewise the table's own words
    "_no_selected_kinds": lambda cfg: (
        _kinds(cfg, "YZX"),
        f"no 'Y' / 'Z' / 'X' layer (layer_pattern={cfg.layer_pattern!r}, "
        f"first_layer={cfg.first_layer}, index_topk={cfg.index_topk})"),
    # PR 65's row, likewise
    "_no_memory_kinds": lambda cfg: (
        _kinds(cfg, "mwfgc"),
        f"no 'm' / 'w' / 'f' / 'g' / 'c' layer (layer_pattern="
        f"{cfg.layer_pattern!r}, ssm_expand={cfg.ssm_expand}, "
        f"window={cfg.window})"),
}

_NO_WAY_ACROSS_STAGES = (
    "its stages pass the residual stream alone: a selection made on "
    "one stage has no way to the shared layers of the next")
_NO_MEMORY_ACROSS_STAGES = (
    "its stages pass the residual stream alone: a memory or keys and "
    "values made on one stage have no way to the layers of the next")


def _engine_dense_only(cfg):
    """The engine's call of ``_dense_only``: only for a stack that is not
    one family of ``SERVED`` (or a part served alone), its reason built from
    the stack's kinds."""
    families = {}
    for c, kind in llama.SERVED.items():
        families[kind.family] = families.get(kind.family, "") + c
    kinds = set(llama.served_kinds(cfg))
    part = any(kinds <= set(family)
               and all(llama.SERVED[c].alone for c in kinds)
               for family in families.values())
    if cfg.layer_pattern and (part or kinds in map(set, families.values())):
        return None
    return (f"it serves the kinds of ONE family of its table (SERVED: "
            f"{' | '.join(families.values())}), all of them (both full "
            f"and window layers), and this stack has "
            f"{' '.join(sorted(kinds))}, of which the table lacks "
            f"{' '.join(sorted(kinds - set(llama.SERVED))) or 'none'}; for the "
            f"'M' / 'E' / '*' "
            f"halves, a part or a mix of families, whole-projection "
            f"QK-norm or an unpatterned routed block no test compares "
            f"its logits with the reference")


# each builder's calls, in its order, with the paragraph it gave
CALLS = {
    "make_spmd_train_step": [
        ("_no_window_kinds",
         "its layers attend through the flash kernel, which masks the "
         "causal triangle and has no window (forward and backward), and "
         "under fsdp / tensor a patterned stack has no per-kind gather; "
         "models.llama.loss_fn runs these kinds through attend_tiles"),
        ("_no_delta_kinds",
         "no train step is held to a reference for the gated delta rule's "
         "backward (autodiff through ops/gdn.py's chunked form) or the gated "
         "attention's; models.llama.loss_fn runs the forward of both"),
        ("_no_parallel_kinds",
         "no train step is held to a reference for the parallel block's "
         "backward, and its flash kernel has no window; "
         "models.llama.loss_fn runs the forward through attend_tiles"),
        ("_no_hybrid_kinds",
         "no train step is held to a reference for the hybrid blocks' "
         "backward or their three multipliers' (the Mamba-2 mixer's own is "
         "held as the 'M' half); models.llama.loss_fn runs the forward"),
        ("_no_selected_kinds",
         "no train step carries a selection from the layer that makes it to "
         "the layers that read it, masks its flash kernel by one, or is held "
         "to a reference for an indexer's backward; LlamaDecodeEngine "
         "serves these kinds"),
        ("_no_memory_kinds",
         "no train step carries a state-space layer's scan output or an "
         "attention layer's keys and values to the layers that read them, "
         "has a backward for the Mamba-1 scan (ops/s6_prefill.py is forward "
         "only) or is held to a reference for differential attention's; "
         "LlamaDecodeEngine serves these kinds"),
        ("_no_wide_latent",
         "no train step is held to a reference for the mixes' backward or "
         "keeps a stream of several rows' recomputation in its account, and "
         "its flash kernel attends q, k and v of one width; "
         "models.llama.loss_fn runs the forward")],
    "make_train_step": [
        ("_no_parallel_kinds",
         "no train step is held to a reference for the parallel block's "
         "backward; models.llama.loss_fn runs its forward"),
        ("_no_hybrid_kinds",
         "no train step is held to a reference for the hybrid blocks' "
         "backward or their three multipliers'; models.llama.loss_fn runs "
         "the forward"),
        ("_no_selected_kinds",
         "no train step carries a selection from the layer that makes it to "
         "the layers that read it or is held to a reference for an "
         "indexer's backward; LlamaDecodeEngine serves these kinds"),
        ("_no_memory_kinds",
         "no train step carries a state-space layer's scan output or an "
         "attention layer's keys and values to the layers that read them or "
         "is held to a reference for the Mamba-1 scan's backward; "
         "LlamaDecodeEngine serves these kinds"),
        ("_no_wide_latent",
         "no train step is held to a reference for the mixes' backward, and "
         "its flash kernel attends q, k and v of one width")],
    "make_pipeline_train_step": [
        ("_no_wide_latent",
         "its stages pass ONE row a token from stage to stage, and no train "
         "step is held to a reference for the mixes' backward"),
        ("_no_latent_kinds",
         "its stages run the dense block alone, and the prediction module's "
         "second loss needs the last stage's stream AND the first stage's "
         "embedding"),
        ("_no_parallel_kinds", "its stages run the dense block alone"),
        ("_no_hybrid_kinds", "its stages run the dense block alone"),
        ("_no_window_kinds",
         "its stages run the dense block over the flash kernel, which has "
         "no window, and pass no router's losses on"),
        ("_no_selected_kinds", _NO_WAY_ACROSS_STAGES),
        ("_no_memory_kinds", _NO_MEMORY_ACROSS_STAGES),
        ("_dense_only",
         "its stages pass the residual stream alone, so a router's losses "
         "have no way out, and its layer specs name the dense leaves only")],
    "the MPMD pipeline": [
        ("_no_selected_kinds", _NO_WAY_ACROSS_STAGES),
        ("_no_memory_kinds", _NO_MEMORY_ACROSS_STAGES),
        ("_dense_only",
         "its stages pass the residual stream alone, so a router's "
         "losses have no way out, and no test runs QK-norm through it")],
    "LlamaDecodeEngine": [
        ("_no_latent_kinds(blocks=False)",
         "a prediction module's self-drafted decode steps need a "
         "scheduler that takes more than one token a call"),
        ("_dense_only", _engine_dense_only)],
}


def _raised_before(cfg, who):
    for fn, why in CALLS[who]:
        refuses, words = OLD[fn](cfg)
        why = why(cfg) if callable(why) else why
        if refuses and why:
            return f"{who} takes {words} yet: {why}"
    return None


def _glm(**over):
    return __import__("test_xing4").glm_cfg(**over)


def _of(module):
    return lambda: __import__(module).program_cfg()


# a configuration a kind of the table, the two the engine serves in part or
# not at all, and two that meet several rows at once; made when first asked
# for (the other models' test modules are imported then)
CONFIGS = {
    "dense": LlamaConfig.debug,
    "QK-norm": lambda: dataclasses.replace(LlamaConfig.debug(), qk_norm=True),
    "routed (olmoe)": _of("test_olmoe"),
    "M E * (nemotron)": _of("nemotron_h_small"),
    "F W (smallthinker)": _of("test_smallthinker"),
    "D A (qwen3-next)": _of("test_qwen3_next"),
    "P R (command-a-plus)": _of("test_command_a_plus"),
    "H N (granite-4.0-h)": _of("test_granite_hybrid"),
    "H alone": lambda: __import__("test_granite_hybrid").program_cfg(
        num_hidden_layers=2),
    "L G and the module (glm)": _of("test_glm47_flash"),
    "L G, one row a token": _glm,
    "L G in part": lambda: _glm(layer_pattern="LLL"),
    "the module alone": lambda: _glm(mtp_layers=1),
    "four rows a token and a wide score (xing4)": _of("test_xing4"),
    "Y Z X (glm-5.2)": _of("test_glm_moe_dsa"),
    "m w f g c (phi-4-mini-flash)": _of("test_phi4_flash"),
    "m w alone": lambda: __import__("test_phi4_flash").program_cfg(
        num_hidden_layers=2),
}


@pytest.fixture(scope="module")
def configs():
    return {name: make() for name, make in CONFIGS.items()}


@pytest.mark.parametrize("name", list(CONFIGS))
@pytest.mark.parametrize("who", list(CALLS))
def test_the_table_raises_what_the_six_functions_raised(who, name, configs):
    cfg = configs[name]
    before = _raised_before(cfg, who)
    if before is None:
        llama.held_to(cfg, who)  # the builder runs it
        return
    with pytest.raises(NotImplementedError) as e:
        llama.held_to(cfg, who)
    assert str(e.value) == before


def test_every_row_names_builders_and_every_builder_calls_once(configs):
    """A row's reasons belong to builders of the table; every row of it is
    met by a configuration above; each builder's source calls ``held_to``
    once with its own name; an unknown name is an error, not a pass."""
    import inspect

    from ray_tpu.train import pipeline, spmd

    assert list(CALLS) == list(llama.BUILDERS)
    for name, row in llama.REFUSED.items():
        assert set(row.why) <= set(llama.BUILDERS), name
        assert any(row.present(cfg) for cfg in configs.values()), name
    sources = {
        "make_spmd_train_step": spmd.make_spmd_train_step,
        "make_train_step": llama.make_train_step,
        "make_pipeline_train_step": llama.make_pipeline_train_step,
        "the MPMD pipeline": pipeline._llama_stage_fwd,
        "LlamaDecodeEngine": llama.LlamaDecodeEngine.__init__}
    for who, fn in sources.items():
        assert inspect.getsource(fn).count("held_to(") == 1, who
        assert f'"{who}")' in inspect.getsource(fn), who
    with pytest.raises(AssertionError):
        llama.held_to(LlamaConfig.debug(), "make_some_other_step")
