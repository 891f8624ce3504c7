"""A patterned stack (Mamba-2, routed with a shared expert, attention) through
the trainer's steps at the small widths of ``nemotron_h_small``: the SPMD
step on one device against ``data=2``, the GSPMD step, the loop's reports and
gauges, and the meshes and paths that refuse a pattern."""

import pytest

from jitted import init_params, loss_fn
from nemotron_h_small import (SEQ, assert_trees_close, jax, jnp, llama,
                              program_cfg, tokens)


def _mesh(spec_str, n):
    from ray_tpu.train.spmd import build_train_mesh

    return build_train_mesh(spec_str, jax.devices()[:n])


def _run(cfg, mesh, tokens, steps=2):
    from ray_tpu.train.spmd import make_spmd_train_step

    init, step, sharding, _ = make_spmd_train_step(cfg, mesh)
    state = init(jax.random.PRNGKey(0))
    out = []
    for _ in range(steps):
        state, loss, router = step(state, jax.device_put(tokens, sharding))
        out.append((float(loss), {k: float(v) for k, v in router.items()}))
    return out, state


def test_spmd_step_one_device_against_data2(tokens):
    cfg = program_cfg()
    one, state1 = _run(cfg, _mesh("", 1), tokens)
    two, state2 = _run(cfg, _mesh("data=2", 2), tokens)
    want = float(loss_fn(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                         tokens))
    assert one[0][0] == pytest.approx(want, rel=1e-5)
    for (l1, r1), (l2, r2) in zip(one, two):
        assert l1 == pytest.approx(l2, rel=2e-5)
        assert r1["held_share"] == pytest.approx(r2["held_share"])
        assert r1["dropped"] == r2["dropped"] == 0.0
        assert set(r1) == {"max_load_ratio", "dropped", "held_share",
                           "held_chunks"}
    assert one[1][0] < one[0][0]  # adamw learns
    assert_trees_close(state2["params"], state1["params"], rtol=1e-3,
                       atol=1e-4)
    # the choice bias is a parameter nothing updates
    assert float(jnp.abs(
        state1["params"]["layers"]["moe"]["router_bias"]).max()) == 0.0


@pytest.mark.parametrize("spec_str,n", [("fsdp=2", 2), ("data=2,fsdp=2", 4),
                                        ("tensor=2", 2)])
def test_a_pattern_on_fsdp_or_tensor_is_refused(spec_str, n):
    from ray_tpu.train.spmd import make_spmd_train_step

    with pytest.raises(ValueError) as e:
        make_spmd_train_step(program_cfg(), _mesh(spec_str, n))
    msg = str(e.value)
    assert "batch axes only" in msg and "per-kind" in msg
    assert "tensor-parallel form" in msg


def test_paths_with_their_own_block_refuse_a_pattern():
    cfg = program_cfg()
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        llama.LlamaDecodeEngine(cfg)
    from ray_tpu.parallel.mesh import make_mesh

    with pytest.raises(NotImplementedError, match="layer_pattern"):
        llama.make_pipeline_train_step(cfg, make_mesh(axis_sizes={"pipe": 2}),
                                       2)


def test_gspmd_step_runs_the_pattern(tokens):
    from ray_tpu.parallel.mesh import make_mesh

    cfg = program_cfg()
    init, step, sharding, _ = llama.make_train_step(
        cfg, make_mesh(devices=jax.devices()[:1]))
    state = init(jax.random.PRNGKey(0))
    _, loss = step(state, jax.device_put(tokens, sharding))
    want = float(loss_fn(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                         tokens))
    assert float(loss) == pytest.approx(want, rel=1e-5)


def test_loop_reports_held_share_and_sets_the_stack_gauge():
    from ray_tpu.train.session import TrainContext, set_context
    from ray_tpu.train.spmd import spmd_train_loop
    from ray_tpu.util import flight_recorder as fr
    from ray_tpu.util.metrics import registry

    # the recorder is the process's: this test reads its own instants alone
    # and leaves none for the next test that reads the ring
    fr.reset_for_tests()
    fr.configure(enabled=True)
    ctx = TrainContext(1, 0, 0, 1, 0)
    set_context(ctx)
    try:
        spmd_train_loop({"llama_config": program_cfg(), "steps": 2,
                         "seq": SEQ, "batch_per_device": 1,
                         "mesh": "data=1"})
        reports = [r.metrics for r in ctx._drain()]
        payload = fr.snapshot_payload()
    finally:
        set_context(None)
        fr.reset_for_tests()
    assert 0.0 < reports[-1]["moe_held_share"] < 1.0
    assert reports[-1]["moe_dropped"] == 0.0
    assert "moe_lb_loss" not in reports[-1]
    payload.update(source="test", node_hex="", offset_s=0.0)
    rep = fr.attribute_trace(fr.build_span_events([payload]))
    assert set(rep["router"]) == {"moe.max_load_ratio", "moe.dropped",
                                  "moe.held_share", "moe.held_chunks"}
    assert rep["router"]["moe.held_share"]["last"] == pytest.approx(
        reports[-1]["moe_held_share"])
    # the chunks the live places fill, a routed layer's mean, as an instant
    # of its own (a stat without one is a KeyError in the first report): at
    # this size a call's places are under one chunk, a layer reads 0 or 1
    assert rep["router"]["moe.held_chunks"]["last"] == pytest.approx(
        reports[-1]["moe_held_chunks"])
    assert 0.0 < reports[-1]["moe_held_chunks"] <= 1.0
    # the slowest step's line holds the held share OF THAT STEP
    slow = rep["slowest_step"]
    assert slow["router"]["moe.held_share"] == pytest.approx(
        reports[slow["step"] - 1]["moe_held_share"])
    slowest = fr.format_attribution(rep).split("slowest step")[1].split(
        "compile")[0]
    assert "moe.held_share" in slowest and "moe.held_chunks" in slowest
    gauge = registry().local_values("ray_tpu_train_stack")
    assert {k[0][1]: v for k, v in gauge.items()} == {
        "block_layers": 0.0, "mamba_layers": 4.0, "moe_layers": 4.0,
        "attn_layers": 1.0, "latent_layers": 0.0, "latent_dense_layers": 0.0,
        "mtp_layers": 0.0, "experts_held": 4.0, "router_experts": 32.0}
