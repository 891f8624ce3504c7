"""SPMD sharded training (train/spmd.py) on the virtual 8-device mesh:
partition rules, shard/gather round-trips, sharding invariance, the
shard_map train step's parity with GSPMD, donation, sharded ingest, and
the devices=1 JaxTrainer smoke path."""

import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, init_params, make_train_step
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.train.spmd import (
    build_train_mesh,
    llama_partition_rules,
    make_shard_and_gather_fns,
    make_spmd_train_step,
    match_partition_rules,
    parse_mesh_spec,
    tree_paths,
)


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.debug()


@pytest.fixture(scope="module")
def tokens(cfg):
    rng = np.random.RandomState(0)
    return rng.randint(0, cfg.vocab_size, (8, 33)).astype(np.int32)


# --------------------------------------------------------------------------- #
# partition rules
# --------------------------------------------------------------------------- #


def test_match_partition_rules_llama_tree(cfg):
    """Every llama param leaf gets a spec; matrices shard, norms and
    scalars replicate; paths drive the regex match."""
    import jax
    from jax.sharding import PartitionSpec as P

    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = match_partition_rules(llama_partition_rules(), params)
    assert specs["embedding"] == P("tensor", "fsdp")
    assert specs["layers"]["wq"] == P(None, "fsdp", "tensor")
    assert specs["layers"]["wo"] == P(None, "tensor", "fsdp")
    assert specs["layers"]["attn_norm"] == P()  # norm$ rule
    assert specs["final_norm"] == P()
    assert specs["lm_head"] == P("fsdp", "tensor")
    # paths are '/'-joined key paths
    names = tree_paths(params)
    assert names["layers"]["wq"] == "layers/wq"


def test_match_partition_rules_unmatched_leaf_raises():
    import jax
    from jax.sharding import PartitionSpec as P

    tree = {"mystery": np.zeros((4, 4), np.float32)}
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules(((r"known$", P("fsdp")),), tree)
    # scalars replicate without needing a rule
    out = match_partition_rules((), {"s": np.float32(1.0)})
    assert out["s"] == P()


def test_match_partition_rules_error_names_path_and_candidates():
    """The unmatched-leaf error carries the '/'-joined path AND the
    nearest rule patterns — the first thing a new model hits."""
    from jax.sharding import PartitionSpec as P

    tree = {"blocks": {"wq_new": np.zeros((4, 4), np.float32)}}
    rules = ((r"layers/w(q|k|v)$", P(None, "fsdp", "tensor")),
             (r"norm$", P()))
    with pytest.raises(ValueError) as e:
        match_partition_rules(rules, tree)
    msg = str(e.value)
    assert "blocks/wq_new" in msg          # the full path, not a leaf name
    assert "layers/w(q|k|v)$" in msg       # nearest-rule candidate
    assert "add a (regex, PartitionSpec)" in msg


def test_parse_mesh_spec_and_build():
    assert parse_mesh_spec("data=4,fsdp=2") == {"data": 4, "fsdp": 2}
    assert parse_mesh_spec("") == {}
    with pytest.raises(ValueError):
        parse_mesh_spec("data:4")
    mesh = build_train_mesh("data=2,fsdp=4")
    assert dict(mesh.shape) == {"data": 2, "fsdp": 4}
    assert build_train_mesh("").size == 8  # all local (virtual) devices
    with pytest.raises(ValueError, match="devices"):
        build_train_mesh("data=64")


# --------------------------------------------------------------------------- #
# shard/gather + sharding invariance (satellite: 1xN vs Nx1)
# --------------------------------------------------------------------------- #


def test_shard_gather_round_trip_byte_identical(cfg):
    """shard → gather is byte-identical per leaf, on two layouts."""
    import jax

    params = jax.device_get(init_params(cfg, jax.random.PRNGKey(3)))
    specs = match_partition_rules(llama_partition_rules(), params)
    for mc in [MeshConfig(data=1, fsdp=8), MeshConfig(data=2, fsdp=4)]:
        mesh = make_mesh(mc)
        shard_fns, gather_fns = make_shard_and_gather_fns(specs, mesh)
        sharded = jax.tree.map(lambda f, x: f(x), shard_fns, params)
        # fsdp-sharded leaves actually shard (not silently replicated)
        emb_shards = sharded["embedding"].addressable_shards
        assert len({str(s.index) for s in emb_shards}) == mesh.shape["fsdp"]
        back = jax.tree.map(lambda f, x: jax.device_get(f(x)),
                            gather_fns, sharded)
        for pa, pb in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            assert np.asarray(pa).tobytes() == np.asarray(pb).tobytes()


def test_same_seed_init_invariant_across_mesh_layouts(cfg):
    """jax.random is sharding-invariant: the same seed yields bitwise-equal
    params whether the mesh is 1xN (fsdp=8) or Nx1 (data=8)."""
    import jax

    leaves = {}
    for name, mc in [("1xN", MeshConfig(data=1, fsdp=8)),
                     ("Nx1", MeshConfig(data=8, fsdp=1))]:
        mesh = make_mesh(mc)
        init, _, _, _ = make_spmd_train_step(cfg, mesh, donate=False)
        leaves[name] = [np.asarray(x) for x in jax.tree.leaves(
            jax.device_get(init(jax.random.PRNGKey(7))["params"]))]
    for a, b in zip(leaves["1xN"], leaves["Nx1"]):
        assert a.tobytes() == b.tobytes()


def test_first_step_loss_invariant_across_mesh_layouts(cfg, tokens):
    """Same seed + same batch → same first-step loss on 1xN vs Nx1."""
    import jax

    losses = []
    for mc in [MeshConfig(data=1, fsdp=8), MeshConfig(data=8, fsdp=1)]:
        mesh = make_mesh(mc)
        init, step, ds, _ = make_spmd_train_step(cfg, mesh, donate=False)
        state = init(jax.random.PRNGKey(7))
        _, loss = step(state, jax.device_put(tokens, ds))
        losses.append(float(loss))
    np.testing.assert_allclose(losses[0], losses[1], rtol=2e-3)


def test_same_seed_init_invariant_across_tensor_layouts(cfg):
    """Tensor-mesh mirror of the 1xN/Nx1 invariance: the same seed
    yields bitwise-equal params on data×tensor vs fsdp×tensor."""
    import jax

    leaves = {}
    for name, mc in [("dxt", MeshConfig(data=4, tensor=2)),
                     ("fxt", MeshConfig(fsdp=4, tensor=2))]:
        mesh = make_mesh(mc)
        init, _, _, _ = make_spmd_train_step(cfg, mesh, donate=False)
        leaves[name] = [np.asarray(x) for x in jax.tree.leaves(
            jax.device_get(init(jax.random.PRNGKey(7))["params"]))]
    for a, b in zip(leaves["dxt"], leaves["fxt"]):
        assert a.tobytes() == b.tobytes()


def test_first_step_loss_invariant_across_tensor_layouts(cfg, tokens):
    """Same seed + same batch → same first-step loss on data×tensor vs
    fsdp×tensor (the two layouts run different collective programs:
    pure-DP replicas vs fsdp gathers, same math)."""
    import jax

    losses = []
    for mc in [MeshConfig(data=4, tensor=2), MeshConfig(fsdp=4, tensor=2)]:
        mesh = make_mesh(mc)
        init, step, ds, _ = make_spmd_train_step(cfg, mesh, donate=False)
        state = init(jax.random.PRNGKey(7))
        _, loss = step(state, jax.device_put(tokens, ds))
        losses.append(float(loss))
    np.testing.assert_allclose(losses[0], losses[1], rtol=2e-3)


# --------------------------------------------------------------------------- #
# shard_map step: GSPMD parity, donation
# --------------------------------------------------------------------------- #


def test_spmd_step_matches_gspmd(cfg, tokens):
    """The manual shard_map step and the GSPMD step are the same math:
    same seed + same batch → same two-step loss trajectory."""
    import jax

    m1 = make_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    ginit, gstep, gds, _ = make_train_step(cfg, m1)
    gstate = ginit(jax.random.PRNGKey(0))
    gtoks = jax.device_put(tokens, gds)
    gstate, g1 = gstep(gstate, gtoks)
    _, g2 = gstep(gstate, gtoks)

    for mc in [MeshConfig(data=8), MeshConfig(data=2, fsdp=4)]:
        mesh = make_mesh(mc)
        sinit, sstep, sds, _ = make_spmd_train_step(cfg, mesh, donate=False)
        sstate = sinit(jax.random.PRNGKey(0))
        stoks = jax.device_put(tokens, sds)
        sstate, s1 = sstep(sstate, stoks)
        _, s2 = sstep(sstate, stoks)
        np.testing.assert_allclose(
            [float(s1), float(s2)], [float(g1), float(g2)], rtol=3e-3)


def test_spmd_step_matches_gspmd_both_gather_schedules(cfg, tokens):
    """Streamed per-layer gathers are the SAME math as the upfront bulk
    gather: both schedules reproduce the GSPMD two-step trajectory on a
    data×fsdp mesh (rtol 3e-3, the PR-14 contract)."""
    import jax

    m1 = make_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    ginit, gstep, gds, _ = make_train_step(cfg, m1)
    gstate = ginit(jax.random.PRNGKey(0))
    gtoks = jax.device_put(tokens, gds)
    gstate, g1 = gstep(gstate, gtoks)
    _, g2 = gstep(gstate, gtoks)

    mesh = make_mesh(MeshConfig(data=2, fsdp=4))
    for gather in ("upfront", "streamed"):
        sinit, sstep, sds, _ = make_spmd_train_step(
            cfg, mesh, donate=False, gather=gather)
        sstate = sinit(jax.random.PRNGKey(0))
        stoks = jax.device_put(tokens, sds)
        sstate, s1 = sstep(sstate, stoks)
        _, s2 = sstep(sstate, stoks)
        np.testing.assert_allclose(
            [float(s1), float(s2)], [float(g1), float(g2)], rtol=3e-3,
            err_msg=f"gather={gather}")


def test_spmd_step_matches_gspmd_tensor_mesh(cfg, tokens):
    """Tensor-axis parity (the old ValueError pointer, removed): the
    manual Megatron program — vocab-parallel embed/xent, tp_psum_pair
    block collectives, sharded heads/mlp — reproduces the GSPMD
    trajectory on an fsdp×tensor mesh under BOTH gather schedules."""
    import jax

    m1 = make_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    ginit, gstep, gds, _ = make_train_step(cfg, m1)
    gstate = ginit(jax.random.PRNGKey(0))
    gtoks = jax.device_put(tokens, gds)
    gstate, g1 = gstep(gstate, gtoks)
    _, g2 = gstep(gstate, gtoks)

    mesh = make_mesh(MeshConfig(fsdp=4, tensor=2))
    for gather in ("upfront", "streamed"):
        sinit, sstep, sds, _ = make_spmd_train_step(
            cfg, mesh, donate=False, gather=gather)
        sstate = sinit(jax.random.PRNGKey(0))
        stoks = jax.device_put(tokens, sds)
        sstate, s1 = sstep(sstate, stoks)
        _, s2 = sstep(sstate, stoks)
        np.testing.assert_allclose(
            [float(s1), float(s2)], [float(g1), float(g2)], rtol=3e-3,
            err_msg=f"gather={gather}")


def test_spmd_step_learns_and_donates(cfg, tokens):
    """Donated state: the input buffers die with the step (in-place
    update), and the loss goes down over a few steps."""
    import jax

    mesh = make_mesh(MeshConfig(data=2, fsdp=4))
    init, step, ds, _ = make_spmd_train_step(cfg, mesh, donate=True)
    state = init(jax.random.PRNGKey(0))
    first = None
    for _ in range(5):
        prev = state
        state, loss = step(state, jax.device_put(tokens, ds))
        if first is None:
            first = float(loss)
            # the donated previous state is gone — no second copy
            assert jax.tree.leaves(prev)[0].is_deleted()
    assert float(loss) < first, f"no learning: {first} -> {float(loss)}"


def test_spmd_step_rejects_seq_mesh_and_bad_gather(cfg):
    mesh = make_mesh(MeshConfig(data=4, seq=2))
    with pytest.raises(ValueError, match="GSPMD"):
        make_spmd_train_step(cfg, mesh)
    mesh = make_mesh(MeshConfig(data=8))
    with pytest.raises(ValueError, match="streamed"):
        make_spmd_train_step(cfg, mesh, gather="eager")


def test_spmd_step_rejects_indivisible_tensor_axis(cfg):
    """A tensor axis that does not divide heads/mlp/vocab fails fast
    with a named-config error, not a shard-shape crash."""
    import dataclasses

    bad = dataclasses.replace(cfg, n_kv_heads=3, n_heads=3)
    mesh = make_mesh(MeshConfig(fsdp=4, tensor=2))
    with pytest.raises(ValueError, match="does not divide"):
        make_spmd_train_step(bad, mesh)


def test_param_residency_bytes_streamed_below_upfront(cfg):
    """The analytic residency model (the bench gate): streamed holds
    only a 2-layer gather window, so its peak is strictly below upfront
    whenever n_layers > 2; both exceed the bare shard bytes."""
    import dataclasses

    from ray_tpu.parallel.sharding import param_residency_bytes
    from ray_tpu.train.spmd import spmd_param_specs

    deep = dataclasses.replace(cfg, n_layers=6)
    mesh = make_mesh(MeshConfig(fsdp=4, tensor=2))
    sample, specs = spmd_param_specs(deep, mesh)
    up = param_residency_bytes(sample, specs, mesh, mode="upfront")
    st = param_residency_bytes(sample, specs, mesh, mode="streamed")
    assert st["shard_bytes"] == up["shard_bytes"]
    assert st["peak_bytes"] < up["peak_bytes"]
    assert up["peak_bytes"] > up["shard_bytes"]


# --------------------------------------------------------------------------- #
# what crosses the links: each gradient once, in float32
# --------------------------------------------------------------------------- #

_MESHES = {"data=2,fsdp=4": MeshConfig(data=2, fsdp=4),
           "fsdp=4,tensor=2": MeshConfig(fsdp=4, tensor=2)}
_CASES = [(m, g) for m in _MESHES for g in ("streamed", "upfront")]


def _collectives(jaxpr, times=1):
    """Every ``all_gather`` / ``reduce_scatter`` of ``jaxpr`` and of the
    jaxprs inside it: ``(primitive, operand dtype, times)``, ``times`` the
    product of the scan lengths around it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("all_gather", "reduce_scatter"):
            out.append((name, eqn.invars[0].aval.dtype.name, times))
        inner = times * (eqn.params["length"] if name == "scan" else 1)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    out.extend(_collectives(sub, inner))
    return out


def _census(cfg, mesh_name, gather):
    """``{primitive: calls a step}`` of the step's jaxpr, the operand
    dtypes seen, and how many leaves the step gathers: of one layer, and
    outside the layers (a spec axis other than ``tensor``, whose dims go
    through compute sharded)."""
    import jax

    from ray_tpu.train.spmd import spmd_param_specs

    mesh = make_mesh(_MESHES[mesh_name])
    init, step, _, _ = make_spmd_train_step(cfg, mesh, gather=gather)
    state = jax.eval_shape(init._fn, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(step._fn)(
        state, jax.ShapeDtypeStruct((8, 33), np.int32)).jaxpr
    calls, dtypes = {}, set()
    for prim, dtype, times in _collectives(jaxpr):
        calls[prim] = calls.get(prim, 0) + times
        dtypes.add(dtype)
    _, specs = spmd_param_specs(cfg, mesh)

    def gathered(spec):
        return any(ax not in (None, "tensor") for ax in spec)

    in_layer = sum(gathered(s) for s in specs["layers"].values())
    outside = sum(gathered(s) for k, s in specs.items() if k != "layers")
    return calls, dtypes, in_layer, outside


def _check_census(cfg, mesh_name, gather, n_in_layer):
    calls, dtypes, in_layer, outside = _census(cfg, mesh_name, gather)
    assert (in_layer, outside) == (n_in_layer, 2)  # embedding, lm_head
    # parameters are gathered as they are held and gradients summed in
    # float32: nothing narrower crosses the links in the traced step
    assert dtypes == {"float32"}
    L = cfg.n_layers
    if gather == "upfront":  # the stacked leaves, once each way
        assert calls == {"all_gather": in_layer + outside,
                         "reduce_scatter": in_layer + outside}
        return
    # a layer is gathered 2 L + 1 times a step: layer 0 in front of the
    # scan, the next layer in each of its L iterations (the last, layer 0
    # again, for a carry nobody reads), each layer again in the backward.
    # Its gradient is reduce-scattered ONCE: the carried gather is not
    # differentiated (before PR 29: 2 L + 1 reduce-scatters a leaf, all
    # but L of them of zeros)
    assert calls == {"all_gather": in_layer * (2 * L + 1) + outside,
                     "reduce_scatter": in_layer * L + outside}


@pytest.mark.parametrize("mesh_name,gather", _CASES)
def test_each_gradient_is_reduce_scattered_once_in_float32(cfg, mesh_name,
                                                           gather):
    """Read from the step's jaxpr: one reduce-scatter a gathered leaf a
    layer (seven matmul weights; the norms are replicated) and one each
    for ``embedding`` and ``lm_head``, every operand float32."""
    _check_census(cfg, mesh_name, gather, 7)


def test_routed_gradients_are_reduce_scattered_once_in_float32(cfg):
    """A routed layer gathers its router too: eight leaves a layer."""
    import dataclasses

    routed = dataclasses.replace(cfg, num_experts=4, experts_per_token=2)
    _check_census(routed, "data=2,fsdp=4", "streamed", 8)


def _keep_the_carried_gather_in_the_backward(monkeypatch):
    """Make ``prefetch_layer``'s ``stop_gradient`` the identity (it is the
    one call that is given a dict: a layer's leaves): the step is then
    bdeba28's, text for text (``test_olmoe.py`` holds the hashes)."""
    import jax

    real = jax.lax.stop_gradient
    monkeypatch.setattr(
        jax.lax, "stop_gradient",
        lambda x: x if isinstance(x, dict) else real(x))


# bdeba28 (the commit before the carried gather left the backward), CPU,
# LlamaConfig.debug(), key 0, this module's tokens, per mesh (both schedules
# gave the same): the first two losses, and sha256 over the bytes of every
# leaf of the state (parameters, both adamw moments, counters; in
# ``jax.tree.leaves`` order) after one step and after two.
_PARENT = {
    "data=2,fsdp=4": (
        (6.021799087524414, 5.881684303283691),
        "98659fed367fea3134f5d6bc3f1904c68418e73c77cabd0de6712d81ed768aae",
        "f18ea157c3d71d3b5d80acee38a24669770ee936814e79d4fb951fee115f1a60"),
    "fsdp=4,tensor=2": (
        (6.021988391876221, 5.881901741027832),
        "33d08c8256871f9e813e2e25c4f607f2e91a1c6e4902589112e1ed4d2301a700",
        "3343b7d3f5be538979f1a65dcdc8aa76d264b397ad6b2af9d9030c71b0bc6fa4"),
}


@pytest.mark.parametrize("mesh_name,gather", _CASES)
def test_step_is_the_parents_bit_for_bit(cfg, tokens, mesh_name, gather,
                                         monkeypatch):
    """Two steps against bdeba28: the ``stop_gradient`` takes sums of zeros
    out of the backward, so losses, parameters and both moments are the
    parent's to the bit. Held twice: against the values taken on bdeba28
    itself, and against the parent's program run here (the step with the
    carried gather left in the backward), array for array."""
    import hashlib

    import jax

    mesh = make_mesh(_MESHES[mesh_name])

    def two_steps():
        init, step, ds, _ = make_spmd_train_step(cfg, mesh, donate=False,
                                                 gather=gather)
        s0 = init(jax.random.PRNGKey(0))
        toks = jax.device_put(tokens, ds)
        s1, l1 = step(s0, toks)
        s2, l2 = step(s1, toks)
        return (float(l1), float(l2)), jax.device_get((s1, s2))

    def sha(state):
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(state):
            h.update(np.ascontiguousarray(leaf).tobytes())
        return h.hexdigest()

    losses, states = two_steps()
    _keep_the_carried_gather_in_the_backward(monkeypatch)
    want_losses, want_states = two_steps()
    assert losses == want_losses
    for got, want in zip(jax.tree.leaves(states),
                         jax.tree.leaves(want_states)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    # float32 in, float32 out: parameters and what the optimizer keeps
    assert {leaf.dtype.name for leaf in jax.tree.leaves(states)} \
        == {"float32", "int32"}
    assert (losses, sha(states[0]), sha(states[1])) == _PARENT[mesh_name]


# --------------------------------------------------------------------------- #
# sharded ingest
# --------------------------------------------------------------------------- #


def test_shard_device_put_matches_global_put(tokens):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.sharding import shard_device_put

    mesh = make_mesh(MeshConfig(data=4, fsdp=2))
    sh = NamedSharding(mesh, P(("data", "fsdp")))
    placed = shard_device_put(tokens, sh)
    assert np.array_equal(np.asarray(placed), tokens)
    assert placed.sharding.is_equivalent_to(sh, tokens.ndim)
    # every device holds exactly its 1/8 slice
    assert len({str(s.index) for s in placed.addressable_shards}) == 8


def test_to_jax_sharded_ingest(tokens):
    """DataIterator.to_jax with a multi-device sharding rides the
    per-shard placement path and yields value-identical batches."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.data.iterator import DataIterator

    import ray_tpu

    mesh = make_mesh(MeshConfig(data=8))
    sh = NamedSharding(mesh, P("data"))
    rows = np.arange(64, dtype=np.int64)
    ray_tpu.init(num_cpus=1, num_tpus=0)
    try:
        refs = [ray_tpu.put([{"x": int(v)} for v in rows[i:i + 32]])
                for i in (0, 32)]
        it = DataIterator(lambda: iter(list(refs)))
        batches = list(it.to_jax(batch_size=16, sharding=sh,
                                 drop_last=True, prefetch_batches=2))
    finally:
        ray_tpu.shutdown()
    got = np.concatenate([np.asarray(b["x"]) for b in batches])
    assert np.array_equal(got, rows)
    for b in batches:
        assert len({str(s.index) for s in b["x"].addressable_shards}) == 8


# --------------------------------------------------------------------------- #
# config knobs + trainer smoke (satellite: tier-1-safe devices=1 path)
# --------------------------------------------------------------------------- #


def test_train_knobs_are_config_fields():
    """RAY_TPU_TRAIN_MESH / _DONATE / _INGEST_PREFETCH / _GATHER resolve
    through the Config registry (graftlint config-hygiene contract: no
    direct env reads on the train path)."""
    from ray_tpu.core.config import Config

    cfg = Config()
    assert cfg.train_mesh == ""
    assert cfg.train_donate is True
    assert cfg.train_ingest_prefetch == 2
    assert cfg.train_gather == "streamed"
    import os

    os.environ["RAY_TPU_TRAIN_MESH"] = "data=2"
    os.environ["RAY_TPU_TRAIN_DONATE"] = "0"
    os.environ["RAY_TPU_TRAIN_INGEST_PREFETCH"] = "5"
    os.environ["RAY_TPU_TRAIN_GATHER"] = "upfront"
    try:
        cfg2 = Config()
        assert cfg2.train_mesh == "data=2"
        assert cfg2.train_donate is False
        assert cfg2.train_ingest_prefetch == 5
        assert cfg2.train_gather == "upfront"
    finally:
        for k in ("RAY_TPU_TRAIN_MESH", "RAY_TPU_TRAIN_DONATE",
                  "RAY_TPU_TRAIN_INGEST_PREFETCH", "RAY_TPU_TRAIN_GATHER"):
            os.environ.pop(k, None)


def test_synthetic_fallback_honors_prefetch_depth():
    """The synthetic-batch fallback keeps `train_ingest_prefetch`
    batches in flight (the to_jax discipline), not a hardcoded 1-deep
    buffer: with depth N, the host generator is N batches ahead of the
    consumer at every point."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.train.spmd import _prefetched_synthetic

    sh = NamedSharding(make_mesh(MeshConfig(data=1),
                                 devices=jax.devices()[:1]), P())
    pulled = [0]

    def host():
        while True:
            pulled[0] += 1
            yield np.full((2, 9), pulled[0], np.int32)

    for depth in (1, 3):
        pulled[0] = 0
        next_tokens = _prefetched_synthetic(host(), sh, depth)
        assert pulled[0] == depth  # primed `depth` ahead
        for i in range(1, 4):
            batch = np.asarray(next_tokens())
            assert batch[0, 0] == i  # FIFO order preserved
            assert pulled[0] == depth + i  # stays `depth` ahead


def test_spmd_train_loop_smoke():
    """devices=1-safe sharded-train smoke: the default loop runs the
    same config on whatever devices exist (here the virtual mesh) and
    reports decreasing loss — no cluster needed."""
    from ray_tpu.train.session import TrainContext, set_context
    from ray_tpu.train.spmd import spmd_train_loop

    ctx = TrainContext(1, 0, 0, 1, 0)
    set_context(ctx)
    try:
        # one repeated batch (distinct_batches=1) so the overfit
        # assertion is deterministic
        spmd_train_loop({"steps": 8, "batch_per_device": 1, "seq": 32,
                         "mesh": "data=1", "report_every": 1,
                         "lr": 0.05, "distinct_batches": 1})
        reports = ctx._drain()
    finally:
        set_context(None)
    assert len(reports) == 8
    losses = [r.metrics["loss"] for r in reports]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], f"no learning: {losses}"
    assert reports[-1].metrics["devices"] == 1
    assert reports[-1].metrics["tokens_per_sec_per_chip"] > 0


def _logged_loop(monkeypatch, report_every, recorder):
    """Five steps of the default loop with every issue of a step and every
    report logged in order: (events, reports, the recorder's records by
    span name as sorted (t0, dur, tags))."""
    from ray_tpu.train import session, spmd
    from ray_tpu.train.session import TrainContext, set_context
    from ray_tpu.util import flight_recorder as fr

    events = []
    real_make = spmd.make_spmd_train_step

    def make(*a, **kw):
        init, step, ds, rest = real_make(*a, **kw)

        def logged_step(state, toks):
            events.append("issue")
            return step(state, toks)

        return init, logged_step, ds, rest

    monkeypatch.setattr(spmd, "make_spmd_train_step", make)
    real_report = session.report
    monkeypatch.setattr(
        session, "report",
        lambda m, c=None: (events.append(m["step"]), real_report(m, c)))
    fr.reset_for_tests()
    fr.configure(enabled=recorder, min_span_us=0.0)
    ctx = TrainContext(1, 0, 0, 1, 0)
    set_context(ctx)
    try:
        spmd.spmd_train_loop({"steps": 5, "batch_per_device": 1, "seq": 32,
                              "mesh": "data=1", "distinct_batches": 1,
                              "report_every": report_every})
        reports = [r.metrics for r in ctx._drain()]
        payload = fr.snapshot_payload()
    finally:
        set_context(None)
        fr.configure(enabled=True)
    names = {int(sid): d["name"] for sid, d in payload["names"].items()}
    spans = {}
    for _, sid, kind, t0, dur, tags in payload["events"]:
        assert kind == 0  # a dense model: no router instant
        spans.setdefault(names[int(sid)], []).append((t0, dur, tuple(tags)))
    return events, reports, {n: sorted(v) for n, v in spans.items()}


# the loop's order of issues and reports, by ``report_every``
_LOOP_ORDER = {1: ["issue", 1, "issue", "issue", 2, "issue", 3, "issue", 4, 5],
               2: ["issue", "issue", "issue", 2, "issue", "issue", 4, 5]}


@pytest.mark.parametrize("report_every", [1, 2])
def test_loop_issues_the_next_step_before_it_waits(monkeypatch,
                                                   report_every):
    """The loop keeps one step ahead of its reports: step i + 1 is issued
    before step i's loss is waited for (the first step, which compiles,
    alone), every step is still reported, in order, and the recorder's
    ``spmd.compute`` spans follow one another without overlap."""
    from ray_tpu.train import spmd

    events, reports, by_name = _logged_loop(monkeypatch, report_every, True)
    assert events == _LOOP_ORDER[report_every]
    assert [r["step"] for r in reports] == [e for e in events if e != "issue"]
    assert "device_report" in reports[-1]  # the last report's evidence
    spans = [(t0, dur) for t0, dur, _ in by_name[spmd._sp_compute.name]]
    assert len(spans) == 4
    for (a, da), (b, _) in zip(spans, spans[1:]):
        assert a + da <= b


@pytest.mark.parametrize("report_every", [1, 2])
def test_loop_records_the_hosts_phases_of_every_step(monkeypatch,
                                                     report_every):
    """The host's part of a step as spans joined by ``step``: a dispatch and
    a wait for the device for every step after the first (whose call is the
    compile), a fetch and a report for every reported step; one thread's
    work, so pairwise disjoint; and the one-step-ahead order in the spans
    themselves: step i + 1's dispatch ends before step i's wait begins."""
    events, reports, by_name = _logged_loop(monkeypatch, report_every, True)
    reported = [e for e in events if e != "issue"]

    def steps_of(name):
        return [tags[0] for _, _, tags in by_name.get(name, [])]

    # every span of a step: the one tag, ``step`` (the loop's set-up spans
    # and the compile listener's carry none)
    once = {"spmd.build", "spmd.init_state", "jax.trace", "jax.lower",
            "jax.cache_load", "jax.backend_compile", "xla.compile"}
    assert all(len(tags) == 1 for name, spans in by_name.items()
               if name not in once for _, _, tags in spans)
    assert steps_of("spmd.compile") == [1]
    assert steps_of("spmd.compute") == [2, 3, 4, 5]
    assert steps_of("spmd.ingest_wait") == [1, 2, 3, 4, 5]
    assert steps_of("spmd.dispatch") == [2, 3, 4, 5]
    assert steps_of("spmd.ready_wait") == [2, 3, 4, 5]
    assert steps_of("spmd.fetch") == reported
    assert steps_of("spmd.report") == reported
    phases = sorted((t0, t0 + dur, name, tags[0])
                    for name in ("spmd.ingest_wait", "spmd.dispatch",
                                 "spmd.ready_wait", "spmd.fetch",
                                 "spmd.report")
                    for t0, dur, tags in by_name[name])
    for (_, end, *_a), (start, *_b) in zip(phases, phases[1:]):
        assert end <= start
    dispatched = {tags[0]: t0 + dur
                  for t0, dur, tags in by_name["spmd.dispatch"]}
    for t0, _, tags in by_name["spmd.ready_wait"]:
        if tags[0] + 1 in dispatched:
            assert dispatched[tags[0] + 1] <= t0
    # each step's wait for the device ends inside its own compute span
    computed = {tags[0]: (t0, t0 + dur)
                for t0, dur, tags in by_name["spmd.compute"]}
    for t0, dur, tags in by_name["spmd.ready_wait"]:
        lo, hi = computed[tags[0]]
        assert lo <= t0 + dur <= hi


def test_loop_records_its_set_up_once(monkeypatch):
    """``spmd.build`` (``make_spmd_train_step``) and ``spmd.init_state``
    (``init`` to the state on the device): one record a loop each, no tag,
    build before init, both over before the first step is dispatched, and
    the first step's compile after them."""
    _events, _reports, by_name = _logged_loop(monkeypatch, 1, True)
    (b0, bdur, btags), = by_name["spmd.build"]
    (i0, idur, itags), = by_name["spmd.init_state"]
    assert btags == () == itags
    assert b0 + bdur <= i0
    first_dispatch = min(t0 for t0, _, _ in by_name["spmd.dispatch"])
    (c0, _cdur, _), = by_name["spmd.compile"]
    assert i0 + idur <= c0 <= first_dispatch


def test_loop_with_the_recorder_off_records_nothing(monkeypatch):
    """Recorder off: no record (the set-up spans' sites stop at their flag
    test as every other), the same order of issues and reports."""
    events, reports, by_name = _logged_loop(monkeypatch, 1, False)
    assert by_name == {}
    assert events == _LOOP_ORDER[1]
    assert [r["step"] for r in reports] == [1, 2, 3, 4, 5]


def test_jax_trainer_default_loop_spmd():
    """JaxTrainer with NO train loop runs the sharded default; the
    train_overrides payload lands in the worker's Config."""
    import ray_tpu
    from ray_tpu.train import JaxBackend, JaxTrainer, RunConfig, ScalingConfig

    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        result = JaxTrainer(
            train_loop_config={"steps": 3, "batch_per_device": 1,
                               "seq": 32, "mesh": "data=1"},
            scaling_config=ScalingConfig(num_workers=1),
            backend=JaxBackend(train_overrides={"train_donate": False}),
            run_config=RunConfig(name="spmd_smoke"),
        ).fit()
        assert result.error is None, result.error
        assert np.isfinite(result.metrics["loss"])
        assert result.metrics["step"] == 3
    finally:
        ray_tpu.shutdown()
