"""SPMD sharded training: regex partition rules + a shard_map train step.

This is the manual-SPMD counterpart of the GSPMD path in
``models/llama.py:make_train_step``: instead of letting XLA infer every
collective from output shardings, the parallelism is written down —

- **Regex partition rules** (``match_partition_rules``) map '/'-joined
  param-tree paths to ``PartitionSpec``s (the EasyLM/fmengine idiom, see
  SNIPPETS.md [1]): one table names how every weight shards, checkable
  at a glance, and applies to checkpoints loaded from disk just as well
  as to freshly-initialized trees.
- **Shard/gather fns** (``make_shard_and_gather_fns``) are jit-compiled
  per-leaf placement programs: ``shard`` lays a host (or replicated)
  leaf out across the mesh, ``gather`` pulls a sharded leaf back to a
  fully-replicated array for checkpointing. Round-tripping a tree
  through shard→gather is byte-identical per leaf (tested).
- **The shard_map train step** (``make_spmd_train_step``) runs the
  per-device program explicitly. Two gather schedules for the
  fsdp-sharded scanned layers: ``"upfront"`` all-gathers the whole
  param tree before the first layer; ``"streamed"`` (default) keeps the
  layer stack sharded and gathers each layer INSIDE the ``lax.scan`` —
  layer *i+1*'s all-gather is issued before layer *i*'s matmuls so XLA
  overlaps the collective with compute (the ZeRO-3 prefetch analog),
  and the backward re-gathers per layer and ``psum_scatter``s the layer
  grad straight back to shards, so full-tree param residency never
  materializes. A live ``tensor`` axis is handled Megatron-style:
  heads/mlp/vocab dims stay sharded through compute with the exact-grad
  ``tp_psum_pair`` collectives at block boundaries plus vocab-parallel
  embedding/cross-entropy, numerically matched against the GSPMD step.
  Cross-replica gradient reduction rides the ``collective`` package's
  in-program psum/pmean; fsdp-sharded leaves hold scatter shards
  (ZeRO-3: optimizer state stays sharded); replicated leaves psum. The
  jit step donates the carried state, so XLA aliases every
  param/optimizer buffer to its output and updates in place instead of
  writing a second copy of the training state per step.
- **Sharded ingest** (``data/iterator.py to_jax`` +
  ``parallel/sharding.py shard_device_put``) slices each host batch
  into exactly the shards the data sharding prescribes and device_puts
  them per-device, double-buffered, so host→device transfer of batch
  N+1 overlaps compute on batch N.

The same config runs devices=1 and devices=N: the mesh comes from the
``RAY_TPU_TRAIN_MESH`` Config knob (e.g. ``"data=4,fsdp=2"``) or
defaults to pure data-parallel over all local devices; with one device
every collective folds to the identity.

Supported mesh axes here: the batch axes (``slice``/``data``) plus
``fsdp`` (param + optimizer-state sharding) plus ``tensor``
(head/mlp/vocab sharding through compute). Sequence/pipeline
parallelism stay on the GSPMD/pipeline paths (``make_train_step`` /
``make_pipeline_train_step``), which this step matches numerically
(same-seed loss parity is tested: ``jax.random`` is sharding-invariant,
so the same seed gives the same params on every mesh).
"""

from __future__ import annotations

import difflib
import itertools
import logging
import re
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.util import flight_recorder as _fr
from ray_tpu.util.metrics import Gauge
from ray_tpu.util.xla_observatory import (
    ObservedFunction,
    analyses,
    observe_compiled,
)

logger = logging.getLogger(__name__)

# Every spmd.* span and every moe.* instant carries ``step``, the loop's step
# number as the report spells it (i + 1): the key that joins a step's spans
# and its router scalars, as ``corr`` joins a request's.
_sp_ingest = _fr.register_span("spmd.ingest_wait", tag_keys=("step",))
_sp_compute = _fr.register_span("spmd.compute", tag_keys=("step",))
# the first step pays trace + XLA compile; recording it under its own
# name keeps the badput ledger's compile column honest instead of
# folding a multi-second outlier into spmd.compute
_sp_compile = _fr.register_span("spmd.compile", tag_keys=("step",))
# The host's phases of a step, each where the work happens: one record a
# step, so floor_exempt (a median is over every step, not the slow ones).
# dispatch: the call into the jitted step (fingerprint, argument check, the
# executable's call; it blocks where the runtime's queue is full), every
# step but the first, whose call lowers and compiles under spmd.compile.
# ready_wait: the host asleep on the step's loss; near zero means the host
# came late and sets the pace. fetch: loss and router scalars to the host;
# report: gauges and ``session.report``, what a user's report costs the
# loop's thread (both on reported steps only).
_sp_dispatch = _fr.register_span("spmd.dispatch", tag_keys=("step",),
                                 floor_exempt=True)
_sp_ready = _fr.register_span("spmd.ready_wait", tag_keys=("step",),
                              floor_exempt=True)
_sp_fetch = _fr.register_span("spmd.fetch", tag_keys=("step",),
                              floor_exempt=True)
_sp_report = _fr.register_span("spmd.report", tag_keys=("step",),
                               floor_exempt=True)
# The loop's set-up, one record a loop each (``timeline --attribute``'s set-up
# block): build is ``make_spmd_train_step`` (shardings, the jitted init and
# step; nothing compiles yet: the step's compiles, ``_KeepingStep``'s second
# among them, lie under spmd.compile), init_state is ``init(PRNGKey)`` to the
# state ready on the device (its program's trace, lowering and load or
# compile nest inside as jax.* spans).
_sp_build = _fr.register_span("spmd.build")
_sp_init_state = _fr.register_span("spmd.init_state")
# a routed model's router, one instant a report (``timeline --attribute``
# prints them): the two router losses, the heaviest expert's load over the
# mean, and the assignments no expert computed (0: the routing is dropless)
_ROUTER_GAUGES = {
    "lb_loss": _fr.register_span("moe.lb_loss", tag_keys=("value", "step")),
    "z_loss": _fr.register_span("moe.z_loss", tag_keys=("value", "step")),
    "max_load_ratio": _fr.register_span("moe.max_load_ratio",
                                        tag_keys=("value", "step")),
    "dropped": _fr.register_span("moe.dropped", tag_keys=("value", "step")),
    # where the layers hold a range of the router's experts: the share of
    # all assignments that fell on the held ones
    "held_share": _fr.register_span("moe.held_share",
                                    tag_keys=("value", "step")),
    # and the chunks of the even share their live places fill, a routed
    # layer's mean: 1.0 when every layer's step made its first tier of rows
    # and no other (ops/moe.py held_tiers)
    "held_chunks": _fr.register_span("moe.held_chunks",
                                     tag_keys=("value", "step")),
    # a model with a prediction module (LlamaConfig.mtp_layers): its
    # cross-entropy and the main one apart, as they stand in the loss before
    # the module's weight (the report's ``mtp_loss`` / ``main_loss``)
    "mtp_loss": _fr.register_span("mtp.loss", tag_keys=("value", "step")),
    "main_loss": _fr.register_span("mtp.main_loss",
                                   tag_keys=("value", "step")),
}
# the step scalars a report spells as they are; the router's: ``moe_<name>``
_REPORTED_AS_IS = ("mtp_loss", "main_loss")

# Throughput/step-time gauges feeding the head's metrics-history rings
# (session.report only buffers to the driver's result log) — the series
# the regression detector and TTRT tracker watch. Tagged by loop so the
# MPMD pipeline can publish the same names.
_g_tokens_per_sec = Gauge("ray_tpu_train_tokens_per_sec",
                          "Recent training throughput (tokens/s)",
                          tag_keys=("loop",))
_g_step_seconds = Gauge("ray_tpu_train_step_seconds",
                        "Recent mean train step wall time (s)",
                        tag_keys=("loop",))
# the stack the step was built for, set once when it is built: layers by
# kind (block; of a patterned stack mamba / moe / attn, latent / latent_dense
# for the "L" / "G" blocks, mtp for a prediction module's blocks), and the
# experts a routed layer holds of the router's width
_g_stack = Gauge("ray_tpu_train_stack",
                 "The model a train step was built for: layers by kind, "
                 "experts held, the router's width", tag_keys=("part",))

# what the step that runs keeps of its forward pass (models/llama.py
# KEEP_GROUPS), set when that is chosen: bytes a device by group, 0 for a
# group that is recomputed, and under group="headroom" the bytes the choice
# had to spend
_g_kept = Gauge("ray_tpu_train_kept_bytes",
                "Bytes a device of forward products the train step keeps "
                "for its backward pass instead of recomputing them, by "
                "group; group=headroom: what the device had free for them",
                tag_keys=("group",))

__all__ = [
    "match_partition_rules",
    "make_shard_and_gather_fns",
    "llama_partition_rules",
    "spmd_param_specs",
    "make_spmd_train_step",
    "spmd_train_loop",
    "tree_paths",
]


# --------------------------------------------------------------------------- #
# Regex partition rules (SNIPPETS.md [1]: match_partition_rules)
# --------------------------------------------------------------------------- #


def tree_paths(tree, sep: str = "/"):
    """Mirror ``tree`` with '/'-joined key-path strings at the leaves."""
    import jax
    from jax.tree_util import tree_map_with_path

    def name(path):
        parts = []
        for k in path:
            parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
        return sep.join(parts)

    return tree_map_with_path(lambda p, _: name(p), tree)


def match_partition_rules(rules, params, sep: str = "/"):
    """Pytree of PartitionSpec from ``rules``: ordered (regex, spec)
    pairs matched with ``re.search`` against each leaf's '/'-joined
    path. Scalars and size-1 leaves never partition. A leaf no rule
    matches is an error — silent replication of a large weight is the
    classic way to quietly lose FSDP memory savings."""
    import jax
    from jax.sharding import PartitionSpec as P

    def spec_for(name, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                return spec
        patterns = [r for r, _ in rules]
        near = difflib.get_close_matches(name, patterns, n=3, cutoff=0.0)
        raise ValueError(
            f"no partition rule matches param path {name!r} "
            f"(shape {shape}); nearest rule patterns: "
            + ", ".join(repr(p) for p in near)
            + " — add a (regex, PartitionSpec) entry for it")

    names = tree_paths(params, sep)
    return jax.tree.map(spec_for, names, params)


def llama_partition_rules(routed: bool = False, pattern: bool = False):
    """Partition rules for the llama param tree (models/llama.py);
    ``routed``: for a config with experts, whose w_gate / w_up / w_down
    carry an expert dimension after the scan's; ``pattern``: for a
    patterned stack, whose weights are stacked per kind
    (``layers/<kind>/<leaf>``) and which ``make_spmd_train_step`` runs on
    batch axes only: one rule, replicated.

    Mirrors ``parallel/sharding.DEFAULT_RULES``'s logical-axis mapping
    (embed→fsdp, heads/kv_heads/mlp/vocab→tensor) but keyed by name, so
    the table reads like the model: every projection shards its embed
    dim over ``fsdp`` and its heads/mlp dim over ``tensor``; the scan
    ('layers') dim never shards, nor does the expert dim (every expert on
    every device; a live ``expert`` axis is refused)."""
    from jax.sharding import PartitionSpec as P

    experts = (
        # router: (L, embed, E); expert weights: (L, E, embed, mlp) and
        # (L, E, mlp, embed), first match wins
        (r"layers/router$", P(None, "fsdp", None)),
        (r"layers/w_(gate|up)$", P(None, None, "fsdp", "tensor")),
        (r"layers/w_down$", P(None, None, "tensor", "fsdp")),
    ) if routed else ()
    if pattern:  # batch axes only: every leaf whole on every device
        return ((r".", P()),)
    return experts + (
        # embedding: (vocab, embed)
        (r"(^|/)embedding$", P("tensor", "fsdp")),
        # q/k/v and gate/up: (L, embed, heads*hd | mlp)
        (r"layers/w(q|k|v)$", P(None, "fsdp", "tensor")),
        (r"layers/w_(gate|up)$", P(None, "fsdp", "tensor")),
        # output projections: (L, heads*hd | mlp, embed)
        (r"layers/(wo|w_down)$", P(None, "tensor", "fsdp")),
        # norm scales (QK-norm's too): replicated
        (r"norm$", P()),
        # lm_head: (embed, vocab)
        (r"(^|/)lm_head$", P("fsdp", "tensor")),
    )


def _restrict_spec(spec, mesh):
    """Drop mesh axes the spec names that this mesh does not have (or
    has at size 1 — ``make_mesh`` omits size-1 axes from the name set),
    so one rule table serves every layout."""
    from jax.sharding import PartitionSpec as P

    def live(axes):
        if axes is None:
            return None
        if isinstance(axes, (tuple, list)):
            keep = tuple(a for a in axes if a in mesh.axis_names)
            return keep if keep else None
        return axes if axes in mesh.axis_names else None

    return P(*(live(a) for a in spec))


def make_shard_and_gather_fns(partition_specs, mesh, dtype_specs=None):
    """Per-leaf jit-compiled placement fns from a PartitionSpec pytree.

    ``shard_fns[leaf](host_array)`` lays the leaf out across ``mesh``
    per its spec (optionally casting float leaves to ``dtype_specs``);
    ``gather_fns[leaf](sharded)`` returns the fully-replicated array.
    Compilation is per-leaf and cached by jax, so checkpoint load/save
    of a whole tree costs one compiled program per distinct
    (shape, dtype, spec)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def to_dtype(x):
        if dtype_specs is not None and jax.numpy.issubdtype(
                getattr(x, "dtype", np.int32), jax.numpy.floating):
            return x.astype(dtype_specs)
        return x

    from ray_tpu.parallel.sharding import observed_placement_jit

    # one jitted callable per DISTINCT sharding (jax's jit cache keys on
    # the callable identity first, so a fresh wrapper per leaf would
    # compile per leaf even when dozens share (shape, dtype, spec))
    jitted: Dict[Any, Any] = {}

    def placement_fn(sharding):
        if sharding not in jitted:
            jitted[sharding] = observed_placement_jit(
                to_dtype, sharding, "spmd.shard_put")
        return jitted[sharding]

    def make_shard(spec):
        fn = placement_fn(NamedSharding(mesh, _restrict_spec(spec, mesh)))

        def shard(x):
            return fn(x)

        return shard

    gather_jit = observed_placement_jit(
        lambda x: x, NamedSharding(mesh, P()), "spmd.gather_replicate")

    def make_gather(spec):
        def gather(x):
            return gather_jit(x)

        return gather

    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    shard_fns = jax.tree.map(make_shard, partition_specs, is_leaf=is_spec)
    gather_fns = jax.tree.map(make_gather, partition_specs, is_leaf=is_spec)
    return shard_fns, gather_fns


# --------------------------------------------------------------------------- #
# What the step keeps of its forward pass
# --------------------------------------------------------------------------- #

# Of the device's limit, what a program's account must stay under for the
# step to keep anything beside it. The compiler's account is of ONE program
# (the process holds batches in flight too, and the allocator cannot hand
# out every byte in one piece), and the TPU compiler rematerialises ON ITS
# OWN once a program passes some 92-94% of the limit (PERF.md, PR 32): in
# that band a product kept here is bought back by a recomputation added
# there, so a step already in it is left as it is.
KEEP_MARGIN = 0.08


def kept_group_bytes(cfg, batch: int, seq: int, *,
                     tensor: int = 1) -> Dict[str, int]:
    """Bytes a device each of ``models.llama.KEEP_GROUPS`` holds once kept,
    from shapes: ``batch`` x ``seq`` rows a device, each group's widths in
    cfg.dtype, times the layers that have it. Only the groups a step of
    this shape HAS: ``attn`` / ``mlp`` where the layers run under
    ``jax.checkpoint`` (``cfg.remat``), ``mlp`` of dense blocks only (the
    routed half has no names), ``head`` where the loss is chunked (its
    whole chunks)."""
    import jax.numpy as jnp

    item = jnp.dtype(cfg.dtype).itemsize
    rows = batch * seq
    groups: Dict[str, int] = {}
    if cfg.remat:
        kinds = cfg.kinds or "b" * cfg.n_layers
        q = cfg.n_heads * cfg.head_dim // tensor
        kv = cfg.n_kv_heads * cfg.head_dim // tensor
        # q, k, v, the kernel's output, the wo product; and the kernel's
        # float32 log-sum-exp, one a query head
        groups["attn"] = (kinds.count("b") + kinds.count("*")) * rows * (
            (2 * q + 2 * kv + cfg.dim) * item + 4 * cfg.n_heads // tensor)
        # a latent block's (a prediction module's too): keys and values are
        # made a head, at the value's width, which is the score's
        wide = cfg.n_heads * cfg.v_head_dim
        groups["attn"] += (
            kinds.count("L") + kinds.count("G") + cfg.mtp_layers) * rows * (
                (4 * wide + cfg.dim) * item + 4 * cfg.n_heads)
        if not cfg.num_experts:  # gate and up
            groups["mlp"] = (kinds.count("b") * rows
                             * 2 * (cfg.mlp_dim // tensor) * item)
        if "G" in kinds:
            groups["mlp"] = kinds.count("G") * rows * 2 * cfg.dense_mlp_dim \
                * item
    if cfg.loss_chunk and seq > cfg.loss_chunk:
        # a prediction module runs the head a second time
        groups["head"] = ((1 + cfg.mtp_layers) * batch
                          * (seq - seq % cfg.loss_chunk)
                          * (cfg.vocab_size // tensor) * item)
    return {g: b for g, b in groups.items() if b}


def loss_phase_bytes(cfg, batch: int, seq: int, *, tensor: int = 1) -> int:
    """What a device holds beside the step's arguments while the loss runs,
    from shapes: what the forward pass saved whatever is kept (a layer's
    input each, the final stream and its norm), a chunk's float32 logits
    three times over (with their softmax and its gradient) and the head's
    float32 gradient. The kept ``head`` products live and die HERE, before
    the first layer's backward: the compiler's account, whose peak lies in
    the layers' backward, says nothing of the room they have."""
    import jax.numpy as jnp

    vocab = cfg.vocab_size // tensor
    stream = batch * seq * cfg.dim * jnp.dtype(cfg.dtype).itemsize
    chunk = batch * min(cfg.loss_chunk or seq, seq) * vocab * 4
    # a prediction module: its merged input, a block's input each and its
    # own final stream, saved until the second loss's backward has run
    mtp = cfg.mtp_layers + 2 if cfg.mtp_layers else 0
    return ((cfg.n_layers + 2 + mtp) * stream + 3 * chunk
            + cfg.dim * vocab * 4)


def program_bytes(memory: Dict[str, int]) -> int:
    """What a program needs of a device while it runs, by the compiler's
    own account (``xla_observatory.analyses``'s ``memory``): the peak of
    its live buffers where the compiler gives one; else arguments, the
    outputs that are not donated arguments written in place, temporaries
    and code, which counts every temporary as if all were live at once."""
    return memory.get("peak") or (
        memory["argument"] + memory["output"] - memory.get("alias", 0)
        + memory["temp"] + memory.get("code", 0))


def choose_kept(group_bytes: Dict[str, int], layers_room: int,
                loss_room: int) -> Tuple[str, ...]:
    """The groups to keep: the subset of ``group_bytes`` with the most
    bytes (every kept product contracts the model's width away, so the
    FLOPs a group saves go with its bytes) whose ``attn`` and ``mlp`` fit
    ``layers_room``, the room beside the step that keeps nothing, whose
    peak lies in the layers' backward where they are live; and which fits
    ``loss_room`` whole, ``head`` and all, the room while the loss runs. Of
    two subsets with the same bytes the one with ``attn``, which also saves
    the flash forward kernel. ``()``: nothing fits."""
    from ray_tpu.models.llama import KEEP_GROUPS

    have = [g for g in KEEP_GROUPS if group_bytes.get(g)]
    best, best_rank = (), (0, False)
    for r in range(1, len(have) + 1):
        for subset in itertools.combinations(have, r):
            size = sum(group_bytes[g] for g in subset)
            in_layers = sum(group_bytes[g] for g in subset if g != "head")
            rank = (size, "attn" in subset)
            if (in_layers <= layers_room and size <= loss_room
                    and rank > best_rank):
                best, best_rank = subset, rank
    return best


def keeps_what_it_should(base: Dict[str, Any], kept: Dict[str, Any],
                         limit: int) -> str:
    """Why a step that keeps products must NOT run in place of the one that
    keeps nothing ('': it may), from the two programs' ``analyses``. Its
    own account has to stay under the limit less the margin (or under the
    first program's); and its FLOPs under the first program's: every kept
    byte is part of a product the backward no longer makes, so a count
    that did not fall says the compiler, under pressure, recomputes more
    than was kept."""
    if "memory" not in kept:
        return "it has no memory account"
    need, had = program_bytes(kept["memory"]), program_bytes(base["memory"])
    if need > max(had, int((1 - KEEP_MARGIN) * limit)):
        return (f"its account, {need} bytes, is over the first program's "
                f"{had} and leaves no margin under the limit {limit}")
    if "flops" in base and not kept.get("flops", 0) < base["flops"]:
        return (f"it makes {kept.get('flops')} FLOPs, the step that keeps "
                f"nothing {base['flops']}: the compiler recomputes more "
                f"than was kept")
    return ""


def _device_limit(device) -> Optional[int]:
    """The bytes the backend lets a process hold on ``device``, or None
    where it does not say (the CPU)."""
    return (device.memory_stats() or {}).get("bytes_limit")


# the step a build and a batch shape came to on a device with a limit: what
# it keeps and the executable, so that the same step built again in this
# process (a second loop) neither chooses nor lowers nor loads anything:
# {(the build's key, the call's fingerprint): (groups, executable)}
_BUILT: Dict[tuple, Tuple[Tuple[str, ...], Any]] = {}


class _KeepingStep(ObservedFunction):
    """The train step a caller holds. ``_fn`` is the jitted step that keeps
    nothing: every layer and every chunk of the loss recomputed whole in
    the backward. When a batch shape is first seen that program is lowered
    and compiled ahead of time, whatever ``xla_observatory_enabled`` says,
    and its memory account decides what the step that RUNS keeps
    (:func:`choose_kept`). Nothing fits, the account is within
    ``KEEP_MARGIN`` of the limit, or the backend reports no limit: that
    executable is the step, one compile as ever. Else the step that keeps
    the chosen groups is compiled too and runs, unless the compiler refuses
    it or :func:`keeps_what_it_should` does: then the first executable
    runs. At most two compiles a shape."""

    def __init__(self, step_keeping: Callable[[Tuple[str, ...]], Any],
                 sizes: Callable[..., Tuple[Dict[str, int], int]], device,
                 name: str, build_key=None):
        super().__init__(step_keeping(()), name)
        self._step_keeping = step_keeping
        self._sizes = sizes
        self._device = device
        self._build_key = build_key

    def _ahead_of_time(self) -> bool:
        return True

    def _executable(self, fp, args, kwargs):
        groups, loss_bytes = self._sizes(*args, **kwargs)
        key = (self._build_key, fp) if self._build_key is not None else None
        if key in _BUILT:
            keep, compiled = _BUILT[key]
            self._report(groups, keep, None)
            return compiled
        base = self._compile(self._fn, args, kwargs)
        limit = _device_limit(self._device)
        found = analyses(base.compiled)
        keep, why, room = (), "", 0
        if limit and "memory" in found:
            under = int((1 - KEEP_MARGIN) * limit)
            room = under - program_bytes(found["memory"])
            if room > 0:
                keep = choose_kept(
                    groups, room,
                    under - found["memory"]["argument"] - loss_bytes)
        chosen = base
        if keep:
            try:
                kept = self._compile(self._step_keeping(keep), args, kwargs)
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                kept, why = None, f"the compiler refused it: {e}"[:300]
            if kept is not None:
                why = keeps_what_it_should(found, analyses(kept.compiled),
                                           limit)
                self._record(fp, kept if why else base)  # the loser first
                if not why:
                    chosen = kept
            if why:
                keep = ()
        self._record(fp, chosen)
        if key is not None and limit:
            _BUILT[key] = (keep, chosen.compiled)
        self._report(groups, keep, room)
        # a second program that fell back cost a compile for nothing (and
        # one the compiler refuses is not cached: every start pays it)
        logger.log(
            logging.WARNING if why else logging.INFO,
            "%s keeps %s of %s: device limit %s, margin %.0f%%, the step "
            "that keeps nothing %s%s", self.program_name,
            list(keep) or "nothing", groups, limit, 100 * KEEP_MARGIN,
            found.get("memory"), f"; fell back: {why}" if why else "")
        return chosen.compiled

    @staticmethod
    def _report(groups, keep, room) -> None:
        for g, nbytes in groups.items():
            _g_kept.set(float(nbytes if g in keep else 0), tags={"group": g})
        if room is not None:
            _g_kept.set(float(max(room, 0)), tags={"group": "headroom"})


# --------------------------------------------------------------------------- #
# shard_map train step (manual DP + fsdp ZeRO-3 + tensor)
# --------------------------------------------------------------------------- #


def _is_spec(x):
    import jax

    return isinstance(x, jax.sharding.PartitionSpec)


def spmd_param_specs(cfg, mesh, rules=None):
    """(abstract param tree, PartitionSpec tree) for ``cfg`` on ``mesh``
    — the rule table matched and restricted to the mesh's live axes.
    What the train step shards its state by."""
    import jax

    from ray_tpu.models.llama import init_params

    sample = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = jax.tree.map(
        lambda s: _restrict_spec(s, mesh),
        match_partition_rules(
            rules or llama_partition_rules(routed=bool(cfg.num_experts),
                                           pattern=bool(cfg.layer_pattern)),
            sample),
        is_leaf=_is_spec)
    return sample, specs


def make_spmd_train_step(cfg, mesh, optimizer=None, rules=None,
                         donate: bool = True, gather: str = "streamed",
                         keep: Optional[Sequence[str]] = None):
    """Build (init, step, data_sharding, state_shardings) with the SPMD
    program written out in shard_map, matching ``make_train_step``'s
    contract and numerics (rtol 3e-3 vs the GSPMD step, tested).

    ``gather`` picks the fsdp schedule for the scanned layer stack:

    - ``"upfront"``: all-gather every fsdp leaf before the first layer
      (full-tree residency, one bulk collective).
    - ``"streamed"`` (default): non-scanned leaves (embed/head) gather
      up front; each LAYER's shards gather inside the ``lax.scan``,
      with layer *i+1*'s all-gather issued before layer *i*'s matmuls
      (prefetch-in-carry) so XLA overlaps the collective with compute —
      the ZeRO-3 prefetch analog. The backward is a ``custom_vjp``
      whose residuals are the input activation + the SHARDS: it
      re-gathers the layer and recomputes its vjp (inherent per-layer
      remat), then ``psum_scatter``s the layer grad straight back to
      shards. At most two fsdp-full layers (current + prefetched) are
      ever live, so peak param residency stays O(tree/L), not O(tree).
      The carried gather is under ``stop_gradient``: its cotangent is
      zero by construction, and differentiated it reduce-scattered a
      second whole layer (of zeros) a layer in the backward.
      Folds to ``"upfront"`` when the mesh has no live fsdp axis.

    A live ``tensor`` axis shards heads/mlp/vocab THROUGH compute
    (Megatron manual TP: ``decoder_block`` + ``tp_psum_pair`` — exact
    grads under value_and_grad inside shard_map), with vocab-parallel
    embedding and cross-entropy; tensor-sharded dims are never
    gathered. ``seq``/``pipe``/``expert`` still route to the GSPMD /
    pipeline steps.

    A config with experts (``cfg.num_experts``) runs its routed MLP half
    in the same layer (``decoder_block`` -> ``ops/moe.routed_mlp``), every
    expert on every device; the router's losses leave the layer stack as
    the scan's stacked outputs under both gather schedules and are added
    to the loss (``add_router_losses``). Its step returns a third value,
    the router's scalars of the step (``lb_loss``, ``z_loss``,
    ``max_load_ratio``, ``dropped``); a dense config's step and program
    are what they were.

    A patterned stack (``cfg.layer_pattern``: Mamba-2, routed and attention
    layers, each one half of the block, weights stacked per kind) runs on
    batch axes only, its layers walked in the pattern's order
    (``models.llama.pattern_stack``); a live ``fsdp`` or ``tensor`` axis is
    refused. Its routed layers may hold a range of the router's experts:
    the step's router scalars then carry ``held_share`` and ``held_chunks``.
    Latent-attention blocks (``"L"`` / ``"G"``, ``latent_block``) are attended
    with the flash kernel over per-head keys and values of ONE width (the
    score's, which is the value's), forward, dQ and dK/dV, and NOT with
    prefill's kernel, which has no backward of its own. With a prediction
    module (``cfg.mtp_layers``) the loss is the main cross-entropy plus
    ``mtp_loss_weight`` times the module's, built where ``loss_parts``
    builds it (``models.llama.add_mtp_loss``: the step lends its embedding
    table, its head and its kernel), and the step's scalars carry
    ``main_loss`` and ``mtp_loss`` apart.

    Where the layers run under ``jax.checkpoint`` (``cfg.remat``) and where
    the loss is chunked, the step KEEPS the forward products that fit the
    device beside it and recomputes the rest: ``models.llama.KEEP_GROUPS``,
    chosen when a batch shape is first seen from the compiler's memory
    account of the step that keeps nothing (:class:`_KeepingStep`). No
    setting: on a backend that reports no memory limit (the CPU) nothing is
    kept. Not the streamed scan with a live ``fsdp`` axis: it recomputes a
    layer by construction and is left exactly as it was, its loss too.
    ``keep`` is for tests: a subset to keep whatever the room, with no
    choice made.

    A caller-supplied ``optimizer`` runs INSIDE shard_map on the
    fsdp/tensor shards, so per-leaf elementwise transforms (adam/adamw
    moments, per-leaf clipping, weight decay) are exact, but transforms
    that mix leaves or need a GLOBAL statistic — ``clip_by_global_norm``,
    lamb's trust ratio — would compute it over each device's shard
    only and silently diverge from the GSPMD step. Use
    ``make_train_step`` for those, or reduce the statistic explicitly
    (psum over the fsdp/tensor axes) in a custom transform.

    ``donate=True`` donates the carried state (params + optimizer
    moments + step), so XLA aliases every param/moment input buffer to
    its output and updates in place — without it each step writes a
    second full copy of the training state before freeing the first.
    The token batch is deliberately NOT donated: an int32 input has no
    same-shape/dtype output to alias onto, so XLA would ignore the
    donation (with a warning) — the per-step ingest copy is killed on
    the data path instead (fresh per-shard ``device_put`` buffers,
    double-buffered — see ``DataIterator.to_jax``). Callers that
    re-feed one token buffer every step (benches) work unchanged.
    Toggle via the ``RAY_TPU_TRAIN_DONATE`` Config knob when comparing;
    pick the gather schedule via ``RAY_TPU_TRAIN_GATHER``
    (``spmd_train_loop`` threads both through)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.collective import pmean_tree
    from ray_tpu.models.llama import (
        _plain_chunk_nll,
        add_mtp_loss,
        add_router_losses,
        chunked_nll_mean,
        decoder_block,
        flash_causal,
        held_to,
        init_params,
        keep_policy,
        pattern_stack,
        positions_of,
        tp_psum_pair,
        vp_chunk_nll,
        vp_embed,
    )
    from ray_tpu.ops.layers import rms_norm
    from ray_tpu.parallel.sharding import opt_state_shardings
    from ray_tpu.util.jax_compat import shard_map

    for ax in ("seq", "pipe", "expert"):
        if ax in mesh.axis_names and mesh.shape[ax] > 1:
            raise ValueError(
                f"make_spmd_train_step shards over batch axes + fsdp + "
                f"tensor; mesh has live {ax!r} axis — use make_train_step "
                f"(GSPMD) or make_pipeline_train_step for that layout")
    if gather not in ("streamed", "upfront"):
        raise ValueError(
            f"gather must be 'streamed' or 'upfront', got {gather!r}")
    held_to(cfg, "make_spmd_train_step")

    tensor = ("tensor" if "tensor" in mesh.axis_names
              and mesh.shape["tensor"] > 1 else None)
    if cfg.layer_pattern and ("fsdp" in mesh.axis_names or tensor is not None):
        raise ValueError(
            "a patterned stack (layer_pattern) runs on batch axes only: the "
            "streamed fsdp gather scans ONE stacked tree of equal layers "
            "and has no per-kind form, and the Mamba-2 mixer, the held "
            "experts and the shared expert have no tensor-parallel form "
            "(tp_psum_pair placement, a split of heads and groups) yet; "
            f"mesh {dict(mesh.shape)} has a live fsdp or tensor axis")
    if tensor is not None:
        t = mesh.shape["tensor"]
        for what, n in (("n_heads", cfg.n_heads),
                        ("n_kv_heads", cfg.n_kv_heads),
                        ("mlp_dim", cfg.mlp_dim),
                        ("vocab_size", cfg.vocab_size)):
            if n % t:
                raise ValueError(
                    f"tensor axis size {t} does not divide cfg.{what}={n}")
        if cfg.num_experts or cfg.qk_norm:
            raise ValueError(
                "a live tensor axis splits the q / k vectors that QK-norm "
                "normalises whole, and the routed MLP half has no "
                "tensor-parallel form yet: use batch axes and fsdp")

    user_optimizer = optimizer
    optimizer = optimizer or optax.adamw(3e-4, b1=0.9, b2=0.95,
                                         weight_decay=0.1)

    from ray_tpu.parallel.mesh import batch_sharding, data_axes

    batch_axes = data_axes(mesh)  # the canonical ("slice","data","fsdp")
    fsdp = "fsdp" if "fsdp" in mesh.axis_names else None
    kinds = cfg.kinds or "b" * cfg.n_layers
    for part, n in (("block_layers", kinds.count("b")),
                    ("mamba_layers", kinds.count("M")),
                    ("moe_layers", kinds.count("E")),
                    ("attn_layers", kinds.count("*")),
                    ("latent_layers", kinds.count("L")),
                    ("latent_dense_layers", kinds.count("G")),
                    ("mtp_layers", cfg.mtp_layers),
                    ("experts_held", cfg.num_experts),
                    ("router_experts",
                     cfg.router_experts or cfg.num_experts)):
        _g_stack.set(float(n), tags={"part": part})
    # no fsdp axis → nothing to stream; fold so the scan stays simple
    gather_mode = gather if fsdp is not None else "upfront"
    dp_axes = tuple(a for a in batch_axes if a != "fsdp")
    repl = NamedSharding(mesh, P())
    data_sharding = batch_sharding(mesh)
    data_spec = data_sharding.spec

    sample_params, param_specs = spmd_param_specs(cfg, mesh, rules)
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs, is_leaf=_is_spec)

    def init_state(key):
        params = init_params(cfg, key)
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    sample = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    state_shardings = {
        "params": param_shardings,
        "opt_state": opt_state_shardings(
            optimizer, sample["params"], param_shardings, repl),
        "step": repl,
    }
    init_jit = observe_compiled(
        jax.jit(init_state, out_shardings=state_shardings),
        "spmd.init_state")

    state_specs = jax.tree.map(lambda s: s.spec, state_shardings,
                               is_leaf=lambda x: isinstance(x, NamedSharding))

    def spec_axes(ax):
        return ax if isinstance(ax, tuple) else (ax,)

    def gather_leaf(p, spec):
        """Local shard → fsdp-full leaf. Tensor-sharded dims stay local
        — they go THROUGH compute sharded."""
        for dim, ax in enumerate(spec):
            for a in spec_axes(ax):
                if a is not None and a != tensor:
                    p = jax.lax.all_gather(p, a, axis=dim, tiled=True)
        return p

    def scatter_leaf(g, spec):
        """fsdp-full grad → reduce-scattered shard (all_gather's
        transpose, written out for the streamed backward)."""
        for dim, ax in enumerate(spec):
            if fsdp in spec_axes(ax):
                return jax.lax.psum_scatter(g, fsdp, scatter_dimension=dim,
                                            tiled=True)
        return g

    def reduce_leaf(g, spec):
        """Locally-reduced grad shard → global mean. psum over the pure
        data axes always; over fsdp only for leaves WITHOUT an fsdp dim
        (gathered leaves already got their fsdp sum+scatter from the
        all-gather's autodiff transpose / the streamed scatter). No
        tensor reduction: tensor-sharded leaves carry exact per-shard
        grads and tensor-replicated leaves identical ones (the
        tp_psum_pair contract)."""
        for ax in dp_axes:
            g = jax.lax.psum(g, ax)
        if fsdp is not None and not any(
                fsdp in spec_axes(ax) for ax in spec):
            g = jax.lax.psum(g, fsdp)
        denom = 1
        for ax in batch_axes:
            denom = denom * jax.lax.axis_size(ax)
        return g / denom

    # ---- per-layer machinery -------------------------------------------- #
    lspecs = param_specs["layers"]
    # one layer (scan dim sliced off) -> spec dims shift left by one
    lspecs1 = jax.tree.map(lambda sp: P(*sp[1:]), lspecs, is_leaf=_is_spec)
    # value_and_grad runs INSIDE the shard_map: the pair, not a raw psum
    fi, gp = tp_psum_pair(tensor) if tensor is not None else (None, None)

    def layer_fn(x, lp):
        x, stats, _ = decoder_block(
            cfg, x, lp, positions_of(*x.shape[:2]), flash_causal,
            col_in=fi, row_out=gp, stat_axes=batch_axes)
        return x, stats

    def gather_layer(shards):
        return jax.tree.map(gather_leaf, shards, lspecs1)

    def _make_streamed_apply():
        """One layer with ZeRO-3 residency: forward consumes the
        PREFETCHED fsdp-full layer from the scan carry but saves only
        (activation, shards) as residuals — the carried full layer gets
        a zero cotangent, so no gathered layer ever becomes a scan
        residual. The backward re-gathers the layer from its shards,
        recomputes the layer vjp (inherent per-layer remat), and
        reduce-scatters the layer grad back to shards. The layer's stats
        (a routed layer's router losses) are a second output, and their
        cotangent goes through the same recomputed vjp."""

        def apply_fn(x, cur_full, shards):
            return layer_fn(x, cur_full)

        def fwd(x, cur_full, shards):
            return layer_fn(x, cur_full), (x, shards)

        def bwd(res, ct):
            x, shards = res
            cur = gather_layer(shards)
            _, vjp = jax.vjp(layer_fn, x, cur)
            dx, dfull = vjp(ct)
            dshards = jax.tree.map(scatter_leaf, dfull, lspecs1)
            return dx, jax.tree.map(jnp.zeros_like, cur), dshards

        ap = jax.custom_vjp(apply_fn)
        ap.defvjp(fwd, bwd)
        return ap

    streamed_apply = _make_streamed_apply()

    def prefetch_layer(shards):
        """The gathered layer the scan CARRIES. ``streamed_apply`` gives it
        a zero cotangent and takes the layer's gradient from its own
        re-gather, so nothing is differentiated here: a gather left in the
        backward reduce-scatters a whole layer of zeros a layer a step."""
        return jax.lax.stop_gradient(gather_layer(shards))

    def run_layers(x, layer_shards, policy):
        if cfg.layer_pattern:  # batch axes only: nothing to gather
            return pattern_stack(cfg, x, layer_shards, flash_causal,
                                 stat_axes=batch_axes, policy=policy)
        if gather_mode == "streamed":
            first = prefetch_layer(
                jax.tree.map(lambda a: a[0], layer_shards))
            # xs pairs each layer's shards with the NEXT layer's (rolled
            # by -1); the wrap-around gather of layer 0 at the last step
            # feeds a carry nobody reads
            xs = (layer_shards,
                  jax.tree.map(lambda a: jnp.roll(a, -1, axis=0),
                               layer_shards))

            def body(carry, xs_i):
                h, cur = carry
                cur_sh, nxt_sh = xs_i
                # issue layer i+1's gather FIRST: XLA schedules the
                # collective to overlap layer i's matmuls
                nxt = prefetch_layer(nxt_sh)
                h, stats = streamed_apply(h, cur, cur_sh)
                return (h, nxt), stats

            (x, _), stats = jax.lax.scan(body, (x, first), xs)
            return x, stats
        full = jax.tree.map(gather_leaf, layer_shards, lspecs)
        body = (jax.checkpoint(layer_fn, policy=policy) if cfg.remat
                else layer_fn)
        return jax.lax.scan(body, x, full)

    def local_loss(shards, tokens, policy):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        emb_local = gather_leaf(shards["embedding"],
                                param_specs["embedding"])
        if tensor is not None:
            x = vp_embed(cfg, emb_local, inputs, tensor, gp)
        else:
            x = emb_local.astype(cfg.dtype)[inputs]
        x, stats = run_layers(x, shards["layers"], policy)
        x = rms_norm(x, shards["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            head_local = emb_local.T
        else:
            head_local = gather_leaf(shards["lm_head"],
                                     param_specs["lm_head"])
        if tensor is not None:
            nll = chunked_nll_mean(
                cfg, fi(x), targets,
                vp_chunk_nll(cfg, head_local, tensor, gp), policy)
            return add_router_losses(cfg, nll, stats)
        chunk_nll = _plain_chunk_nll(cfg, head_local)
        nll = chunked_nll_mean(cfg, x, targets, chunk_nll, policy)
        # a prediction module shares this step's table, head and kernel
        nll, stats, report = add_mtp_loss(
            cfg, shards.get("mtp"), x, tokens, nll, stats,
            embed=lambda ids: emb_local.astype(cfg.dtype)[ids],
            attend=flash_causal, chunk_nll=chunk_nll, stat_axes=batch_axes,
            policy=policy)
        total, router = add_router_losses(cfg, nll, stats)
        return total, {**router, **report}

    # after the state: the loss, and a routed config's router scalars (with
    # a prediction module's two losses among them)
    routed = cfg.num_experts and (not cfg.layer_pattern
                                  or set(cfg.kinds) & set("EL"))
    scalars = 2 if routed else 1

    def step_keeping(groups):
        """The jitted step whose checkpoints keep ``groups`` (of
        ``KEEP_GROUPS``; none: every layer and chunk recomputed whole)."""
        policy = keep_policy(groups)

        def sm_step(state, tokens):
            (loss, router), grads = jax.value_and_grad(
                lambda p: local_loss(p, tokens, policy),
                has_aux=True)(state["params"])
            # params-major maps: the array tree's structure governs, so the
            # PartitionSpec leaves (tuple subclasses) are passed whole
            grads = jax.tree.map(reduce_leaf, grads, param_specs)
            loss = pmean_tree(loss, batch_axes)
            updates, new_opt = optimizer.update(grads, state["opt_state"],
                                                state["params"])
            new_params = optax.apply_updates(state["params"], updates)
            new_state = {"params": new_params, "opt_state": new_opt,
                         "step": state["step"] + 1}
            if not router:
                return new_state, loss
            return new_state, loss, pmean_tree(router, batch_axes)

        return jax.jit(
            shard_map(sm_step, mesh=mesh,
                      in_specs=(state_specs, data_spec),
                      out_specs=(state_specs,) + (P(),) * scalars,
                      check=False),
            in_shardings=(state_shardings, data_sharding),
            out_shardings=(state_shardings,) + (repl,) * scalars,
            donate_argnums=(0,) if donate else ())

    if keep is not None or gather_mode == "streamed":
        # a test's subset, no choice made; or the streamed fsdp scan, left
        # exactly as it was: it recomputes a layer by construction, and
        # what it could keep (its loss's products too) is a later PR's
        return (init_jit, observe_compiled(step_keeping(tuple(keep or ())),
                                           "spmd.train_step"),
                data_sharding, state_shardings)

    n_batch_shards = int(np.prod([mesh.shape[a] for a in batch_axes]))
    t = mesh.shape[tensor] if tensor else 1

    def sizes(state, tokens):
        batch, seq = tokens.shape[0] // n_batch_shards, tokens.shape[1] - 1
        return (kept_group_bytes(cfg, batch, seq, tensor=t),
                loss_phase_bytes(cfg, batch, seq, tensor=t))

    # a build with the default optimizer and rules is the same step as the
    # last one with this key, and runs that one's executable
    build_key = ((cfg, mesh, donate)
                 if user_optimizer is None and rules is None else None)
    train_step = _KeepingStep(step_keeping, sizes, mesh.devices.flat[0],
                              "spmd.train_step", build_key)
    return init_jit, train_step, data_sharding, state_shardings


# --------------------------------------------------------------------------- #
# Train-loop wiring (JaxTrainer default loop)
# --------------------------------------------------------------------------- #


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """``"data=4,fsdp=2"`` → ``{"data": 4, "fsdp": 2}``."""
    axes: Dict[str, int] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad mesh spec part {part!r} in {spec!r}")
        k, v = part.split("=", 1)
        axes[k.strip()] = int(v)
    return axes


def build_train_mesh(spec: str = "", devices=None):
    """Mesh for the sharded train loop: ``spec`` (the
    ``RAY_TPU_TRAIN_MESH`` knob / config key) or pure data-parallel
    over all local devices when empty. The same empty spec therefore
    runs devices=1 and devices=N unchanged."""
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.mesh import AXIS_ORDER
    from ray_tpu.util.device_telemetry import backend_devices

    # often a process's first touch of its backend: jax.backend_init
    devs = list(devices) if devices is not None else backend_devices()
    axes = parse_mesh_spec(spec)
    unknown = [k for k in axes if k not in AXIS_ORDER]
    if unknown:
        # make_mesh keeps only AXIS_ORDER names, so a typo'd axis would
        # otherwise yield a silent size-1 mesh (no parallelism at all)
        raise ValueError(f"unknown mesh axis(es) {unknown!r} in "
                         f"{spec!r}; valid axes: {AXIS_ORDER}")
    if not axes:
        axes = {"data": len(devs)}
    n = int(np.prod(list(axes.values())))
    if n > len(devs):
        raise ValueError(f"mesh spec {spec!r} needs {n} devices, "
                         f"have {len(devs)}")
    return make_mesh(axis_sizes=axes, devices=devs[:n])


def _synthetic_token_batches(vocab_size: int, batch: int, seq: int,
                             seed: int = 0, distinct: int = 8):
    """Host-side token stream for loops without a dataset: ``distinct``
    pre-generated numpy batches cycled forever (generation cost off the
    measured path, fresh buffer semantics preserved)."""
    rng = np.random.RandomState(seed)
    pool = [rng.randint(0, vocab_size, (batch, seq + 1)).astype(np.int32)
            for _ in range(distinct)]
    i = 0
    while True:
        yield pool[i % len(pool)]
        i += 1


def _prefetched_synthetic(host, data_sharding, depth: int):
    """Synthetic-batch fallback with the SAME prefetch discipline as
    ``to_jax`` (the ``train_ingest_prefetch`` knob): keep ``depth``
    placed batches in flight ahead of the consumer so H2D transfer
    overlaps compute, instead of the old hardcoded 1-deep buffer."""
    from collections import deque

    from ray_tpu.parallel.sharding import shard_device_put

    depth = max(1, int(depth))
    pending = deque(shard_device_put(next(host), data_sharding)
                    for _ in range(depth))

    def next_tokens():
        pending.append(shard_device_put(next(host), data_sharding))
        return pending.popleft()

    return next_tokens


def spmd_train_loop(config: Optional[Dict[str, Any]] = None):
    """Default ``train_loop_per_worker`` for :class:`JaxTrainer` —
    sharded llama training that runs the SAME config at devices=1 and
    devices=N.

    config keys (all optional): ``model`` (LlamaConfig preset name,
    default "debug") or ``llama_config`` (a LlamaConfig), ``steps``,
    ``batch_per_device``, ``seq``, ``seed``, ``lr``, ``mesh`` (axis
    spec, else the ``RAY_TPU_TRAIN_MESH`` Config knob), ``donate``
    (else ``RAY_TPU_TRAIN_DONATE``), ``gather`` (else
    ``RAY_TPU_TRAIN_GATHER``), ``report_every``. With a
    ``datasets={"train": ds}`` trainer dataset, batches come from the
    shard's ``to_jax`` (sharded, double-buffered ingest) reading the
    ``tokens`` column; otherwise a synthetic token stream feeds the
    step through the same per-shard placement path.

    The loop runs one step ahead of its reports: step i + 1 is issued
    before step i's loss is waited for, and step i is reported when that
    loss is ready. With the flight recorder on, the loop thread's phases of
    a step are spans joined by ``step`` (``spmd.ingest_wait``, ``.dispatch``,
    ``.ready_wait``, ``.fetch``, ``.report``; ``timeline --attribute``).
    """
    import jax
    import optax

    from ray_tpu.core.config import global_config
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.train import session

    config = dict(config or {})
    knobs = global_config()
    cfg = config.get("llama_config") or getattr(
        LlamaConfig, config.get("model", "debug"))()
    steps = int(config.get("steps", 10))
    seq = int(config.get("seq", min(128, cfg.max_seq_len)))
    seed = int(config.get("seed", 0))
    report_every = int(config.get("report_every", 1))
    mesh = build_train_mesh(config.get("mesh", knobs.train_mesh))
    if jax.process_count() > 1:
        # the ingest path assembles the global batch from THIS
        # process's host array (shard_device_put places addressable
        # shards of it) — across a jax.distributed gang that would
        # silently drop every other process's rows. Multi-host SPMD
        # (process-local batch assembly) is the roadmapped next step.
        raise NotImplementedError(
            "spmd_train_loop drives a single-process mesh; multi-host "
            "SPMD over jax.distributed gangs is not wired up yet "
            "(see ROADMAP: SPMD training)")
    donate = bool(config.get("donate", knobs.train_donate))
    gather = str(config.get("gather", knobs.train_gather))
    batch = int(config.get("batch_per_device", 2)) * mesh.size

    optimizer = None
    if "lr" in config:
        optimizer = optax.adamw(float(config["lr"]), b1=0.9, b2=0.95,
                                weight_decay=0.1)
    _t = _fr.now()
    init, step_fn, data_sharding, _ = make_spmd_train_step(
        cfg, mesh, optimizer=optimizer, donate=donate, gather=gather)
    _sp_build.end(_t)
    _t = _fr.now()
    state = init(jax.random.PRNGKey(seed))
    if _t:  # recorder on: the span closes when the state is on the device
        jax.block_until_ready(state)
    _sp_init_state.end(_t)

    try:
        shard = session.get_dataset_shard("train")
    except (KeyError, RuntimeError):
        shard = None
    if shard is not None and hasattr(shard, "to_jax"):
        batches = ({"tokens": b["tokens"]} for b in shard.to_jax(
            batch_size=batch, columns=["tokens"], sharding=data_sharding,
            drop_last=True,
            prefetch_batches=max(1, knobs.train_ingest_prefetch)))

        def next_tokens():
            # a finite dataset ends training at exhaustion (drop_last
            # can eat the tail): None stops the loop after the steps
            # that DID run, instead of StopIteration escaping the
            # worker fn
            b = next(batches, None)
            return None if b is None else b["tokens"]
    else:
        host = _synthetic_token_batches(
            cfg.vocab_size, batch, seq, seed,
            distinct=int(config.get("distinct_batches", 8)))
        next_tokens = _prefetched_synthetic(
            host, data_sharding, knobs.train_ingest_prefetch)

    # every report names the device the steps ran on, as JAX reports it in
    # THIS process: a run on the wrong platform is visible in its results
    dev0 = jax.local_devices()[0]
    ran_on = {"platform": dev0.platform, "device_kind": dev0.device_kind}

    t0 = time.perf_counter()
    tokens_done = 0
    win_t, win_tokens, win_step = t0, 0, 0  # since last report (gauges)
    ready_at = 0.0  # when the step before was seen ready (recorder's clock)

    def settle(i, issued_at, loss, router, n_tokens):
        """Step ``i``'s spans and report, once its loss is ready."""
        nonlocal tokens_done, win_t, win_tokens, win_step, ready_at
        if issued_at:
            # recorder on: close the span at data-ready, not dispatch. A
            # step issued behind the one before starts when that one ends
            _t = _fr.now()
            jax.block_until_ready(loss)
            if i:
                _sp_ready.end(_t, i + 1)
            (_sp_compile if i == 0 else _sp_compute).end(
                max(issued_at, ready_at), i + 1)
            ready_at = _fr.now()
        tokens_done += n_tokens
        if (i + 1) % report_every and i != steps - 1:
            return
        # the router's scalars come with the loss: one fetch a report
        _t = _fr.now()
        lf, moe = jax.device_get((loss, router[0] if router else {}))
        lf = float(lf)
        moe = {k: float(v) for k, v in moe.items()}
        _sp_fetch.end(_t, i + 1)
        _t = _fr.now()
        for k, v in moe.items():
            _ROUTER_GAUGES[k].instant(v, i + 1)
        as_is = {k: moe.pop(k) for k in _REPORTED_AS_IS if k in moe}
        now = time.perf_counter()
        dt = max(now - t0, 1e-9)
        win_dt = max(now - win_t, 1e-9)
        _g_tokens_per_sec.set((tokens_done - win_tokens) / win_dt,
                              tags={"loop": "spmd"})
        step_seconds = win_dt / max(i + 1 - win_step, 1)
        _g_step_seconds.set(step_seconds, tags={"loop": "spmd"})
        win_t, win_tokens, win_step = now, tokens_done, i + 1
        report = {
            "loss": lf,
            "step": i + 1,
            "step_seconds": step_seconds,  # mean since the last report
            "tokens_per_sec": tokens_done / dt,
            "tokens_per_sec_per_chip": tokens_done / dt / mesh.size,
            "devices": mesh.size,
            "mesh": dict(mesh.shape),
            **ran_on,
            **{f"moe_{k}": v for k, v in moe.items()}, **as_is,
        }
        if i == steps - 1:
            report.update(_run_evidence(state))
        session.report(report)
        _sp_report.end(_t, i + 1)

    # One step ahead: step i + 1 is issued BEFORE the loop waits for step
    # i's loss, so the device goes from one step into the next while the
    # host fetches, reports and places a batch. Waiting first left the
    # device idle for exactly as long as the host took, every step, and a
    # host whose cores are busy elsewhere then sets the rate. A step is
    # still reported when its loss is ready. The first step (trace and
    # compile) is waited for alone.
    loss = None
    before = None  # the step issued and not yet settled
    for i in range(steps):
        _t = _fr.now()
        toks = next_tokens()
        _sp_ingest.end(_t, i + 1)
        if toks is None:
            break
        _t = _fr.now()
        state, loss, *router = step_fn(state, toks)
        if i:
            _sp_dispatch.end(_t, i + 1)
        if before is not None:
            settle(*before)
        before = (i, _t, loss, router,
                  int(toks.shape[0]) * (int(toks.shape[1]) - 1))
        if i == 0:
            settle(*before)
            before = None
    if before is not None:
        settle(*before)
    return float(loss) if loss is not None else None


def _run_evidence(state) -> Dict[str, Any]:
    """What the last report adds so that a caller can check HOW the run
    ran, not only that it ended: the process's device report (memory per
    device, compile seconds, compile-cache hits and misses), where the
    largest parameter leaf's shards live, and which attention path every
    traced call took."""
    import jax

    from ray_tpu.ops.flash_attention import paths_taken
    from ray_tpu.parallel.sharding import shard_layout
    from ray_tpu.util.device_telemetry import process_device_report

    largest = max(jax.tree.leaves(state["params"]), key=lambda a: a.size)
    return {
        "device_report": process_device_report(),
        "param_shards": shard_layout(largest),
        "attention_paths": paths_taken(),
    }
