"""MPMD pipeline-parallel training over compiled graphs.

Per "Scaling Deep Learning Training with MPMD Pipeline Parallelism"
(arXiv:2412.14374): instead of one global SPMD program, each pipeline
stage is its OWN program — here a resident actor holding its slice of
the param pytree — and stages exchange activations/gradients
point-to-point. The stage graph (forward chain, loss+grad at the last
stage, backward chain) is compiled ONCE into ring channels
(``experimental_compile(device_channels=True, max_inflight=N)``) — shm
rings between co-located stages, NetRings (core/net_ring.py) between
stages on different nodes — so a training step is M microbatch
``execute()`` calls flowing through the pipeline, activations and
gradients crossing stages on the typed tensor path (no serialization
layer), and per-call scheduling completely out of the loop.

Two schedules:

- ``schedule="1f1b"`` (default; 1F1B per arXiv:1806.03377 /
  arXiv:2412.14374): at most K (= num_stages) microbatches in flight,
  so each stage's activation stash never exceeds K; stage executor
  loops run **backward-over-forward** (the backward nodes are bound
  with a higher scheduling priority, so a stage with both a forward
  and a backward microbatch ready runs the backward first — the 1F1B
  steady-state order); and the per-stage SGD update is **overlapped
  into the drain bubble**: each stage applies its mean-grad step the
  moment its own M-th backward microbatch lands, while downstream
  stages are still draining — no post-flush apply barrier.
- ``schedule="gpipe"``: the PR-8 order — stream all M microbatches in
  a sliding window of ``max_inflight`` (default 2K), then apply
  updates in one eager ``apply_grads()`` barrier after the flush.

Both schedules compute exactly full-batch gradient descent (mean over
microbatch mean-grads), so ``reference_train_losses`` /
``reference_llama_losses`` reproduce them in one process and the
distributed losses AND final params must match to numerical noise.

Two stage models:

- ``model="mlp"`` — the original MLP slices (tanh layers, MSE loss).
- ``model="llama"`` — transformer-block stages reusing
  ``ray_tpu/models/llama.py``: stage 0 owns the embedding plus the
  first block slice, middle stages own contiguous decoder-block
  slices, the last stage owns the final blocks + final_norm + lm_head
  and computes next-token cross-entropy. Only activation-sized
  ``[B, T, dim]`` tensors (and their gradients) cross stages.

    trainer = MPMDPipelineTrainer([8, 32, 32, 4], num_stages=2, lr=0.05)
    losses = trainer.fit(x, y, steps=20, num_microbatches=4)
    trainer.shutdown()
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import ray_tpu
from ray_tpu.util import flight_recorder as _fr

_sp_fwd = _fr.register_span("pipe.fwd", tag_keys=("stage", "chunk", "mb"))
_sp_bwd = _fr.register_span("pipe.bwd", tag_keys=("stage", "chunk", "mb"))
_sp_loss_bwd = _fr.register_span("pipe.loss_bwd",
                                 tag_keys=("stage", "chunk", "mb"))
_sp_step = _fr.register_span("pipe.step")

# Regression-detector feed: the MPMD loop publishes its step time under
# the same gauge name the SPMD loop uses (registered there), tagged
# loop=pipeline, so the health monitor watches one series family.
from ray_tpu.train.spmd import _g_step_seconds  # noqa: E402  (shared gauge)

__all__ = [
    "MPMDPipelineTrainer",
    "init_mlp_params",
    "reference_train_losses",
    "reference_llama_losses",
    "split_llama_stages",
    "split_stages",
]


# ------------------------------------------------------------ model math
#
# A small MLP: tanh on every layer except the final (linear) one, MSE
# loss. The SAME functions drive the stage actors and the single-process
# reference, so loss-equivalence is a property of the schedule, not of
# two implementations agreeing.


def init_mlp_params(layer_sizes: Sequence[int],
                    seed: int = 0) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Deterministic (W, b) list — one entry per layer."""
    rng = np.random.RandomState(seed)
    params = []
    for fan_in, fan_out in zip(layer_sizes[:-1], layer_sizes[1:]):
        scale = np.sqrt(2.0 / fan_in)
        params.append((
            (rng.randn(fan_in, fan_out) * scale).astype(np.float32),
            np.zeros((fan_out,), dtype=np.float32),
        ))
    return params


def split_stages(params: List, num_stages: int) -> List[List]:
    """Partition the layer list into contiguous, near-even stages."""
    if num_stages < 1 or num_stages > len(params):
        raise ValueError(
            f"num_stages={num_stages} must be in [1, {len(params)}]")
    base, extra = divmod(len(params), num_stages)
    out, i = [], 0
    for s in range(num_stages):
        n = base + (1 if s < extra else 0)
        out.append(params[i:i + n])
        i += n
    return out


def _apply_stage(params, x, final_linear: bool):
    import jax.numpy as jnp

    for i, (w, b) in enumerate(params):
        z = x @ w + b
        x = z if (final_linear and i == len(params) - 1) else jnp.tanh(z)
    return x


def _stage_loss(params, a, y):
    import jax.numpy as jnp

    pred = _apply_stage(params, a, True)
    return jnp.mean((pred - y) ** 2)


# ----------------------------------------------------- llama stage math
#
# Transformer-block stages over models/llama.py building blocks: the
# SAME _layer as the SPMD train step (mesh=None: single-program stage),
# stacked layer params sliced [l0:l1] per stage. Stage boundaries carry
# the [B, T, dim] residual stream only.


def split_llama_stages(cfg, params, num_stages: int) -> List[dict]:
    """Slice a models/llama.py param pytree into contiguous block
    stages: stage 0 adds the embedding, the last stage adds final_norm
    + lm_head. Requires untied embeddings (a tied head would couple the
    first and last stage's weights across the pipeline)."""
    if cfg.tie_embeddings:
        raise ValueError(
            "MPMD llama stages need tie_embeddings=False (a tied lm_head "
            "would make stage 0 and stage K-1 share one weight)")
    if num_stages < 1 or num_stages > cfg.n_layers:
        raise ValueError(
            f"num_stages={num_stages} must be in [1, {cfg.n_layers}]")
    bounds = [round(s * cfg.n_layers / num_stages)
              for s in range(num_stages + 1)]
    stages = []
    for s in range(num_stages):
        l0, l1 = bounds[s], bounds[s + 1]
        sp: dict = {"layers": {k: np.asarray(v[l0:l1])
                               for k, v in params["layers"].items()}}
        if s == 0:
            sp["embedding"] = np.asarray(params["embedding"])
        if s == num_stages - 1:
            sp["final_norm"] = np.asarray(params["final_norm"])
            sp["lm_head"] = np.asarray(params["lm_head"])
        stages.append(sp)
    return stages


def _llama_stage_fwd(cfg, sparams, x):
    """One pipeline stage of the backbone: embed (stage 0 only: x is
    int32 tokens there, the residual stream everywhere else), then this
    stage's decoder blocks via lax.scan over the sliced layer stack."""
    import jax

    from ray_tpu.models.llama import _layer, held_to, positions_of

    held_to(cfg, "the MPMD pipeline")
    if "embedding" in sparams:
        x = sparams["embedding"].astype(cfg.dtype)[x]
    positions = positions_of(*x.shape[:2])

    def body(carry, lp):
        return _layer(cfg, None, carry, lp, positions)[0], None

    x, _ = jax.lax.scan(body, x, sparams["layers"])
    return x


def _llama_stage_loss(cfg, sparams, a, tokens):
    """Last stage: remaining blocks + final_norm + lm_head + next-token
    cross-entropy (fp32 log-softmax). ``tokens`` is the full [B, T+1]
    input; the stage slices its own targets."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import head_logits

    x = _llama_stage_fwd(cfg, sparams, a)
    logits = head_logits(cfg, x, sparams["final_norm"], sparams["lm_head"])
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# --------------------------------------------------------- stage actors


class _Chunk:
    """One model chunk resident on a stage actor: its param slice,
    activation stash, grad accumulator, and jitted fwd/vjp/loss. With
    ``virtual_stages == 1`` an actor hosts exactly one chunk (plain
    1F1B/GPipe); the interleaved schedule round-robins ``v`` chunks per
    actor (Megatron-style, arXiv:2104.04473) so each actor always has
    another chunk's work to fill what would otherwise be bubble."""

    def __init__(self, kind, spec_meta, cparams, cid: int,
                 is_first: bool, is_last: bool):
        import jax
        import jax.numpy as jnp

        self.cid = cid
        self.is_first = is_first
        self.is_last = is_last
        self.stash: collections.deque = collections.deque()
        self.stash_max = 0
        self.grad_sum = None
        self.nmb = 0
        self.fwd_seq = 0  # forward-microbatch index within the step
        self.loss_sum = 0.0
        if kind == "mlp":
            self.params = [(jnp.asarray(w), jnp.asarray(b))
                           for w, b in cparams]
            fwd = lambda p, x: _apply_stage(p, x, False)  # noqa: E731
            loss = _stage_loss
        else:  # llama
            cfg = spec_meta
            self.params = jax.tree_util.tree_map(jnp.asarray, cparams)
            fwd = lambda p, x: _llama_stage_fwd(cfg, p, x)  # noqa: E731
            loss = lambda p, a, y: _llama_stage_loss(cfg, p, a, y)  # noqa: E731,E501
        self.jfwd = jax.jit(fwd)

        def _vjp(p, x, g):
            _, vjp_fn = jax.vjp(fwd, p, x)
            return vjp_fn(g)

        def _vjp_first(p, x, g):
            # chunk 0's input is not differentiable for llama (int32
            # tokens); grads flow to params only, a zero scalar rides
            # the output edge as the DAG's (discarded) result
            _, vjp_fn = jax.vjp(lambda pp: fwd(pp, x), p)
            (gp,) = vjp_fn(g)
            return gp, jax.numpy.zeros((), jax.numpy.float32)

        self.jvjp = jax.jit(_vjp_first if is_first else _vjp)
        self.jloss = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    def accum(self, gparams) -> None:
        import jax

        if self.grad_sum is None:
            self.grad_sum = gparams
        else:
            self.grad_sum = jax.tree_util.tree_map(
                lambda a, b: a + b, self.grad_sum, gparams)

    def apply_step(self, lr: float) -> Optional[float]:
        import jax

        mean_grads = jax.tree_util.tree_map(
            lambda g: g / self.nmb, self.grad_sum)
        self.params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g, self.params, mean_grads)
        loss = (self.loss_sum / self.nmb) if self.is_last else None
        self.grad_sum = None
        self.nmb = 0
        self.fwd_seq = 0
        self.loss_sum = 0.0
        return loss

    def reset(self) -> None:
        self.stash.clear()
        self.stash_max = 0
        self.grad_sum = None
        self.nmb = 0
        self.fwd_seq = 0
        self.loss_sum = 0.0


@ray_tpu.remote
class PipelineStageActor:
    """One pipeline stage: one or more model chunks resident on a
    worker, driven by compiled-graph executor loops. ``fwd*`` stashes
    the chunk input (GPipe activation rematerialization: backward
    re-runs the chunk under jax.vjp instead of shipping intermediate
    activations), ``bwd``/``loss_bwd`` accumulate param grads
    chunk-locally; updates apply either eagerly (``apply_grads()``
    barrier, gpipe) or chunk-locally the moment the armed microbatch
    count lands (1F1B overlap — ``set_step_microbatches``)."""

    def __init__(self, kind: str, spec_meta, chunk_params: Dict[int, Any],
                 first_cid: int, last_cid: int, lr: float,
                 stage: int = 0):
        self.kind = kind
        self.lr = lr
        self._stage = stage  # flight-recorder span tag
        self.chunks: Dict[int, _Chunk] = {
            cid: _Chunk(kind, spec_meta, cp, cid,
                        cid == first_cid, cid == last_cid)
            for cid, cp in chunk_params.items()}
        self._last_cid = last_cid
        self._busy_s = 0.0
        self._step_m = 0  # auto-apply target (0 = eager barrier mode)
        self._last_loss: Optional[float] = None

    def _microbatch_done(self, ch: _Chunk) -> None:
        """Bump the chunk's microbatch count; in 1F1B mode the armed
        M-th backward applies the chunk's update HERE, inside the
        pipeline drain — upstream chunks are still running their
        remaining backwards while this one steps its weights
        (update/bubble overlap)."""
        ch.nmb += 1
        if self._step_m and ch.nmb >= self._step_m:
            loss = ch.apply_step(self.lr)
            if ch.is_last:
                self._last_loss = loss

    # ---- compiled-graph node methods (one resident loop each) ----

    def fwd(self, x, cid: int = None):
        import jax.numpy as jnp

        t0 = time.perf_counter()
        _t = _fr.now()
        ch = self.chunks[next(iter(self.chunks)) if cid is None else cid]
        mb = ch.fwd_seq
        ch.fwd_seq += 1
        x = jnp.asarray(x)
        ch.stash.append(x)
        ch.stash_max = max(ch.stash_max, len(ch.stash))
        out = ch.jfwd(ch.params, x)
        out.block_until_ready()
        self._busy_s += time.perf_counter() - t0
        _sp_fwd.end(_t, self._stage, ch.cid, mb)
        return out

    def fwd_first(self, inp, cid: int = None):
        if self.kind == "llama":
            # inp = tokens [B, T+1]; the backbone sees [:, :-1]
            return self.fwd(inp[:, :-1], cid)
        return self.fwd(inp[0], cid)

    def bwd(self, g, cid: int = None):
        import jax.numpy as jnp

        t0 = time.perf_counter()
        _t = _fr.now()
        ch = self.chunks[next(iter(self.chunks)) if cid is None else cid]
        mb = ch.nmb
        x = ch.stash.popleft()
        gparams, gx = ch.jvjp(ch.params, x, jnp.asarray(g))
        ch.accum(gparams)
        gx.block_until_ready()
        self._microbatch_done(ch)
        self._busy_s += time.perf_counter() - t0
        _sp_bwd.end(_t, self._stage, ch.cid, mb)
        return gx

    def loss_bwd(self, a, inp):
        import jax.numpy as jnp

        t0 = time.perf_counter()
        _t = _fr.now()
        ch = self.chunks[self._last_cid]
        mb = ch.nmb
        a = jnp.asarray(a)
        y = jnp.asarray(inp if self.kind == "llama" else inp[1])
        loss, (gparams, ga) = ch.jloss(ch.params, a, y)
        ch.accum(gparams)
        ch.loss_sum += float(loss)
        ga.block_until_ready()
        self._microbatch_done(ch)
        self._busy_s += time.perf_counter() - t0
        _sp_loss_bwd.end(_t, self._stage, ch.cid, mb)
        return ga

    # ---- eager control-plane methods (between pipeline flushes) ----

    def set_step_microbatches(self, m: int) -> None:
        """Arm 1F1B overlapped updates: each chunk applies its
        mean-grad SGD step the moment its m-th backward microbatch
        completes (0 disarms — gpipe/warmup mode, updates via
        apply_grads)."""
        self._step_m = int(m)

    def collect_loss(self):
        """The armed step's mean loss (last chunk's host; None
        elsewhere) — read AFTER the pipeline drains, the updates
        already applied."""
        loss, self._last_loss = self._last_loss, None
        return loss

    def apply_grads(self):
        """Mean each chunk's accumulated microbatch grads, take one SGD
        step, reset. Returns the mean microbatch loss (last chunk's
        host only)."""
        loss = None
        for ch in self.chunks.values():
            if ch.nmb == 0:
                continue
            step_loss = ch.apply_step(self.lr)
            if ch.is_last:
                loss = step_loss
        self._last_loss = None
        return loss

    def reset_state(self):
        """Drop accumulated grads/metrics WITHOUT stepping (used after
        the compile-warming execution)."""
        for ch in self.chunks.values():
            ch.reset()
        self._busy_s = 0.0
        self._last_loss = None

    def get_params(self):
        import jax

        return {cid: jax.tree_util.tree_map(np.asarray, ch.params)
                for cid, ch in self.chunks.items()}

    def stage_stats(self):
        return {"busy_s": self._busy_s,
                "stash_depth": sum(len(ch.stash)
                                   for ch in self.chunks.values()),
                "stash_max": max(ch.stash_max
                                 for ch in self.chunks.values()),
                "stash_actor_max": sum(ch.stash_max
                                       for ch in self.chunks.values())}

    def channel_stats(self):
        from ray_tpu.experimental.channel import STATS

        return dict(STATS)


# ---------------------------------------------------------- the trainer


class MPMDPipelineTrainer:
    """Partition a model across resident stage actors, compile the
    forward/backward stage graph once, and train with a 1F1B (default)
    or GPipe microbatch schedule over ring channels."""

    def __init__(self, layer_sizes: Optional[Sequence[int]] = None,
                 num_stages: int = 2,
                 lr: float = 0.05, seed: int = 0,
                 max_inflight: Optional[int] = None,
                 buffer_size_bytes: int = 8 << 20,
                 params: Optional[List] = None,
                 schedule: str = "1f1b",
                 virtual_stages: int = 1,
                 model: str = "mlp",
                 llama_cfg=None,
                 stage_resources: Optional[List[dict]] = None):
        if num_stages < 2:
            raise ValueError(
                "MPMD pipeline needs >= 2 stages (use a plain in-process "
                "train loop for 1)")
        if schedule not in ("1f1b", "gpipe"):
            raise ValueError(f"unknown schedule {schedule!r} "
                             "(expected '1f1b' or 'gpipe')")
        if virtual_stages < 1:
            raise ValueError("virtual_stages must be >= 1")
        if virtual_stages > 1 and schedule != "1f1b":
            raise ValueError("interleaved virtual stages require "
                             "schedule='1f1b'")
        self.num_stages = num_stages
        self.virtual_stages = virtual_stages
        num_chunks = num_stages * virtual_stages
        self.lr = lr
        self.schedule = schedule
        self.model = model
        if model == "mlp":
            if layer_sizes is None:
                raise ValueError("model='mlp' needs layer_sizes")
            self.layer_sizes = list(layer_sizes)
            if params is None:
                params = init_mlp_params(layer_sizes, seed)
            kind, meta = "mlp", None
            chunk_params = split_stages(params, num_chunks)
        elif model == "llama":
            if llama_cfg is None:
                raise ValueError("model='llama' needs llama_cfg")
            if params is None:
                import jax

                from ray_tpu.models.llama import init_params

                params = init_params(llama_cfg, jax.random.PRNGKey(seed))
            kind, meta = "llama", llama_cfg
            chunk_params = split_llama_stages(llama_cfg, params, num_chunks)
        else:
            raise ValueError(f"unknown model {model!r}")
        # interleaved chunk placement (Megatron, arXiv:2104.04473):
        # chunk c lives on actor c % K, so the forward chain visits the
        # actor ring v times and every actor always holds both early and
        # late pipeline work — the idle gaps of plain 1F1B fill with the
        # other chunk's microbatches
        chunk_actor = [c % num_stages for c in range(num_chunks)]
        # in-flight bound: the driver keeps at most window microbatches
        # outstanding. Plain 1F1B: K (the defining per-stage activation
        # bound). Interleaved: K*v (each in-flight microbatch occupies
        # one of the K*v chunk positions; per-chunk activations are 1/v
        # the size, so per-actor activation MEMORY stays ~K full-stage
        # activations). GPipe: the ring depth.
        self.max_inflight = max_inflight or 2 * num_chunks
        self.window = num_chunks if schedule == "1f1b" \
            else self.max_inflight
        resources = stage_resources or [None] * num_stages
        self.stages = []
        for s in range(num_stages):
            cls = PipelineStageActor
            if resources[s]:
                cls = PipelineStageActor.options(resources=resources[s])
            own = {c: chunk_params[c] for c in range(num_chunks)
                   if chunk_actor[c] == s}
            self.stages.append(cls.remote(
                kind, meta, own, 0, num_chunks - 1, lr, s))
        self._num_chunks = num_chunks
        self._chunk_actor = chunk_actor
        # constructor barrier: compile only against live actors
        ray_tpu.get([s.stage_stats.remote() for s in self.stages])

        from ray_tpu.dag import InputNode

        with InputNode() as inp:
            # forward chain over chunks 0..n-2; the LAST chunk's forward
            # is fused into its loss_bwd (one value_and_grad call)
            h = self.stages[0].fwd_first.bind(inp, 0)
            for c in range(1, num_chunks - 1):
                h = self.stages[chunk_actor[c]].fwd.bind(h, c)
            # backward nodes get scheduling priority on their actor:
            # 1F1B's backward-over-forward rule (a no-op for gpipe —
            # priority only matters when both loops hold ready inputs,
            # which the wider gpipe window also allows)
            last_actor = self.stages[chunk_actor[num_chunks - 1]]
            g = last_actor.loss_bwd.bind(h, inp).with_priority(1)
            for c in range(num_chunks - 2, -1, -1):
                g = self.stages[chunk_actor[c]].bwd.bind(g, c) \
                    .with_priority(1)
        self._dag = g.experimental_compile(
            buffer_size_bytes=buffer_size_bytes,
            device_channels=True,
            max_inflight=self.max_inflight)
        self._warmed = False
        self._armed_m = 0
        self._pipeline_wall_s = 0.0
        self._microbatches_run = 0
        self._torn_down = False

    # ---- schedule ----

    def _split_inputs(self, x, y, num_microbatches: int):
        if self.model == "llama":
            tokens = np.asarray(x, dtype=np.int32)
            if len(tokens) % num_microbatches:
                raise ValueError(
                    f"batch of {len(tokens)} does not split into "
                    f"{num_microbatches} equal microbatches")
            return [t for t in np.split(tokens, num_microbatches)]
        x = np.asarray(x, dtype=np.float32)
        y = np.asarray(y, dtype=np.float32)
        if len(x) % num_microbatches:
            raise ValueError(
                f"batch of {len(x)} does not split into "
                f"{num_microbatches} equal microbatches")
        return list(zip(np.split(x, num_microbatches),
                        np.split(y, num_microbatches)))

    def _warmup(self, mb, timeout: float) -> None:
        """One throwaway microbatch to trigger every stage's XLA compile
        outside the measured/loss-bearing path, then reset stage state
        (params untouched — no apply path runs: auto-apply is disarmed
        and apply_grads is never called)."""
        self._dag.execute(mb, timeout=timeout).get(timeout=timeout)
        ray_tpu.get([s.reset_state.remote() for s in self.stages])
        self._warmed = True

    def _arm(self, num_microbatches: int) -> None:
        """1F1B: tell every stage at which backward count to self-apply
        (one eager barrier, only when M changes — step boundaries are
        pipeline flushes, so this never races in-flight microbatches)."""
        target = num_microbatches if self.schedule == "1f1b" else 0
        if self._armed_m == target:
            return
        ray_tpu.get([s.set_step_microbatches.remote(target)
                     for s in self.stages])
        self._armed_m = target

    def train_step(self, x, y=None, num_microbatches: int = 4,
                   timeout: float = 120.0) -> float:
        """One full-batch step = M microbatches streamed through the
        compiled pipeline. 1F1B: in-flight window K, stages self-apply
        their update as their last backward lands (inside the drain);
        the driver then reads the step loss with one cheap call. GPipe:
        window max_inflight, then an eager apply_grads() barrier."""
        if self._torn_down:
            raise RuntimeError("trainer was shut down")
        mbs = self._split_inputs(x, y, num_microbatches)
        if not self._warmed:
            self._warmup(mbs[0], timeout)
        self._arm(num_microbatches)
        t0 = time.perf_counter()
        _t = _fr.now()
        # sliding window: at most ``window`` microbatches outstanding.
        # The output ring (max_inflight >= window deep) can always
        # absorb every in-flight result — the driver never holds the
        # submit side while the drain side is the only way forward.
        pending: collections.deque = collections.deque()
        for mb in mbs:
            if len(pending) >= self.window:
                pending.popleft().get(timeout=timeout)
            pending.append(self._dag.execute(mb, timeout=timeout))
        while pending:
            pending.popleft().get(timeout=timeout)
        self._pipeline_wall_s += time.perf_counter() - t0
        _sp_step.end(_t)
        _g_step_seconds.set(time.perf_counter() - t0,
                            tags={"loop": "pipeline"})
        self._microbatches_run += num_microbatches
        if self.schedule == "1f1b":
            # updates already applied stage-locally during the drain;
            # one eager read fetches the recorded step loss
            return ray_tpu.get(self.stages[-1].collect_loss.remote())
        losses = ray_tpu.get(
            [s.apply_grads.remote() for s in self.stages])
        return losses[-1]

    def fit(self, x, y=None, steps: int = 1,
            num_microbatches: int = 4) -> List[float]:
        return [self.train_step(x, y, num_microbatches)
                for _ in range(steps)]

    # ---- introspection ----

    def get_params(self):
        """MLP: flat (W, b) list across chunks in pipeline order.
        Llama: list of per-chunk param pytrees in pipeline order (==
        per-stage when virtual_stages is 1)."""
        per_stage = ray_tpu.get(
            [s.get_params.remote() for s in self.stages])
        chunks: Dict[int, Any] = {}
        for d in per_stage:
            chunks.update(d)
        ordered = [chunks[c] for c in range(self._num_chunks)]
        if self.model == "llama":
            return ordered
        out: List[Tuple[np.ndarray, np.ndarray]] = []
        for chunk in ordered:
            out.extend(chunk)
        return out

    def pipeline_stats(self) -> Dict[str, Any]:
        """Measured pipeline efficiency: busy time summed over stages
        against K x wall (the pipeline's capacity to do work). The
        complement is the bubble fraction — GPipe's theoretical floor is
        (K-1)/(M+K-1) per flush; 1F1B shares the floor but keeps the
        activation window at K and fills the drain with weight updates."""
        stats = ray_tpu.get([s.stage_stats.remote() for s in self.stages])
        busy = sum(s["busy_s"] for s in stats)
        wall = self._pipeline_wall_s
        k = self.num_stages
        eff = busy / (k * wall) if wall > 0 else 0.0
        return {
            "num_stages": k,
            "virtual_stages": self.virtual_stages,
            "schedule": self.schedule,
            "model": self.model,
            "max_inflight": self.max_inflight,
            "window": self.window,
            "microbatches_run": self._microbatches_run,
            "pipeline_wall_s": round(wall, 6),
            "stage_busy_s": [round(s["busy_s"], 6) for s in stats],
            "stash_max": max(s["stash_max"] for s in stats),
            "pipeline_efficiency": round(eff, 4),
            "bubble_fraction": round(1.0 - eff, 4),
        }

    def channel_stats(self) -> List[Dict[str, int]]:
        """Per-stage channel byte accounting (the typed-tensor-path
        proof: serialized_bytes must stay flat across training)."""
        return ray_tpu.get([s.channel_stats.remote() for s in self.stages])

    def shutdown(self) -> None:
        if self._torn_down:
            return
        self._torn_down = True
        self._dag.teardown()
        for s in self.stages:
            try:
                ray_tpu.kill(s)
            except Exception:
                pass


# ------------------------------------------------- in-process reference


def reference_train_losses(layer_sizes: Sequence[int], seed: int,
                           x: np.ndarray, y: np.ndarray, steps: int,
                           num_microbatches: int, num_stages: int,
                           lr: float = 0.05,
                           params: Optional[List] = None,
                           return_params: bool = False):
    """Single-process replay of the exact pipeline computation: same
    stage split, same per-stage jax.vjp backward, same
    mean-over-microbatch grad accumulation, same SGD step — so the
    distributed trainer must match these losses to numerical noise
    (both schedules: 1F1B reorders execution, not math)."""
    import jax
    import jax.numpy as jnp

    if params is None:
        params = init_mlp_params(layer_sizes, seed)
    stages = [[(jnp.asarray(w), jnp.asarray(b)) for w, b in st]
              for st in split_stages(params, num_stages)]
    jfwd = jax.jit(lambda p, xx: _apply_stage(p, xx, False))

    def _vjp(p, xx, g):
        _, vjp_fn = jax.vjp(lambda pp, aa: _apply_stage(pp, aa, False),
                            p, xx)
        return vjp_fn(g)

    jvjp = jax.jit(_vjp)
    jloss = jax.jit(jax.value_and_grad(_stage_loss, argnums=(0, 1)))

    x = np.asarray(x, dtype=np.float32)
    y = np.asarray(y, dtype=np.float32)
    xs = np.split(x, num_microbatches)
    ys = np.split(y, num_microbatches)
    losses = []
    for _ in range(steps):
        grad_sums = [None] * num_stages
        loss_sum = 0.0

        def accum(s, g):
            grad_sums[s] = g if grad_sums[s] is None else \
                jax.tree_util.tree_map(lambda a, b: a + b, grad_sums[s], g)

        for xm, ym in zip(xs, ys):
            acts = [jnp.asarray(xm)]
            for s in range(num_stages - 1):
                acts.append(jfwd(stages[s], acts[-1]))
            loss, (gp_last, g) = jloss(stages[-1], acts[-1],
                                       jnp.asarray(ym))
            accum(num_stages - 1, gp_last)
            loss_sum += float(loss)
            for s in range(num_stages - 2, -1, -1):
                gp, g = jvjp(stages[s], acts[s], g)
                accum(s, gp)
        for s in range(num_stages):
            mean_g = jax.tree_util.tree_map(
                lambda gg: gg / num_microbatches, grad_sums[s])
            stages[s] = jax.tree_util.tree_map(
                lambda p, gg: p - lr * gg, stages[s], mean_g)
        losses.append(loss_sum / num_microbatches)
    if return_params:
        flat = []
        for st in stages:
            flat.extend((np.asarray(w), np.asarray(b)) for w, b in st)
        return losses, flat
    return losses


def reference_llama_losses(cfg, seed: int, tokens: np.ndarray, steps: int,
                           num_microbatches: int, num_stages: int,
                           lr: float = 0.05, params=None,
                           return_params: bool = False):
    """Single-process replay of the llama-stage pipeline: same block
    slicing, same per-stage vjp backward, same mean-grad SGD step."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import init_params

    if params is None:
        params = init_params(cfg, jax.random.PRNGKey(seed))
    stages = [jax.tree_util.tree_map(jnp.asarray, sp)
              for sp in split_llama_stages(cfg, params, num_stages)]
    jfwd = jax.jit(lambda p, xx: _llama_stage_fwd(cfg, p, xx))

    def _vjp(p, xx, g):
        _, vjp_fn = jax.vjp(lambda pp, aa: _llama_stage_fwd(cfg, pp, aa),
                            p, xx)
        return vjp_fn(g)

    def _vjp_first(p, xx, g):
        _, vjp_fn = jax.vjp(lambda pp: _llama_stage_fwd(cfg, pp, xx), p)
        return vjp_fn(g)[0]

    jvjp = jax.jit(_vjp)
    jvjp0 = jax.jit(_vjp_first)
    jloss = jax.jit(jax.value_and_grad(
        lambda p, a, t: _llama_stage_loss(cfg, p, a, t), argnums=(0, 1)))

    tokens = np.asarray(tokens, dtype=np.int32)
    mbs = np.split(tokens, num_microbatches)
    losses = []
    for _ in range(steps):
        grad_sums = [None] * num_stages
        loss_sum = 0.0

        def accum(s, g):
            grad_sums[s] = g if grad_sums[s] is None else \
                jax.tree_util.tree_map(lambda a, b: a + b, grad_sums[s], g)

        for tm in mbs:
            tm = jnp.asarray(tm)
            acts = [tm[:, :-1]]
            for s in range(num_stages - 1):
                acts.append(jfwd(stages[s], acts[-1]))
            loss, (gp_last, g) = jloss(stages[-1], acts[-1], tm)
            accum(num_stages - 1, gp_last)
            loss_sum += float(loss)
            for s in range(num_stages - 2, 0, -1):
                gp, g = jvjp(stages[s], acts[s], g)
                accum(s, gp)
            accum(0, jvjp0(stages[0], acts[0], g))
        for s in range(num_stages):
            mean_g = jax.tree_util.tree_map(
                lambda gg: gg / num_microbatches, grad_sums[s])
            stages[s] = jax.tree_util.tree_map(
                lambda p, gg: p - lr * gg, stages[s], mean_g)
        losses.append(loss_sum / num_microbatches)
    if return_params:
        return losses, [jax.tree_util.tree_map(np.asarray, sp)
                        for sp in stages]
    return losses
