"""Llama-3-family decoder, TPU-first.

Functional pytree model (no framework classes): params are nested dicts with
per-leaf logical axes consumed by ray_tpu.parallel.sharding rules, so one
model definition runs dp/fsdp/tp/sp via GSPMD. Design choices for the MXU:

- layers stacked and scanned (``lax.scan``) — one compiled layer body,
  constant compile time in depth; or, where the config gives a
  ``layer_pattern``, a stack of Mamba-2, routed and attention layers (each
  ONE half of the block), of shortcut-connected double layers (latent
  attention, :func:`shortcut_layer`) or of whole routed blocks whose
  attention is full and unrotated in some layers and windowed and rotated
  in the others (:func:`window_block`) or reads the keys a learned indexer
  picks (:func:`index_block`), or whose mixer is the gated delta rule in
  some layers (:func:`delta_block`: a STATE a sequence, no row a position)
  and gated full attention in the others (:func:`gated_block`), or of
  latent-attention blocks outside the double layer, TRAINED through the
  flash kernel (:func:`latent_block`; a prediction module behind them:
  :func:`add_mtp_loss`) and SERVED over latent rows
  (:func:`serve_latent_block`), their residual stream ONE row a token or
  ``hc_mult`` rows mixed around every sublayer (:func:`hyper_connected`),
  weights stacked per kind and walked in the pattern's order
  (:func:`pattern_layer`, :func:`pattern_stack`);
- bf16 matmuls with fp32 accumulation (``preferred_element_type``), params
  stored fp32, gradients/optimizer fp32;
- ``jax.checkpoint`` per layer (remat) to trade FLOPs for HBM, its products
  named (``KEEP_GROUPS``) so that a step with room can keep some of them;
- attention: GQA + RoPE; ring attention over the ``seq`` mesh axis for long
  context, plain (XLA-fused, or Pallas flash) otherwise;
- static shapes everywhere; causal masking is position arithmetic, no
  dynamic control flow.

The reference delegates all of this to torch/DeepSpeed (SURVEY.md §2.3);
here it is the in-framework model that Train, Serve and the benchmark
(``benchmarks/run.py``) run.
"""

from __future__ import annotations

import itertools
import math
import threading
from contextlib import nullcontext
from dataclasses import dataclass
from functools import partial
from types import SimpleNamespace
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.layers import (layer_norm, rms_norm, rotary_embedding,
                                rotate_pairs)
from ray_tpu.parallel.ring_attention import plain_attention, ring_attention_local
from ray_tpu.parallel.sharding import DEFAULT_RULES, logical_sharding
from ray_tpu.util import flight_recorder as _fr
from ray_tpu.util.metrics import Gauge
from ray_tpu.util.xla_observatory import observe_compiled

# the decode engine's padded-bucket contract made measurable: distinct
# padded KV lengths each cost one compilation (decode_step_with_cache
# docstring) — this gauge is the decode-side churn-attribution signal
# next to ray_tpu_xla_program_variants{program=llama.decode}
_g_decode_buckets = Gauge(
    "ray_tpu_serve_decode_buckets",
    "Distinct padded KV lengths (compile buckets) the decode engine "
    "has served", tag_keys=("kind",))
# the engine's resident weights by dtype, set once when it is built: the
# leaves its programs multiply are held in cfg.dtype, so at a bfloat16
# config nearly every byte reads dtype=bfloat16 and the float32 norms are
# the rest; weight bytes under float32 are bytes every call would convert
_g_engine_weight_bytes = Gauge(
    "ray_tpu_serve_engine_weight_bytes",
    "Bytes of model weights the decode engine keeps on the device, by "
    "the dtype they are held in", tag_keys=("dtype",))
# the layer bodies ONE program of the engine traces, set once beside it: the
# walker scans a stack's whole periods and, where it walks in line, a run of
# one kind (_segments), so a body is traced once however often it is scanned.
# A program's time to trace, lower, compile and load follows it, not the depth
_g_engine_traced_layers = Gauge(
    "ray_tpu_serve_engine_traced_layers",
    "Layer bodies one serving program of the decode engine traces: a "
    "scanned period's layers or a scanned run's one, and each layer in line")

# where a prefill's stream is cut to the last real position (stream_cut): the
# layers it runs over ALL positions and over ONE. Every family whose every
# layer keeps a row reads its depth and 0
_g_engine_prefill_layers = Gauge(
    "ray_tpu_serve_engine_prefill_layers",
    "Layers a prefill of the decode engine runs over every position of the "
    "prompt (positions=all) and over the last real one alone (positions=one: "
    "the layers behind the last that keeps a row)",
    tag_keys=("positions",))

# what ONE position (in a store by slot: one SLOT position) leaves in the
# engine's page stores, over all layers, summed under the tag each store has
# in the table of served kinds (SERVED's rows: kv, latent, full, window,
# index); the tags an engine has not read 0
_g_engine_page_bytes = Gauge(
    "ray_tpu_serve_engine_page_bytes",
    "Bytes one position holds in the decode engine's page store, by the "
    "layout the layer kind gives it", tag_keys=("kind",))
# what ONE SEQUENCE leaves in the stores whose table is "state" (TABLES),
# whatever its length, over all layers: a recurrent layer's state and its
# convolution's tail. Not bytes a position: page_bytes does not count them
_g_engine_state_bytes = Gauge(
    "ray_tpu_serve_engine_state_bytes",
    "Bytes one sequence holds in the decode engine's state stores whatever "
    "its length, by part", tag_keys=("part",))
# set where the engine builds its programs: the groups XLA's grouped expert
# products run over (part=program: a kind's whole stack, read where it
# lies) and the experts one layer holds (part=layer). Equal, the programs
# cut each layer's experts out of the stack; a dense engine reads 0 and 0.
# A prefill's products that take the Pallas kernel read the layer's experts
# by index map (ray_tpu_serve_engine_expert_products{path}, below)
_g_engine_expert_groups = Gauge(
    "ray_tpu_serve_engine_expert_groups",
    "Groups the decode engine's grouped expert products run over, and the "
    "experts one layer holds", tag_keys=("part",))
# the window layers' page slabs (LlamaDecodeEngine: a slot a page that holds
# window rows): how many the stores have, and how many are assigned
_g_engine_window_slots = Gauge(
    "ray_tpu_serve_engine_window_slots",
    "Page slabs of the decode engine's window stores: all of them, and "
    "those assigned to a pool page", tag_keys=("state",))
# which way a prefill's attention went, counted where a program is traced
# (attend_tiles; attend_delta): a Pallas kernel (ops/flash_prefill.py; kind
# delta: ops/gdn_prefill.py) or XLA (path=tiles; kind delta: path=chunks),
# by the layer kind that asked. Both paths of a kind are always set, the one
# not taken at what it has counted so far (0 in an engine's process on the
# chip); prefill_attend_paths() keeps the reason beside the count
_g_engine_prefill_attend = Gauge(
    "ray_tpu_serve_engine_prefill_attend",
    "Prefill attentions traced into the decode engine's programs (and the "
    "full forward), by layer kind and by the path they took: a Pallas "
    "kernel, or XLA tiles / chunks", tag_keys=("kind", "path"))
# which way the experts' products on a kind's STACKED leaves went, counted
# where a program is traced (ops/moe.py _expert_ffn tells an engine's
# process through watch_stacked_calls): in an engine's process on the chip
# the prefill programs read path=kernel and the decode programs (a few rows
# a call) path=xla; expert_product_paths() keeps the reason beside the count
_g_engine_expert_products = Gauge(
    "ray_tpu_serve_engine_expert_products",
    "Calls of the experts' products on stacked leaves traced in a decode "
    "engine's process, by the path they took: the Pallas grouped kernel or "
    "XLA's grouped products", tag_keys=("path",))
# the last prefill's routed assignments, averaged over its layers: on the
# experts held here, on identity experts, and on experts held elsewhere
# (left out). Read with the logits; a model without a router sets none
_g_moe_assignment_share = Gauge(
    "ray_tpu_serve_moe_assignment_share",
    "Share of the last prefill's routed assignments that fell on experts "
    "held here, on identity experts, or elsewhere", tag_keys=("part",))

# an indexed block attends over the keys its indexer picks: what the last
# call attended of what it could see (1.0 while nothing is longer than
# index_topk), counted on the host from the call's own length
_g_engine_selected_share = Gauge(
    "ray_tpu_serve_engine_selected_share",
    "Keys the decode engine's indexed blocks attended over the keys "
    "visible, last call, by program", tag_keys=("program",))

# the layers of the engine's stack that MAKE a selection (role=select: an
# indexed block, a full layer under DSA_KINDS: index scores, an index-key
# store) against those that attend under the one made below them
# (role=reuse: no indexer weights, no index keys, no score), set once beside
# the engine; a stack that selects nothing reads 0 and 0
_g_engine_selecting_layers = Gauge(
    "ray_tpu_serve_engine_selecting_layers",
    "Layers of the decode engine's stack that make a selection of the keys "
    "they attend, and layers that reuse the selection of a layer below them",
    tag_keys=("role",))

# LlamaDecodeEngine's calls taken apart (one registration site per name):
# the device program against what the host does on either side of it. The
# three prefill spans lie inside the scheduler's serve.prefill; the three
# decode parts inside engine.decode (one sequence, one token). The pages
# live on the device, so three of them time what is left of a host copy
# (microseconds): floor_exempt, one record per call, so that they read as
# a small number and not as a missing one.
_sp_prefill_program = _fr.register_span("engine.prefill_program",
                                        tag_keys=("pages",))
_sp_prefill_kv = _fr.register_span("engine.prefill_kv", tag_keys=("pages",),
                                   floor_exempt=True)
_sp_prefill_logits = _fr.register_span("engine.prefill_logits",
                                       tag_keys=("pages",),
                                       floor_exempt=True)
_sp_decode = _fr.register_span("engine.decode", tag_keys=("pages",))
_sp_decode_upload = _fr.register_span("engine.decode_upload",
                                      tag_keys=("pages",),
                                      floor_exempt=True)
_sp_decode_program = _fr.register_span("engine.decode_program",
                                       tag_keys=("pages",))
_sp_decode_readback = _fr.register_span("engine.decode_readback",
                                        tag_keys=("pages",))
# a stack with window layers: the engine's slot bookkeeping in a prefill or
# decode call (looking the pages' slots up, assigning the missing ones,
# building the table that is uploaded); inside engine.prefill_program's or
# engine.decode_upload's stretch of the call, microseconds
_sp_window_slots = _fr.register_span("engine.window_slots",
                                     tag_keys=("pages",), floor_exempt=True)
# the engine's constructor, one record an engine (``timeline --attribute``'s
# set-up block): engine.build is entry to return; inside it engine.weights
# (the ``serving_params`` jit or conversion, to the tree ready on the device)
# and one engine.stores a store kind of ``served_stores`` (that kind's page,
# slot or state arrays, to ready). What is left of engine.build is the pool,
# the gauges and the copy programs.
_sp_engine_build = _fr.register_span("engine.build")
_sp_engine_weights = _fr.register_span("engine.weights")
_sp_engine_stores = _fr.register_span("engine.stores", tag_keys=("kind",))


# What a train step may KEEP of its forward pass where it would otherwise
# recompute it in the backward (``jax.checkpoint`` around a layer and around
# a chunk of the loss): every kept value carries its group's name
# (``checkpoint_name``). ``attn``: q / k / v as they are handed to ``attend``,
# the flash kernel's output and log-sum-exp (ops/flash_attention.py), the
# ``wo`` product; ``mlp``: the dense SwiGLU's gate and up products; ``head``:
# the logits product in the compute type. All are results in cfg.dtype with
# the model's width contracted away, so a group's bytes say what it saves. A
# name is the identity outside a checkpoint (the serving programs) and under
# one whose policy does not list it. In the order a tie is broken in.
KEEP_GROUPS = ("attn", "mlp", "head")


def keep_policy(keep):
    """``jax.checkpoint``'s ``policy`` that keeps the groups ``keep`` (of
    :data:`KEEP_GROUPS`); None, which keeps nothing, for none."""
    if not keep:
        return None
    return jax.checkpoint_policies.save_only_these_names(*keep)


# a patterned stack's layer kinds: the character -> the name of the kind's
# stacked weights under params["layers"]. "L" / "G" (LATENT_KINDS): latent
# attention THEN the routed MLP ("L") or a dense SwiGLU ("G"). TRAINED: the
# train steps attend them with the flash kernel over per-head keys and values
# at ONE width (forward, dQ and dK/dV are Pallas calls), NOT with prefill's
# kernel, which is forward only and whose transpose is the XLA tile loop.
# SERVED too (rows of SERVED): prefill's kernel over expanded keys and
# values, decode absorbed over ONE latent row a position. Their stream is
# the only one that may be cfg.hc_mult rows a token ([B, T, hc_mult * dim],
# or the rows apart where the engine serves them, widened behind the
# embedding and summed in front of the final norm; no train step takes that)
LAYER_KINDS = {"M": "mamba", "E": "moe", "*": "attn", "S": "scmoe",
               "F": "block", "W": "block", "I": "index", "D": "delta",
               "A": "gated", "L": "latent", "G": "latent_dense",
               "P": "parallel", "R": "parallel",
               "H": "hybrid_mamba", "N": "hybrid_attn",
               "Y": "dsa_full", "Z": "dsa_shared", "X": "dsa_dense",
               "m": "memory_mamba", "w": "memory_attn", "f": "memory_attn",
               "g": "memory_gate", "c": "memory_cross"}
# the kinds that are a WHOLE block (attention THEN the routed MLP, whose
# router reads the attention's normed input; window_block): "F" attends over
# every earlier position without rotation, "W" over the last cfg.window with
# rotation. They have the same leaves and share ONE stack, in layer order.
BLOCK_KINDS = "FW"
# the kinds of the delta-rule family: whole blocks too, each with a routed MLP
# of its own; "D" mixes through the gated delta rule, "A" through gated full
# attention. A stack EACH (their mixers' leaves differ)
DELTA_KINDS = "DA"
# the latent-attention blocks outside "S" (latent_block):
# whole blocks, a stack each (their MLPs' leaves differ)
LATENT_KINDS = "LG"
# the PARALLEL blocks (parallel_block): ONE
# mean-subtracting norm a layer feeds the attention AND the routed MLP, and
# both are added to the stream. "P" attends over every earlier position
# without rotation, "R" over the last cfg.window with rotation. The same
# leaves, ONE stack in layer order (as "F" / "W")
PARALLEL_KINDS = "PR"
# the kinds whose attention has cfg.window ("W" and "R" rotate, "w" does not)
WINDOW_KINDS = "WRw"
# the state-space hybrid's WHOLE blocks (hybrid_block): a mixer, then a dense
# SwiGLU, each added ``residual_multiplier`` times. "H" mixes through Mamba-2
# (ops/ssm.py: a STATE a sequence, no row a position), "N" through GQA without
# rotation whose scores are scaled by ``attention_multiplier``. A stack EACH
# (their mixers' leaves differ)
HYBRID_KINDS = "HN"
# latent attention UNDER a learned selection (dsa_block): a latent block
# ("L" / "G"'s attention and MLPs) that attends, a query, the cfg.index_topk
# latent rows an indexer picks. "Y" (routed MLP) and "X" (dense SwiGLU: a
# stack's leading layers) are FULL: each has an indexer, which reads the
# query's own latent, and makes the selection. "Z" (routed MLP) is SHARED: it
# has NO indexer and attends what the nearest full layer below it selected.
# A stack EACH (their leaves differ), the routed ones first (LATENT_KINDS'
# order and reason). SERVED only
DSA_KINDS = "YZX"
DSA_FULL = "YX"
# the decoder-hybrid-decoder's WHOLE blocks (memory_block, arXiv:2507.06607):
# a mixer, then a dense SwiGLU, each under a LayerNorm with gain AND bias.
# "m" mixes through Mamba-1 (ops/s6.py: a STATE a sequence, no row a
# position) and hands its scan's output on beside the stream, THE MEMORY.
# "w" / "f": DIFFERENTIAL attention without rotation (two softmax maps over
# one value, their difference normed), over the last cfg.window positions or
# over every earlier one; "f" hands its keys and values on beside the
# stream. "g" (a gated memory unit) gates the newest memory at its own
# position, "c" (cross attention) attends the newest "f" layer's keys and
# values with a query of its own and has no key or value projection: NEITHER KEEPS A ROW.
# "w" and "f" have the same leaves and share ONE stack in layer order, the
# others a stack each. SERVED only
MEMORY_KINDS = "mwfgc"
# Where their matrices start off the square root of their fan-in (seeded random
# weights; a checkpoint brings its own): ``wo`` 12 times as wide, the router
# 6 times, so that BOTH halves carry the logits and a comparison of logits
# sees a fault in either. At fan-in scaling it sees neither. Attention over
# thousands of keys whose scores are one unit wide is nearly an average, 0.03
# of the stream a layer against the experts' 0.3: a window, a rotation or a
# slot does not show. And a top-k choice is a hard one: bfloat16 products
# move the LAST of a token's six choices in some 3% of its layers, a router
# one unit wide weighs the six 0.27 .. 0.09, and one moved choice is then a
# third of a layer's routed sum, 6-16% of a logit (4 of 16 readings on the
# chip): more than SwiGLU for ReGLU moves it. A router 6 units wide weighs
# the six 0.90, 0.08, 0.013 .. 0.001: the choice that rounding can move
# weighs next to nothing but for the rare token whose six lie close
# together (wider than 6 the tail grows again: each layer's sharper choice
# multiplies what the layers before it left). ``wo`` sets the halves'
# shares: at 12 the routed sum is some 12% of a logit, enough that the
# router's input and the experts' activation show, little enough that the
# worst moved choice stays under the limit (PERF.md, PR 38, has the
# readings at 3, 4, 6, 8, 12 and 16). WHICH experts are chosen, and so their
# load and every product's shape and time, is the same at any width.
BLOCK_INIT = {"wo": 12.0, "router": 6.0}
# The indexed block's ("I", index_block) starting scales off the square root
# of the fan-in, for the same reason: ``wo`` starts 16 times as wide and
# ``q_norm`` (the per-head QK-norm's gain on the query) at 0.7, not 1.
# ``wo`` sets the halves' shares. The routed half here is the HELD experts'
# eighth of the routed sum, and what makes a sound engine's rare large
# difference is in it: the router's 8th and 9th logits of 128 lie 0.06
# apart, a stream that differs by rounding moves that choice in one
# token-layer of ten, and where a held expert is the one moved that is a
# whole layer's routed part. At ``wo`` 1 that read 6.3% of a logit in one of
# four sound positions, at 2 (router 4 times as wide, which weighs the moved
# choice at 0.01) still 0.97-5.50% over 12 seeds against 8.4% for the score
# without its ReLU; at 16 the attention is nearly all of a layer's output
# and the sound engine reads 0.52-1.92% (19 seeds), every fault of the
# attention or the indexer 8.3% or more. The price: leaving the router's
# renormalisation out moves a logit by 1.5-1.9%, under any limit that
# passes the sound engine (tests/test_keye_vl2.py holds it in float32).
# The gain: QK-norm makes a score one unit wide times the gain whatever the
# weights. At 1.4 some 290 of a query's 2,048 chosen keys carry its weight
# (2,048 / exp(gain^2), for normal scores) and one key that bfloat16 index
# scores move across the last place (3.5 of 2,048 do, a query) can be a
# head's best; at 0.7 some 1,250 carry it, and leaving the norm out (scores
# then one unit wide) still reads 14-15%; at 1.0 with ``wo`` 8 that read
# 6.2-6.8%. The indexer's and the router's own weights start at the fan-in
# (both read the NORMED stream): how sharply either chooses, the experts'
# load and every product's shape and time depend on neither scale (my chip
# runs, PR 40; the configuration file's ``correct`` has every reading).
INDEX_INIT = {"wo": 16.0, "q_norm": 0.7}
# The delta-rule ("D", delta_block) and gated-attention ("A", gated_block)
# layers' starting values, for the same reason. ``dt``: the delta layers'
# ``dt_bias`` starts at the inverse softplus of a log-uniform step in this
# range (Mamba-2's start, ops/ssm.py) and NOT at the published ones(): with
# untrained projections a bias of 1 decays a state by exp(-A 1.3), A up to
# 16, and all but one head in thirty forgets everything within a token: the
# state, the page it lies in and the position it was taken at then change
# no logit, and a comparison of logits sees no fault in any of them.
# ``q_norm``: the gated attention's zero-centred QK-norm gain on the query,
# ``1 + w``, starts at this value: scores that many units wide over some
# 8,000 keys (at 1 the softmax is nearly an average and the layer adds 0.03
# of the stream). ``wo``: its output product starts that many times as wide,
# so that the attention layers carry the logits as the delta layers do
# (whose gated norm makes their output one unit wide whatever the state's
# size). ``router``: the routers start that many times as wide as their
# fan-in, as BLOCK_INIT's does and for its reason: a router one unit wide
# weighs a token's ten of 512 nearly alike (0.1 each), rounding moves the
# tenth choice in a fifth of the token-layers, and a moved choice is then a
# fifth of a layer's MLP output: the sound engine read 3.9-10.7% of a logit
# over ten seeds at 1 and 2.3-4.3% at 4 (4.6 at 6), where the tenth weighs
# a hundredth of the first. Readings: the configuration file's ``correct``
# (my chip runs, PR 45). WHICH experts are chosen, their load and every
# product's shape and time are the same at any width.
DELTA_INIT = {"dt": (0.001, 0.1), "q_norm": 2.0, "wo": 8.0, "router": 4.0}
# Where the hyper-connections' own leaves start (seeded weights; a
# checkpoint brings its own). ``phi`` is over its fan-in, so that ``m`` is
# one unit wide; the three ``alpha`` start at ``alpha`` and not near zero
# (the paper starts the dynamic part small, the mix nearly static): here the
# DYNAMIC part has to carry enough of a logit that a comparison of logits
# refuses a fault in it. ``b``: zero for the read-out and the write-back
# (``H_pre`` around 1 / 2, ``H_post`` around 1), ``res_diag`` on the residual
# mix's diagonal, so that a row keeps some 0.6 of itself and the rows stay
# apart (at zero the doubly stochastic mix is near uniform and ten sublayers
# make the rows one).
HC_INIT = {"alpha": 1.0, "res_diag": 2.0}
# The names ``LlamaConfig.seeded_scales`` may give, a latent block's leaves
# that a CONFIGURATION starts off the square root of their fan-in
# (_init_latent_kind): the attention's ``wo`` and the routed experts'
# ``w_down`` (``expert_down``; the shared expert's and the dense layer's
# stay). The values and their readings are the configuration file's
# (``seeded_scales``, ``correct``), as BLOCK_INIT's reason has it: they set
# the halves' shares of a logit. WHICH experts are chosen, their load and
# every product's shape and time are the same at any of them.
SEEDED_SCALES = ("wo", "expert_down")
# Where a parallel block's SEEDED matrices start off the square root of their
# fan-in (a checkpoint brings its own), for BLOCK_INIT's reason: so that a
# comparison of logits sees a fault in either half and does not trip over a
# sound engine's moved choice. At fan-in scaling a layer adds the four shared
# experts' mean (0.30 an element), ONE held expert's eighth where a token
# chose one (0.075; a sigmoid router's renormalised weights are 1/8 each
# however wide it starts, so no router scale helps) and an attention over
# thousands of keys whose scores are one unit wide, nearly an average (0.025).
# The 8th and 9th of 128 router logits lie 0.06 apart, a stream that differs
# by bfloat16's rounding moves that choice in one token-layer of twenty, and
# where a held expert is the one moved that is 0.075 on a stream of 0.6: 12%
# of a logit in one run of six, while a rotation or a window would show
# nothing. ``wq`` twice as wide makes scores two units wide (some 75 of 4,096
# keys carry a query's weight and which keys a layer sees matters); ``wo``
# sets the attention's share so that a moved choice stays near 1% of a logit.
# WHICH experts are chosen, their load and every product's shape and time are
# the same at any of them. Readings: the configuration file's ``correct``.
PARALLEL_INIT = {"wq": 2.0, "wo": 24.0}
# Where the "H" / "N" blocks' SEEDED leaves start (a checkpoint brings its
# own), for BLOCK_INIT's reason: every mechanism has to carry enough of a
# logit that a comparison of logits refuses a fault in it. ``dt``: the
# time step's range (log-uniform; ``dt_bias`` is its inverse softplus) and
# ``A``: the decay rate's (uniform; ``A_log`` its logarithm), in place of
# init_mamba2's 0.001-0.1 and 1-16: there ``S C`` is a tenth of the skip ``D
# x`` beside it (its width over ``x``'s goes as sqrt(state dt / A)), so the
# state, its page and its position change a logit by next to nothing. At
# these every head's ``S C`` is within 0.5-3 times its ``D x`` and it
# remembers 20-50 tokens, so that BOTH carry the logits: a state read from
# zeros moves them by a fifth, and the skip dropped, the tail zeroed or two
# pad tokens taken for real ones by 4-11% (at 0.05-0.5 and 0.05-1.0, tried
# first, the slowest heads' states were a hundred times the others', the
# gated norm over all 4,096 followed them alone, and the skip dropped read
# 1.9-4.4%).
# ``conv_b``: the convolution's bias starts normal at this width (zeros as
# published would make it no mechanism at all). ``wq``: the attention's
# query projection starts this many times as wide: the published score
# scale is 1/64 on heads 64 wide, scores an eighth of a unit wide over
# thousands of keys, an average; at 16 they are two units wide. ``wo``: its
# output product starts this many times as wide, so that the FOUR attention
# layers of forty carry a share of the logits that a rotation or a wrong
# scale shows in. ``embedding``: the table starts this many times as wide
# (the tied head with it), so that ``embedding_multiplier`` times it is a
# part of the stream that leaving the multiplier out changes. Which tokens go
# where and every product's shape and time depend on none of them. Readings:
# the configuration file's ``correct``.
HYBRID_INIT = {"dt": (0.02, 0.2), "A": (0.25, 1.0), "conv_b": 1.0,
               "wq": 16.0, "wo": 12.0, "embedding": 2.0}
# Where the "m" / "w" / "f" / "g" / "c" blocks' SEEDED leaves start (a
# checkpoint brings its own), for BLOCK_INIT's reason: every mechanism has to
# carry enough of a logit that a comparison of logits refuses a fault in it,
# and a SOUND engine must not trip over its own rounding. ``embedding``: the
# table's rows start THIS wide an element (the tied head with them), not one
# over the square root of the width. Thirty-two undamped blocks of seeded
# weights are a chaotic map: each block rounds some ten bfloat16 operands
# (0.35% of its output), every later block reads that through a LayerNorm,
# and an attention whose scores are several units wide multiplies it: at the
# fan-in start the sound bfloat16 engine read 4-15% of a logit against the
# float32 reference on the chip, a window of 511 19-30% (my chip run, PR 65).
# With the rows 6 wide the stream is the token's own embedding plus the
# blocks' sums (the published family has no such scale; granite's
# embedding_multiplier 12 and residual_multiplier 0.22 do the same there): a
# block reads nearly clean inputs, its rounding and its faults reach the
# logits by its own share and do not compound. ``dt`` and ``A``: HYBRID_INIT's
# reasoning for a state that is [channels, 16]: the time step's range
# (log-uniform; dt_bias its inverse softplus, r W_dt scatters each position's
# around it) and the decay rate's (uniform, a channel AND a state; the
# published start A[:, n] = n + 1 forgets a token inside a step or two), so
# that S C stands beside the skip D u and a state read from zeros, the skip
# dropped or the tail zeroed each move a logit. ``conv_b``: the convolution's
# bias, normal at this width. ``wq``: the WINDOW layers' query projections
# start this many times as wide: scores that many units wide, so that ONE or
# two of a window's 512 keys carry a query's weight and a window of 511 or 513
# shows where the key at its edge is one of them (at one unit the softmax
# over 512 keys is an average, one key a five-hundredth of it). ``wq_full``:
# the full and the cross layers' (whose faults are whole key sets, which show
# at any width). ``wo``: the attention's output product, so that the 9 + 7
# attention layers carry their share beside the Mamba and memory layers'.
# ``lam``: the four learned 64-vectors a layer start normal at this width
# (lq . lk is then some 64 lam^2 wide): lam differs from lam0 by a learned
# amount and lam at 0 or lam0 alone shows. The biases on Wq, Wkv and Wo start
# normal at ``bias``. Which tokens go where and every product's shape and time
# depend on none of them. Readings: the configuration file's ``correct``.
MEMORY_INIT = {"dt": (0.02, 0.2), "A": (0.25, 1.0), "conv_b": 1.0,
               "wq": 8.0, "wq_full": 4.0, "wo": 2.0, "lam": 0.1, "bias": 0.1,
               "embedding": 4.0, "out": 0.25}


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # compute dtype: init_params and the trainer keep float32 master
    # weights and convert at each product; LlamaDecodeEngine holds what it
    # multiplies in this dtype
    dtype: Any = jnp.bfloat16
    # jax.checkpoint around every layer: a layer's backward makes the layer
    # again from its input. False: no checkpoint at all, every intermediate
    # saved. True is not all-or-nothing in the SPMD trainer's step: of the
    # recomputed products it keeps the named ones (KEEP_GROUPS) that fit the
    # device beside the step, and decides that itself from the compiler's
    # memory account (train/spmd.py); the GSPMD and pipeline steps keep
    # nothing. The loss's chunks are under a checkpoint of their own
    # whatever this says (chunked_nll_mean)
    remat: bool = True
    loss_chunk: int = 256  # seq-chunk for the xent head; 0 = unchunked
    # routed experts in place of the dense MLP (0 = dense): mlp_dim is then
    # the width of ONE expert, every token runs its experts_per_token best
    num_experts: int = 0
    experts_per_token: int = 0
    norm_topk_prob: bool = False  # renormalise the chosen weights to sum 1
    lb_loss_coef: float = 0.01    # router losses' weights (routed only)
    z_loss_coef: float = 0.001
    # RMSNorm over the WHOLE q / k projection, before heads and RoPE
    qk_norm: bool = False
    # a head's width where it is not dim // n_heads (0 = that)
    attn_head_dim: int = 0
    rope: bool = True  # False: attention without rotation
    # The stack as data: one character a layer, of which the first n_layers
    # are built. "M" a Mamba-2 mixer (ops/ssm.py), "E" a routed
    # feed-forward, "*" attention; each layer is ONE of them,
    # x + f(RMSNorm(x)). "S": the shortcut-connected double layer
    # (shortcut_layer): two latent attentions and two dense feed-forwards
    # in line, the routed experts beside the first and added at the end.
    # "F" / "W": a whole block (window_block), full unrotated or windowed
    # rotated attention, then the routed MLP whose router reads the
    # attention's input. "I": a whole block too (index_block): rotated
    # attention with per-head QK-norm over the index_topk keys a learned
    # indexer picks for each query, then the routed MLP (its router reads
    # the MLP's own input; a held range of experts may be set). "G" / "L":
    # a whole block of latent attention (the "S" layer's, each sqrt(dim /
    # rank) scale only where mla_scale_* sets it) then a dense SwiGLU of
    # dense_mlp_dim ("G": a stack's leading layers) or the routed MLP with
    # an ungated shared SwiGLU expert ("L"); TRAINED through the flash
    # kernel (latent_block) and served (serve_latent_block). "Y" / "Z" /
    # "X": a latent block that attends under a learned selection
    # (DSA_KINDS, dsa_block), SERVED only. "m" / "w" / "f" / "g" / "c":
    # the decoder-hybrid-decoder's whole blocks (MEMORY_KINDS, memory_block),
    # SERVED only. Empty:
    # every layer is the block (attention THEN MLP), as every dense and
    # every all-routed configuration has it.
    layer_pattern: str = ""
    # the pattern's first BUILT layer: the stack is layer_pattern[first_layer:
    # first_layer + n_layers], one pipeline stage's share of a published
    # stack that the pattern spells whole (0: the pattern's first n_layers)
    first_layer: int = 0
    window: int = 0  # a "W" layer's position i sees j <= i with j > i - window
    ssm_heads: int = 0      # H; d_inner = ssm_heads * ssm_head_dim
    ssm_head_dim: int = 0   # P
    ssm_groups: int = 1     # G: heads that share one B and C
    ssm_state: int = 0      # N
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # the routed layer beyond softmax top-k of SwiGLU experts, all here:
    # num_experts is what THIS device holds, router_experts the router's
    # width (0 = num_experts: all here), first_expert the first one held
    router_experts: int = 0
    first_expert: int = 0
    router_scoring: str = "softmax"  # or "sigmoid", which trains no router
    #   loss; a patterned stack's router has a bias added for the CHOICE
    #   alone, whatever the scoring
    routed_scale: float = 1.0        # multiplies the top-k weights
    mlp_act: str = "swiglu"          # experts: or "reglu" (relu for silu),
    #   or "relu2", two matrices
    shared_mlp_dim: int = 0          # a shared expert's width (0 = none)
    zero_experts: int = 0  # identity experts, the router's LAST outputs: its
    #   width is (router_experts or num_experts) + zero_experts
    # the "S" layer: mlp_dim is ONE routed expert's width, dense_mlp_dim its
    # two dense feed-forwards'; its attention is latent (_latent_half): q
    # through a rank-q_lora_rank bottleneck, keys and values expanded from
    # ONE rank-kv_lora_rank row a position; a head scores over
    # qk_nope_head_dim + qk_rope_head_dim (the last rotated, its key shared
    # by all heads) and returns v_head_dim
    dense_mlp_dim: int = 0
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the "I" layer's indexer: index_heads query heads of index_head_dim on
    # ONE key head, a weight a head; a query attends over the index_topk
    # visible positions of largest index score (all of them while there are
    # no more). index_chunk: the published tiling of the score, here the
    # XLA path's query block; it changes no selection
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_chunk: int = 512
    # the rotation's sections (temporal, height, width), in frequencies:
    # frequency i takes its angle from the component whose section it falls
    # in, a head with fewer frequencies than their sum has the first of them
    # only. Empty: one position a token. Only the "I" layer reads it
    mrope_section: Tuple[int, ...] = ()
    # the "D" layer (delta_block): lin_key_heads query / key heads of
    # lin_key_dim and lin_value_heads value heads of lin_value_dim (a value
    # head reads key head i // (value heads / key heads)), a causal depthwise
    # convolution of lin_conv over [q | k | v], the gated delta rule
    # (ops/gdn.py) in chunks of lin_chunk. The "A" layer (gated_block): the
    # block's heads (n_heads on n_kv_heads of head_dim), ``wq`` twice as wide
    # (a head's query and its output gate), the first partial_rotary_factor
    # of a head's width rotated. Both: shared_mlp_dim is the width of the
    # routed MLP's ONE shared SwiGLU expert behind a per-token sigmoid gate,
    # and every RMSNorm but the delta rule's output norm is zero-centred
    lin_key_heads: int = 0
    lin_value_heads: int = 0
    lin_key_dim: int = 0
    lin_value_dim: int = 0
    lin_conv: int = 4
    lin_chunk: int = 64
    partial_rotary_factor: float = 1.0
    # latent attention's two published corrections for matrices that all
    # start at one variance: the query's normed bottleneck times sqrt(dim /
    # q_lora_rank), the normed latent row times sqrt(dim / kv_lora_rank). A
    # published config of this kind says each by a key of its own; the
    # default is the first such model's, which sets both
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    # the "L" / "G" layers (latent_block): latent attention as the "S"
    # layer's (the ranks and head widths above; a TRAIN step wants the
    # score's width, nope + rope, to be the value's: its flash kernel attends
    # q, k and v of ONE width), then the routed MLP with ONE shared SwiGLU
    # expert of width shared_mlp_dim and no gate in front of it ("L") or a dense
    # SwiGLU of width dense_mlp_dim ("G"). mtp_layers: multi-token-prediction
    # modules behind the stack (0 or 1; add_mtp_loss): one more "L" block
    # that reads [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)] W_eh and predicts
    # t_{i+2} through the model's own embedding and head; its loss is added
    # mtp_loss_weight times
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3
    # the residual stream as hc_mult ROWS a token (manifold-constrained
    # hyper-connections; hyper_connected has the
    # equations): every sublayer of an "L" / "G" block reads ONE mixed row,
    # and its output is written back onto all of them beside a doubly
    # stochastic mix of the rows (hc_sinkhorn_iters normalisations of
    # exp(clip(.., hc_res_clamp_min, hc_res_clamp_max)), hc_eps in the
    # mix's norm and in every divisor). The full forward carries the rows
    # side by side, [B, T, hc_mult * dim], the serving programs apart
    # (widen_stream .. collapse_stream). 1: the plain residual stream, and
    # nothing of this is traced
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp_min: float = -30.0
    hc_res_clamp_max: float = 30.0
    # a published ``rope_scaling`` group of type "yarn" (factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim), key for key; kept as sorted pairs (a config is
    # hashed). Only the latent half reads it: yarn_frequencies turns its
    # rope slice, yarn_softmax_factor multiplies its scores. Empty: the
    # plain rotation at rope_theta
    rope_yarn: Any = ()
    # where an "L" / "G" block's SEEDED leaves start off the square root of
    # their fan-in, the configuration file's data (SEEDED_SCALES has the
    # names: the attention's ``wo``, the routed experts' ``w_down``); kept as
    # sorted pairs. Weights alone: no program reads it, a checkpoint brings
    # its own. Empty: every matrix over the square root of its fan-in
    seeded_scales: Any = ()
    # the "P" / "R" layers (parallel_block): norm_kind "layer" is the
    # mean-subtracting LayerNorm without a bias (ops/layers.py layer_norm),
    # the blocks' ONE norm and the model's final one; rope_interleaved turns
    # the pairs (2i, 2i + 1) of a head and not (i, i + D/2); shared_experts
    # ungated SwiGLU experts of shared_mlp_dim EACH run on every token and
    # their MEAN is added ("average", the one combination a reference holds;
    # the program keeps them as one expert shared_experts times as wide whose
    # down product is divided by their count); logit_scale multiplies the
    # logits
    norm_kind: str = "rms"
    rope_interleaved: bool = False
    shared_experts: int = 1
    shared_combine: str = "average"
    logit_scale: float = 1.0
    # the "H" / "N" layers (hybrid_block), key for key a published config's:
    # the embedding's rows times embedding_multiplier; attention scores times
    # attention_multiplier in place of 1 / sqrt(head_dim) (0: that); a
    # mixer's and an MLP's output times residual_multiplier where it is added
    # to the stream. The fourth, the logits divided by ``logits_scaling``, is
    # logit_scale (its inverse). "H": the ssm_* fields above size its mixer;
    # both: mlp_dim is the dense SwiGLU's width
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    # the "m" layers (memory_block; ops/s6.py): a Mamba-1 mixer ssm_expand x
    # dim channels wide with ssm_state states a channel, a convolution of
    # ssm_conv and a time step through a bottleneck of ssm_dt_rank (0: the
    # published "auto", ceil(dim / 16)). The family's other sizes are the
    # block's: n_heads query heads on n_kv_heads of head_dim, taken in PAIRS
    # (differential attention), window, mlp_dim; norm_kind is "layer" and
    # every LayerNorm has a bias
    ssm_expand: int = 0
    ssm_dt_rank: int = 0

    def __post_init__(self):
        object.__setattr__(self, "mrope_section", tuple(self.mrope_section))
        object.__setattr__(self, "rope_yarn", tuple(sorted(
            dict(self.rope_yarn or ()).items())))
        object.__setattr__(self, "seeded_scales", tuple(sorted(
            dict(self.seeded_scales or ()).items())))
        bad = set(self.layer_pattern) - set(LAYER_KINDS)
        if bad or self.first_layer < 0 or 0 < len(self.layer_pattern) \
                < self.first_layer + self.n_layers or (
                    self.first_layer and not self.layer_pattern):
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: one of "
                f"{sorted(LAYER_KINDS)} a layer, at least first_layer + "
                f"n_layers = {self.first_layer} + {self.n_layers} of them "
                f"(or empty)")
        if (self.router_scoring not in ("softmax", "sigmoid")
                or self.mlp_act not in ("swiglu", "relu2", "reglu")):
            raise ValueError(f"router_scoring={self.router_scoring!r}, "
                             f"mlp_act={self.mlp_act!r}")
        delta = set(self.kinds) & set(DELTA_KINDS)
        if delta and not (
                set(self.kinds) <= set(DELTA_KINDS)
                and ("D" not in delta or (
                    self.lin_key_heads and self.lin_key_dim
                    and self.lin_value_dim and self.lin_conv > 1
                    and self.lin_value_heads % self.lin_key_heads == 0))
                and self.num_experts and self.experts_per_token
                and self.mlp_act == "swiglu" and self.rope
                and not self.qk_norm and not self.zero_experts
                and self.router_scoring == "softmax"
                and self.routed_scale == 1.0
                and 0 < self.partial_rotary_factor <= 1
                and round(self.head_dim * self.partial_rotary_factor) % 2
                == 0):
            raise ValueError(
                "a 'D' / 'A' layer is the gated delta rule (lin_key_heads, "
                "lin_key_dim, lin_value_heads a multiple of them, "
                "lin_value_dim, lin_conv) or gated full attention with its "
                "own per-head QK-norm and a partial rotation, then a "
                "softmax-routed SwiGLU MLP (num_experts, experts_per_token; "
                "a held range and a gated shared expert, shared_mlp_dim, may "
                "be set): every built layer is one of the two, and it has no "
                "whole-projection qk_norm, identity expert or weight scale")
        if (self.partial_rotary_factor != 1.0 or self.lin_key_heads) \
                and not delta:
            raise ValueError("only 'D' / 'A' layers read lin_* and "
                             "partial_rotary_factor")
        if not self.layer_pattern and (
                self.router_experts or self.shared_mlp_dim
                or self.router_scoring != "softmax" or self.zero_experts
                or self.mlp_act != "swiglu" or self.routed_scale != 1.0):
            raise ValueError(
                "a held range, a shared expert, sigmoid scores, a weight "
                "scale, identity experts and relu2 experts belong to a "
                "patterned stack's 'E' or 'S' layer: the block's MLP half "
                "has none of them")
        if "S" in self.kinds and not (
                self.q_lora_rank and self.kv_lora_rank and self.v_head_dim
                and self.qk_nope_head_dim and self.qk_rope_head_dim
                and self.dense_mlp_dim and self.mlp_act == "swiglu"
                and not self.shared_mlp_dim):
            raise ValueError(
                "an 'S' layer needs its latent ranks, its three head "
                "widths and dense_mlp_dim, and has swiglu experts and no "
                "shared one")
        if "I" in self.kinds and not (
                set(self.kinds) == {"I"} and self.index_heads
                and self.index_head_dim and self.index_head_dim % 4 == 0
                and self.index_topk and self.num_experts
                and self.experts_per_token and self.mlp_act != "relu2"
                and self.rope and not self.qk_norm and not self.zero_experts
                and not self.shared_mlp_dim
                and self.router_scoring == "softmax"
                and self.routed_scale == 1.0
                and len(self.mrope_section) in (0, 3)):
            raise ValueError(
                "an 'I' layer is rotated attention with its own per-head "
                "QK-norm over the keys an indexer picks (index_heads, "
                "index_head_dim a multiple of 4, index_topk; mrope_section "
                "empty or three sections), then a softmax-routed gated MLP "
                "(num_experts, experts_per_token; a held range may be set): "
                "every built layer is 'I', and it has no whole-projection "
                "qk_norm, identity or shared expert, or weight scale")
        if self.mrope_section and "I" not in self.kinds:
            raise ValueError("only an 'I' layer reads mrope_section")
        dsa = set(self.kinds) & set(DSA_KINDS)
        if dsa and not (
                set(self.kinds) <= set(DSA_KINDS)
                and self.kinds[0] in DSA_FULL
                and self.q_lora_rank and self.kv_lora_rank
                and self.qk_rope_head_dim % 2 == 0
                and self.qk_nope_head_dim > 0 and self.v_head_dim > 0
                and self.index_heads and self.index_head_dim
                and self.index_head_dim % 4 == 0 and self.index_topk
                and ("X" not in dsa or self.dense_mlp_dim)
                and (dsa == {"X"} or (
                    self.num_experts and self.experts_per_token))
                and self.mlp_act == "swiglu" and self.rope
                and not self.qk_norm and not self.zero_experts
                and self.hc_mult == 1 and not self.mtp_layers
                and not self.rope_yarn):
            raise ValueError(
                "a 'Y' / 'Z' / 'X' layer is latent attention (q_lora_rank, "
                "kv_lora_rank, qk_nope_head_dim, an even qk_rope_head_dim, "
                "v_head_dim) over the index_topk positions an indexer picks "
                "(index_heads, index_head_dim a multiple of 4, index_topk), "
                "then a routed SwiGLU MLP ('Y', 'Z': num_experts, "
                "experts_per_token; an ungated shared expert and a held "
                "range may be set) or a dense one ('X': dense_mlp_dim): "
                "every built layer is one of the three, the FIRST makes a "
                "selection ('Y' or 'X': a 'Z' layer reads the one made "
                "below it), and it has no QK-norm, identity expert, "
                "prediction module, YaRN or stream of several rows")
        latent = set(self.kinds) & set(LATENT_KINDS)
        if latent and not (
                set(self.kinds) <= set(LATENT_KINDS)
                and self.q_lora_rank and self.kv_lora_rank
                and self.qk_rope_head_dim % 2 == 0
                and self.qk_nope_head_dim > 0 and self.v_head_dim > 0
                and ("G" not in latent or self.dense_mlp_dim)
                and ("L" not in latent or (
                    self.num_experts and self.experts_per_token))
                and self.mlp_act == "swiglu" and self.rope
                and not self.qk_norm and not self.zero_experts):
            raise ValueError(
                "an 'L' / 'G' layer is latent attention (q_lora_rank, "
                "kv_lora_rank, qk_nope_head_dim, an even qk_rope_head_dim, "
                "v_head_dim: a train step's flash kernel wants the score's "
                "width to be the value's and says so itself) then a routed "
                "SwiGLU MLP ('L': num_experts, "
                "experts_per_token; an ungated shared expert, "
                "shared_mlp_dim, and a held range may be set) or a dense "
                "one ('G': dense_mlp_dim): every built layer is one of the "
                "two, and it has no QK-norm and no identity expert")
        if self.mtp_layers not in (0, 1) or (
                self.mtp_layers and "L" not in latent):
            raise ValueError(
                f"mtp_layers={self.mtp_layers}: one multi-token-prediction "
                "module or none, and its block is an 'L' layer's: the stack "
                "has to have one")
        if self.hc_mult < 1 or (self.hc_mult > 1 and not (
                latent and self.hc_sinkhorn_iters > 0 and not self.mtp_layers
                and self.hc_res_clamp_min < self.hc_res_clamp_max)):
            raise ValueError(
                f"hc_mult={self.hc_mult}: a stream of several rows is mixed "
                "around the sublayers of 'L' / 'G' blocks alone "
                "(hc_sinkhorn_iters > 0, hc_res_clamp_min < "
                "hc_res_clamp_max), and a prediction module has no form "
                "for it")
        if set(dict(self.seeded_scales)) - set(SEEDED_SCALES) or (
                self.seeded_scales and not (latent or dsa)):
            raise ValueError(
                f"seeded_scales={dict(self.seeded_scales)}: starting scales "
                f"of a latent block's leaves ('L' / 'G', 'Y' / 'Z' / 'X'), "
                f"of {SEEDED_SCALES}")
        yarn = dict(self.rope_yarn)
        if yarn and not (
                latent and yarn.get("type", yarn.get("rope_type")) == "yarn"
                and yarn.get("factor", 0) >= 1
                and yarn.get("original_max_position_embeddings", 0) > 0
                and yarn.get("mscale", 1) == yarn.get("mscale_all_dim", 1)):
            raise ValueError(
                f"rope_yarn={yarn}: a rope_scaling group of type 'yarn' "
                "(factor >= 1, original_max_position_embeddings, beta_fast, "
                "beta_slow) whose mscale is its mscale_all_dim (the "
                "rotation's amplitude is then 1: no other is held to a "
                "reference), and only the 'L' / 'G' layers' latent half "
                "reads it")
        blocks = set(self.kinds) & set(BLOCK_KINDS)
        if (bool(set(self.kinds) & set(WINDOW_KINDS)) != bool(self.window)
                or self.window < 0):
            raise ValueError(
                f"window={self.window} with kinds {self.kinds!r}: a 'W' "
                "layer needs a window, and only a 'W' layer has one (as an "
                "'R' layer)")
        parallel = set(self.kinds) & set(PARALLEL_KINDS)
        if parallel and not (
                set(self.kinds) <= set(PARALLEL_KINDS)
                and self.norm_kind == "layer" and self.num_experts
                and self.experts_per_token and self.mlp_act == "swiglu"
                and self.rope and not self.qk_norm and not self.zero_experts
                and self.routed_scale == 1.0 and self.shared_mlp_dim
                and self.shared_experts >= 1
                and self.shared_combine == "average"
                and self.head_dim % 2 == 0):
            raise ValueError(
                "a 'P' / 'R' layer is ONE LayerNorm (norm_kind 'layer') that "
                "feeds attention ('R': rotated, over cfg.window; 'P': not "
                "rotated, over everything) and a routed SwiGLU MLP "
                "(num_experts, experts_per_token; sigmoid or softmax scores "
                "and a held range may be set) beside the mean of "
                "shared_experts ungated shared experts of shared_mlp_dim "
                "(shared_combine 'average'): every built layer is one of the "
                "two, and it has no QK-norm, identity expert or weight scale")
        hybrid = set(self.kinds) & set(HYBRID_KINDS)
        if hybrid and not (
                set(self.kinds) <= set(HYBRID_KINDS) and self.mlp_dim
                and ("H" not in hybrid or (
                    self.ssm_heads and self.ssm_head_dim and self.ssm_state
                    and self.ssm_conv > 1
                    and self.ssm_heads % self.ssm_groups == 0))
                and not self.num_experts and not self.qk_norm
                and self.attention_multiplier >= 0
                and self.n_heads % self.n_kv_heads == 0):
            raise ValueError(
                "an 'H' / 'N' layer is a Mamba-2 mixer (ssm_heads, "
                "ssm_head_dim, ssm_state, ssm_conv, ssm_groups that divide "
                "the heads) or unrotated GQA (n_heads on n_kv_heads, scores "
                "times attention_multiplier), then a dense SwiGLU of mlp_dim: "
                "every built layer is one of the two, and it has no expert "
                "and no QK-norm")
        memory = set(self.kinds) & set(MEMORY_KINDS)
        if memory and not (
                set(self.kinds) <= set(MEMORY_KINDS) and self.mlp_dim
                and self.norm_kind == "layer" and not self.num_experts
                and not self.qk_norm and self.n_heads % 2 == 0
                and self.n_kv_heads % 2 == 0
                and self.n_heads % self.n_kv_heads == 0
                and ("m" not in memory or (
                    self.ssm_expand and self.ssm_state and self.ssm_conv > 1))
                and all({"g": "m", "c": "f"}[c] in self.kinds[:i]
                        for i, c in enumerate(self.kinds) if c in "gc")):
            raise ValueError(
                "an 'm' / 'w' / 'f' / 'g' / 'c' layer is a Mamba-1 mixer "
                "(ssm_expand, ssm_state, ssm_conv, ssm_dt_rank), differential "
                "attention without rotation (an even n_heads on an even "
                "n_kv_heads, over cfg.window or over everything), a gated "
                "memory unit or cross attention, then a dense SwiGLU of "
                "mlp_dim under LayerNorms with a bias (norm_kind 'layer'): "
                "every built layer is one of the five, an 'm' layer stands "
                "below every 'g' (whose memory it gates) and an 'f' layer "
                "below every 'c' (whose keys and values it reads), and it "
                "has no expert and no QK-norm")
        if not memory and (self.ssm_expand or self.ssm_dt_rank):
            raise ValueError("only an 'm' layer reads ssm_expand and "
                             "ssm_dt_rank")
        if not hybrid and (
                self.embedding_multiplier != 1.0 or self.attention_multiplier
                or self.residual_multiplier != 1.0):
            raise ValueError(
                f"embedding_multiplier={self.embedding_multiplier}, "
                f"attention_multiplier={self.attention_multiplier}, "
                f"residual_multiplier={self.residual_multiplier}: only a "
                "stack of 'H' / 'N' layers reads them (no test holds another "
                "kind to a reference with any of them)")
        if not parallel and (
                (self.norm_kind != "rms" and not memory)
                or self.rope_interleaved
                or self.shared_experts != 1
                or (self.logit_scale != 1.0 and not hybrid)
                or self.shared_combine != "average"):
            raise ValueError(
                f"norm_kind={self.norm_kind!r}, rope_interleaved="
                f"{self.rope_interleaved}, shared_experts="
                f"{self.shared_experts}, shared_combine="
                f"{self.shared_combine!r}, logit_scale={self.logit_scale}: "
                "only a stack of 'P' / 'R' layers reads them, one of "
                "'H' / 'N' layers logit_scale and one of 'm' / 'w' / 'f' / "
                "'g' / 'c' layers norm_kind (no test holds "
                "another kind to a reference with any of them)")
        if blocks and (
                not (self.num_experts and self.experts_per_token)
                or self.mlp_act == "relu2" or not self.rope or self.qk_norm
                or self.router_experts or self.zero_experts
                or self.shared_mlp_dim or self.router_scoring != "softmax"
                or self.routed_scale != 1.0):
            raise ValueError(
                "an 'F' / 'W' layer is attention then a softmax-routed "
                "gated MLP with every expert here (num_experts, "
                "experts_per_token; swiglu or reglu): it rotates its 'W' "
                "layers by itself (rope stays True) and has no QK-norm, "
                "held range, identity or shared expert, or weight scale")

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.dim // self.n_heads

    @property
    def latent_row(self) -> int:
        """What a position leaves behind in one latent sublayer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kinds(self) -> str:
        """The built layers' kinds, in order ('' for a stack of blocks)."""
        return self.layer_pattern[self.first_layer:
                                  self.first_layer + self.n_layers]

    @property
    def window_layout(self):
        """The whole pattern as a published config lists it: 1 a layer
        whose attention has the window, else 0."""
        return [int(kind in WINDOW_KINDS) for kind in self.layer_pattern]

    @property
    def rope_layout(self):
        """1 a layer of the whole pattern whose attention rotates."""
        return [int(kind in "WSIALGR" + DSA_KINDS
                    or (kind == "*" and self.rope))
                for kind in self.layer_pattern]

    @property
    def layer_types(self):
        """The whole pattern as a published config names its layers: window
        and full attention layers, or a state-space hybrid's ``mamba`` and
        ``attention`` ones ("H" / "N"), or a decoder-hybrid-decoder's
        ``mamba``, ``gated_memory`` and ``cross_attention`` ones beside its
        attention layers ("m" / "g" / "c")."""
        hybrid = {"H": "mamba", "N": "attention", "m": "mamba",
                  "g": "gated_memory", "c": "cross_attention"}
        return [hybrid.get(kind, "sliding_attention" if kind in WINDOW_KINDS
                           else "full_attention")
                for kind in self.layer_pattern]

    @property
    def indexer_types(self):
        """The whole pattern as a published config names its layers'
        indexers: ``full`` (the layer makes its selection) or ``shared`` (it
        reads the one made below it)."""
        return ["shared" if kind == "Z" else "full"
                for kind in self.layer_pattern]

    @property
    def mlp_layer_types(self):
        """The whole pattern as a published config names its layers' MLPs:
        ``dense`` or ``sparse`` (routed)."""
        return ["dense" if kind in "GX" else "sparse"
                for kind in self.layer_pattern]

    @property
    def qk_head_dim(self) -> int:
        """A latent head's score width, as a published config sums it."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope_parameters(self):
        """The plain rotation as a published config nests it."""
        return {"rope_theta": self.rope_theta, "rope_type": "default"}

    @property
    def logits_scaling(self) -> float:
        """What a published config DIVIDES the logits by: ``logit_scale``'s
        inverse."""
        return 1.0 / self.logit_scale

    @property
    def s6_inner(self) -> int:
        """An "m" layer's channels."""
        return self.ssm_expand * self.dim

    @property
    def s6_dt_rank(self) -> int:
        """An "m" layer's time-step bottleneck: ``ssm_dt_rank``, or the
        published "auto"."""
        return self.ssm_dt_rank or -(-self.dim // 16)

    @property
    def mb_per_layer(self):
        """``n`` where every ``n``-th layer in front of the pattern's full
        attention layer is an "m" layer, as a published config counts it
        (None for any other pattern)."""
        front = self.layer_pattern.split("f")[0]
        n = front.find("m", 1)
        fits = n > 0 and all((kind == "m") == (i % n == 0)
                             for i, kind in enumerate(front))
        return n if fits else None

    def lambda_init(self, kinds: str):
        """``lam0 = 0.8 - 0.6 exp(-0.3 i)`` of the built layers of ``kinds``,
        in order, ``i`` the layer's depth in the published stack."""
        return [0.8 - 0.6 * math.exp(-0.3 * (self.first_layer + i))
                for i, c in enumerate(self.kinds) if c in kinds]

    @property
    def full_attention_interval(self):
        """``n`` where the whole pattern is ``n - 1`` delta-rule layers and
        ONE gated attention layer, over and over, as a published config
        counts it (None for any other pattern)."""
        n = self.layer_pattern.find("A") + 1
        fits = n and all(kind == ("A" if (i + 1) % n == 0 else "D")
                         for i, kind in enumerate(self.layer_pattern))
        return n if fits else None

    @property
    def zero_centered(self) -> bool:
        """Every RMSNorm gain is ``1 + w`` (the "D" / "A" family's)."""
        return bool(set(self.kinds) & set(DELTA_KINDS))

    @property
    def sa_config(self):
        """The indexer as a published config nests it (None without)."""
        if not self.index_heads:
            return None
        return {"indexer_head_dim": self.index_head_dim,
                "indexer_num_heads": self.index_heads,
                "indexer_num_kv_heads": 1,
                "kv_chunk_size": self.index_chunk,
                "q_chunk_size": self.index_chunk, "topk": self.index_topk}

    @property
    def rope_scaling(self):
        """The rotation's sections, or its YaRN group, as a published config
        nests them."""
        if self.rope_yarn:
            return dict(self.rope_yarn)
        if not self.mrope_section:
            return None
        return {"mrope_section": list(self.mrope_section),
                "rope_type": "default", "type": "default"}

    def window_pages(self, page_size: int) -> int:
        """The pages at a sequence's end that can hold a position some later
        position's window reaches: a stretch of ``window`` positions touches
        at most ``ceil(window / page_size) + 1`` pages."""
        return -(-self.window // page_size) + 1

    @staticmethod
    def small(vocab_size: int = 32000) -> "LlamaConfig":
        """~110M params — single-chip bench size."""
        return LlamaConfig(vocab_size=vocab_size, dim=768, n_layers=12,
                           n_heads=12, n_kv_heads=4, mlp_dim=2048,
                           max_seq_len=2048)

    @staticmethod
    def bench(vocab_size: int = 32000) -> "LlamaConfig":
        """~660M params with head_dim=128 — MXU-native lane width, no
        padding in the flash kernel."""
        return LlamaConfig(vocab_size=vocab_size, dim=1536, n_layers=16,
                           n_heads=12, n_kv_heads=6, mlp_dim=6144,
                           max_seq_len=2048)

    @staticmethod
    def debug() -> "LlamaConfig":
        return LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                           n_kv_heads=2, mlp_dim=128, max_seq_len=128,
                           remat=False)

    def num_params(self) -> int:
        d, v, l = self.dim, self.vocab_size, self.n_layers
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.layer_pattern:
            di, gn = self.ssm_heads * self.ssm_head_dim, \
                self.ssm_groups * self.ssm_state
            per_kind = {
                "M": (d * (2 * di + 2 * gn + self.ssm_heads) + di * d
                      + (self.ssm_conv + 1) * (di + 2 * gn)
                      + 3 * self.ssm_heads + di + d),
                "E": ((self.router_experts or self.num_experts) * (d + 1)
                      + 2 * d * self.shared_mlp_dim + self.num_experts
                      * (2 if self.mlp_act == "relu2" else 3)
                      * d * self.mlp_dim + d),
                "*": 2 * d * q + 2 * d * kv + d,
            }
            per_kind["F"] = per_kind["W"] = (
                2 * d * q + 2 * d * kv + d * self.num_experts
                + self.num_experts * 3 * d * self.mlp_dim + 2 * d)
            # the parallel block: attention, the router, the held experts,
            # shared_experts shared ones and ONE norm
            per_kind["P"] = per_kind["R"] = (
                2 * d * q + 2 * d * kv
                + d * (self.router_experts or self.num_experts)
                + self.num_experts * 3 * d * self.mlp_dim
                + self.shared_experts * 3 * d * self.shared_mlp_dim + d)
            hi, di = self.index_heads, self.index_head_dim
            per_kind["I"] = (
                2 * d * q + 2 * d * kv + 2 * self.head_dim
                + d * hi * di + d * di + d * hi + 2 * di
                + d * (self.router_experts or self.num_experts)
                + self.num_experts * 3 * d * self.mlp_dim + 2 * d)
            # the "D" / "A" blocks' routed MLP: router, the held experts, a
            # gated shared expert; then the two mixers
            routed = (d * (self.router_experts or self.num_experts)
                      + self.num_experts * 3 * d * self.mlp_dim
                      + 3 * d * self.shared_mlp_dim
                      + (d if self.shared_mlp_dim else 0) + 2 * d)
            kd = self.lin_key_heads * self.lin_key_dim
            vd = self.lin_value_heads * self.lin_value_dim
            per_kind["D"] = (
                d * (2 * kd + 2 * vd) + d * 2 * self.lin_value_heads
                + self.lin_conv * (2 * kd + vd) + 2 * self.lin_value_heads
                + self.lin_value_dim + vd * d + routed)
            per_kind["A"] = (2 * d * q + 2 * d * kv + q * d
                             + 2 * self.head_dim + routed)
            # the hybrid's whole blocks: the "M" mixer's leaves or plain GQA
            # (each with ONE of the block's two norms), then the dense SwiGLU
            # and the other norm
            dense = 3 * d * self.mlp_dim + d
            per_kind["H"] = per_kind["M"] + dense
            per_kind["N"] = per_kind["*"] + dense
            # the decoder-hybrid-decoder's blocks: a mixer (Mamba-1: in, the
            # convolution and its bias, x, dt and its bias, A, D, out;
            # differential attention with its biases, four lambda vectors
            # and the 2 head_dim gain; cross: the same without keys and
            # values; a memory unit: in and out), the dense SwiGLU and two
            # LayerNorms with gain AND bias
            inner, n, r = self.s6_inner, self.ssm_state, self.s6_dt_rank
            block = 3 * d * self.mlp_dim + 4 * d
            per_kind["m"] = (
                2 * d * inner + (self.ssm_conv + 1) * inner
                + inner * (r + 2 * n) + (r + 1) * inner + inner * n + inner
                + inner * d + block)
            per_kind["c"] = (d * q + q + q * d + d + 6 * self.head_dim
                             + block)
            per_kind["w"] = per_kind["f"] = (
                per_kind["c"] + 2 * d * kv + 2 * kv)
            per_kind["g"] = 2 * d * inner + block
            if set(self.kinds) & set("S" + LATENT_KINDS + DSA_KINDS):
                rq, rkv, h = self.q_lora_rank, self.kv_lora_rank, self.n_heads
                qk = self.qk_nope_head_dim + self.qk_rope_head_dim
                mla = (d * rq + rq + rq * h * qk + d * self.latent_row + rkv
                       + rkv * h * (self.qk_nope_head_dim + self.v_head_dim)
                       + h * self.v_head_dim * d)
                wide = ((self.router_experts or self.num_experts)
                        + self.zero_experts)
                per_kind["S"] = (
                    2 * mla + 2 * 3 * d * self.dense_mlp_dim + wide * (d + 1)
                    + self.num_experts * 3 * d * self.mlp_dim + 4 * d)
                # a block's two hyper-connections: phi, b and three alphas
                n = self.hc_mult
                hc = 2 * ((n * d + 1) * (2 * n + n * n) + 3) if n > 1 else 0
                per_kind["G"] = mla + 3 * d * self.dense_mlp_dim + 2 * d + hc
                per_kind["L"] = (
                    mla + wide * (d + 1) + 3 * d * self.shared_mlp_dim
                    + self.num_experts * 3 * d * self.mlp_dim + 2 * d + hc)
                # under a selection: the latent blocks', and where the layer
                # is FULL its indexer: the query's [q_lora_rank, heads x
                # width], ONE key head, a weight a head, a LayerNorm
                indexer = (rq * hi * di + d * di + d * hi + 2 * di)
                per_kind["Z"] = per_kind["L"]
                per_kind["Y"] = per_kind["L"] + indexer
                per_kind["X"] = per_kind["G"] + indexer
                # a prediction module: its block, W_eh and three norms
                emb += self.mtp_layers * (per_kind["L"] + 2 * d * d + 3 * d)
            if set(self.kinds) & set(MEMORY_KINDS):
                emb += d  # the final LayerNorm's bias
            return emb + sum(per_kind[k] for k in self.kinds) + d
        attn = d * q + 2 * d * kv + q * d
        if self.qk_norm:
            attn += q + kv
        mlp = 3 * d * self.mlp_dim
        if self.num_experts:
            mlp = self.num_experts * mlp + d * self.num_experts
        per_layer = attn + mlp + 2 * d
        return emb + l * per_layer + d


# --------------------------------------------------------------------------- #
# Params
# --------------------------------------------------------------------------- #


def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Pytree of per-leaf logical axis names (leading 'layers' = scan axis)."""
    if cfg.layer_pattern:
        kinds = {
            "mamba": {
                "norm": ("layers", None),
                "w_in": ("layers", "embed", None),
                "conv_w": ("layers", None, None),
                "conv_b": ("layers", None),
                "dt_bias": ("layers", None),
                "A_log": ("layers", None),
                "D": ("layers", None),
                "gate_norm": ("layers", None),
                "w_out": ("layers", None, "embed"),
            },
            "moe": {
                "norm": ("layers", None),
                "router": ("layers", "embed", None),
                "router_bias": ("layers", None),
                "w_up": ("layers", None, "embed", "mlp"),
                "w_down": ("layers", None, "mlp", "embed"),
            },
            "attn": {
                "norm": ("layers", None),
                "wq": ("layers", "embed", "heads"),
                "wk": ("layers", "embed", "kv_heads"),
                "wv": ("layers", "embed", "kv_heads"),
                "wo": ("layers", "heads", "embed"),
            },
        }
        # a double layer's leaves: the two sublayers' stacked behind the
        # layer ([L, 2, ...]), router and experts as the "moe" kind's
        two = ("layers", None)
        kinds["scmoe"] = {
            "attn_norm": two + (None,), "mlp_norm": two + (None,),
            "wq_a": two + ("embed", None), "q_norm": two + (None,),
            "wq_b": two + (None, "heads"),
            "wkv_a": two + ("embed", None), "kv_norm": two + (None,),
            "wkv_b": two + (None, "heads"), "wo": two + ("heads", "embed"),
            "ffn_gate": two + ("embed", "mlp"),
            "ffn_up": two + ("embed", "mlp"),
            "ffn_down": two + ("mlp", "embed"),
            "router": ("layers", "embed", None),
            "router_bias": ("layers", None),
            "w_gate": ("layers", None, "embed", "mlp"),
            "w_up": ("layers", None, "embed", "mlp"),
            "w_down": ("layers", None, "mlp", "embed"),
        }
        # a whole block's leaves: the dense block's with a router and
        # four-dimensional experts
        kinds["block"] = {
            "attn_norm": ("layers", None), "mlp_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "router": ("layers", "embed", None),
            "w_gate": ("layers", None, "embed", "mlp"),
            "w_up": ("layers", None, "embed", "mlp"),
            "w_down": ("layers", None, "mlp", "embed"),
        }
        # an indexed block's: the whole block's, the per-head QK-norm's two
        # gains and the indexer's three projections and LayerNorm
        kinds["index"] = dict(
            kinds["block"], q_norm=("layers", None), k_norm=("layers", None),
            wqi=("layers", "embed", None), wki=("layers", "embed", None),
            ww=("layers", "embed", None), ki_norm=("layers", None),
            ki_bias=("layers", None))
        # the delta-rule family's: a routed MLP with a gated shared expert
        # under either mixer
        routed = {
            "attn_norm": ("layers", None), "mlp_norm": ("layers", None),
            "router": ("layers", "embed", None),
            "w_gate": ("layers", None, "embed", "mlp"),
            "w_up": ("layers", None, "embed", "mlp"),
            "w_down": ("layers", None, "mlp", "embed"),
        }
        if cfg.shared_mlp_dim:
            routed.update(w_sg=("layers", None),
                          ws_gate=("layers", "embed", "mlp"),
                          ws_up=("layers", "embed", "mlp"),
                          ws_down=("layers", "mlp", "embed"))
        kinds["delta"] = dict(
            routed, w_qkvz=("layers", "embed", None),
            w_ba=("layers", "embed", None), conv_w=("layers", None, None),
            dt_bias=("layers", None), A_log=("layers", None),
            gate_norm=("layers", None), w_out=("layers", None, "embed"))
        kinds["gated"] = dict(
            routed, wq=("layers", "embed", "heads"),
            wk=("layers", "embed", "kv_heads"),
            wv=("layers", "embed", "kv_heads"),
            wo=("layers", "heads", "embed"), q_norm=("layers", None),
            k_norm=("layers", None))
        if cfg.mlp_act != "relu2":
            kinds["moe"]["w_gate"] = ("layers", None, "embed", "mlp")
        if cfg.shared_mlp_dim:
            kinds["moe"]["shared_up"] = ("layers", "embed", "mlp")
            kinds["moe"]["shared_down"] = ("layers", "mlp", "embed")
        # the latent blocks': ONE latent attention's leaves (a sublayer of
        # the double layer's, unstacked), then the dense block's MLP or the
        # routed one's with its choice bias and an ungated shared expert
        mla = {w: ("layers",) + ax[2:] for w, ax in kinds["scmoe"].items()
               if w in ("attn_norm", "mlp_norm", "wq_a", "q_norm", "wq_b",
                        "wkv_a", "kv_norm", "wkv_b", "wo")}
        kinds["latent_dense"] = dict(
            mla, w_gate=("layers", "embed", "mlp"),
            w_up=("layers", "embed", "mlp"), w_down=("layers", "mlp", "embed"))
        kinds["latent"] = dict(
            mla, **{w: kinds["scmoe"][w] for w in (
                "router", "router_bias", "w_gate", "w_up", "w_down")})
        if cfg.shared_mlp_dim:
            kinds["latent"].update(shared_gate=("layers", "embed", "mlp"),
                                   shared_up=("layers", "embed", "mlp"),
                                   shared_down=("layers", "mlp", "embed"))
        # under a selection: the latent blocks' leaves, and a FULL layer's
        # indexer (its query reads the query's latent, [q_lora_rank, ...])
        indexer = dict(wqi=("layers", None, None), wki=("layers", "embed", None),
                       ww=("layers", "embed", None), ki_norm=("layers", None),
                       ki_bias=("layers", None))
        kinds["dsa_full"] = dict(kinds["latent"], **indexer)
        kinds["dsa_shared"] = dict(kinds["latent"])
        kinds["dsa_dense"] = dict(kinds["latent_dense"], **indexer)
        # the parallel block's: ONE norm, the whole block's attention, router
        # and experts, and the shared experts side by side as one wide one
        kinds["parallel"] = dict(
            {w: ax for w, ax in kinds["block"].items()
             if w not in ("attn_norm", "mlp_norm")},
            norm=("layers", None), shared_gate=("layers", "embed", "mlp"),
            shared_up=("layers", "embed", "mlp"),
            shared_down=("layers", "mlp", "embed"))
        # the hybrid's whole blocks: the "M" mixer's leaves or the block's
        # attention, then the dense block's MLP
        dense = {"attn_norm": ("layers", None), "mlp_norm": ("layers", None),
                 "w_gate": ("layers", "embed", "mlp"),
                 "w_up": ("layers", "embed", "mlp"),
                 "w_down": ("layers", "mlp", "embed")}
        kinds["hybrid_mamba"] = dict(
            {w: ax for w, ax in kinds["mamba"].items() if w != "norm"},
            **dense)
        kinds["hybrid_attn"] = dict(
            {w: kinds["block"][w] for w in ("wq", "wk", "wv", "wo")}, **dense)
        # the decoder-hybrid-decoder's: served only, whole on every device
        # but for the products every block has
        norms = {w: ("layers", None) for w in (
            "attn_norm", "attn_norm_b", "mlp_norm", "mlp_norm_b")}
        memory = dict(dense, **norms)
        kinds["memory_mamba"] = dict(
            memory, w_in=("layers", "embed", None),
            conv_w=("layers", None, None), conv_b=("layers", None),
            w_x=("layers", None, None), w_dt=("layers", None, None),
            dt_bias=("layers", None), A_log=("layers", None, None),
            D=("layers", None), w_out=("layers", None, "embed"))
        kinds["memory_cross"] = dict(
            memory, wq=("layers", "embed", "heads"), bq=("layers", None),
            wo=("layers", "heads", "embed"), bo=("layers", None),
            lam=("layers", None, None), sub_norm=("layers", None))
        kinds["memory_attn"] = dict(
            kinds["memory_cross"], wkv=("layers", "embed", "kv_heads"),
            bkv=("layers", None))
        kinds["memory_gate"] = dict(
            memory, wg_in=("layers", "embed", None),
            wg_out=("layers", None, "embed"))
        if cfg.hc_mult > 1:  # a sublayer's mix: small, whole on every device
            for kind in ("latent", "latent_dense"):
                kinds[kind].update(hc_phi=("layers", None, None, None),
                                   hc_b=("layers", None, None),
                                   hc_alpha=("layers", None, None))
        out = {
            "embedding": ("vocab", "embed"),
            "layers": {LAYER_KINDS[k]: kinds[LAYER_KINDS[k]]
                       for k in LAYER_KINDS if k in cfg.kinds},
            "final_norm": (None,),
        }
        if set(cfg.kinds) & set(MEMORY_KINDS):
            out["final_norm_b"] = (None,)
        if not cfg.tie_embeddings:
            out["lm_head"] = ("embed", "vocab")
        if cfg.mtp_layers:  # embedding and head are the model's own
            out["mtp"] = {"enorm": (None,), "hnorm": (None,),
                          "eh_proj": (None, "embed"), "final_norm": (None,),
                          "layers": {"latent": kinds["latent"]}}
        return out
    layer = {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
        "attn_norm": ("layers", None),
        "mlp_norm": ("layers", None),
    }
    if cfg.num_experts:
        # every expert on every device: no logical "expert" axis here
        layer.update({
            "router": ("layers", "embed", None),
            "w_gate": ("layers", None, "embed", "mlp"),
            "w_up": ("layers", None, "embed", "mlp"),
            "w_down": ("layers", None, "mlp", "embed"),
        })
    if cfg.qk_norm:
        layer.update({"q_norm": ("layers", None), "k_norm": ("layers", None)})
    out = {
        "embedding": ("vocab", "embed"),
        "layers": layer,
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    return out


def _dense_init(rng, shape, fan_in):
    return (jax.random.normal(rng, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in)))


def _init_pattern_layers(cfg: LlamaConfig, key) -> Dict[str, Any]:
    """A patterned stack's weights, stacked per KIND in the order the
    pattern meets them: ``{"mamba": {leaf: [n_M, ...]}, "moe": {...},
    "attn": {...}, "scmoe": {...}, "block": {...}}``, a kind the built
    layers lack left out ("F" and "W" layers share the one ``block`` stack,
    in layer order). The router's choice bias starts at zero, as
    published."""
    from ray_tpu.ops.ssm import init_mamba2

    d, hd, f = cfg.dim, cfg.head_dim, cfg.mlp_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    held = cfg.num_experts
    wide = (cfg.router_experts or cfg.num_experts) + cfg.zero_experts
    n = {kind: cfg.kinds.count(kind) for kind in LAYER_KINDS}
    k = iter(jax.random.split(key, 12))
    dense = _dense_init
    out = {}
    if n["M"]:
        out["mamba"] = init_mamba2(
            next(k), n["M"], d, heads=cfg.ssm_heads,
            head_dim=cfg.ssm_head_dim, groups=cfg.ssm_groups,
            state=cfg.ssm_state, conv=cfg.ssm_conv, dt_min=cfg.ssm_dt_min,
            dt_max=cfg.ssm_dt_max, dt_floor=cfg.ssm_dt_floor)
    if n["E"]:
        L = n["E"]
        moe = {
            "norm": jnp.ones((L, d), jnp.float32),
            "router": dense(next(k), (L, d, wide), d),
            "router_bias": jnp.zeros((L, wide), jnp.float32),
            "w_up": dense(next(k), (L, held, d, f), d),
            "w_down": dense(next(k), (L, held, f, d), f),
        }
        if cfg.mlp_act != "relu2":
            moe["w_gate"] = dense(next(k), (L, held, d, f), d)
        if cfg.shared_mlp_dim:
            fs = cfg.shared_mlp_dim
            moe["shared_up"] = dense(next(k), (L, d, fs), d)
            moe["shared_down"] = dense(next(k), (L, fs, d), fs)
        out["moe"] = moe
    if n["*"]:
        L = n["*"]
        out["attn"] = {
            "norm": jnp.ones((L, d), jnp.float32),
            "wq": dense(next(k), (L, d, nq * hd), d),
            "wk": dense(next(k), (L, d, nkv * hd), d),
            "wv": dense(next(k), (L, d, nkv * hd), d),
            "wo": dense(next(k), (L, nq * hd, d), nq * hd),
        }
    if n["S"]:
        # keys of its own: the other kinds draw what they drew before
        k = iter(jax.random.split(jax.random.fold_in(key, 1), 12))
        L, fd = n["S"], cfg.dense_mlp_dim
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        kv = cfg.qk_nope_head_dim + cfg.v_head_dim
        ones = lambda width: jnp.ones((L, 2, width), jnp.float32)  # noqa: E731
        out["scmoe"] = {
            "attn_norm": ones(d), "mlp_norm": ones(d),
            # wq_b / wkv_b over sqrt(dim), not over sqrt(their rank): the
            # latent half multiplies their inputs by sqrt(dim / rank), the
            # published correction for matrices that all start at one
            # variance; with it q, k and v have unit variance. Over sqrt(rank)
            # scores are 6 wide and a softmax picks one key (4 layers then
            # turn bfloat16's rounding into half a logit's size on the chip)
            "wq_a": dense(next(k), (L, 2, d, rq), d), "q_norm": ones(rq),
            "wq_b": dense(next(k), (L, 2, rq, nq * qk), d),
            "wkv_a": dense(next(k), (L, 2, d, cfg.latent_row), d),
            "kv_norm": ones(rkv),
            "wkv_b": dense(next(k), (L, 2, rkv, nq * kv), d),
            "wo": dense(next(k), (L, 2, nq * cfg.v_head_dim, d),
                        nq * cfg.v_head_dim),
            "ffn_gate": dense(next(k), (L, 2, d, fd), d),
            "ffn_up": dense(next(k), (L, 2, d, fd), d),
            "ffn_down": dense(next(k), (L, 2, fd, d), fd),
            "router": dense(next(k), (L, d, wide), d),
            "router_bias": jnp.zeros((L, wide), jnp.float32),
            "w_gate": dense(next(k), (L, held, d, f), d),
            "w_up": dense(next(k), (L, held, d, f), d),
            "w_down": dense(next(k), (L, held, f, d), f),
        }
    L = n["F"] + n["W"]
    if L:  # keys of its own, as above
        k = iter(jax.random.split(jax.random.fold_in(key, 2), 8))
        out["block"] = {
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "mlp_norm": jnp.ones((L, d), jnp.float32),
            "wq": dense(next(k), (L, d, nq * hd), d),
            "wk": dense(next(k), (L, d, nkv * hd), d),
            "wv": dense(next(k), (L, d, nkv * hd), d),
            "wo": BLOCK_INIT["wo"] * dense(next(k), (L, nq * hd, d), nq * hd),
            "router": BLOCK_INIT["router"] * dense(next(k), (L, d, wide), d),
            "w_gate": dense(next(k), (L, held, d, f), d),
            "w_up": dense(next(k), (L, held, d, f), d),
            "w_down": dense(next(k), (L, held, f, d), f),
        }
    L = n["I"]
    if L:  # keys of its own, as above
        k = iter(jax.random.split(jax.random.fold_in(key, 3), 12))
        hi, di = cfg.index_heads, cfg.index_head_dim
        out["index"] = {
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "mlp_norm": jnp.ones((L, d), jnp.float32),
            "wq": dense(next(k), (L, d, nq * hd), d),
            "wk": dense(next(k), (L, d, nkv * hd), d),
            "wv": dense(next(k), (L, d, nkv * hd), d),
            "wo": INDEX_INIT["wo"] * dense(next(k), (L, nq * hd, d), nq * hd),
            "q_norm": jnp.full((L, hd), INDEX_INIT["q_norm"], jnp.float32),
            "k_norm": jnp.ones((L, hd), jnp.float32),
            "wqi": dense(next(k), (L, d, hi * di), d),
            "wki": dense(next(k), (L, d, di), d),
            "ww": dense(next(k), (L, d, hi), d),
            "ki_norm": jnp.ones((L, di), jnp.float32),
            "ki_bias": jnp.zeros((L, di), jnp.float32),
            "router": dense(next(k), (L, d, wide), d),
            "w_gate": dense(next(k), (L, held, d, f), d),
            "w_up": dense(next(k), (L, held, d, f), d),
            "w_down": dense(next(k), (L, held, f, d), f),
        }
    for c in DELTA_KINDS:
        if n[c]:
            out[LAYER_KINDS[c]] = _init_delta_family(cfg, c, n[c], key)
    for c in LATENT_KINDS:
        if n[c]:
            out[LAYER_KINDS[c]] = _init_latent_kind(cfg, c, n[c], key)
    if n["P"] + n["R"]:
        out["parallel"] = _init_parallel(cfg, n["P"] + n["R"], key)
    for c in HYBRID_KINDS:
        if n[c]:
            out[LAYER_KINDS[c]] = _init_hybrid(cfg, c, n[c], key)
    for c in DSA_KINDS:
        if n[c]:
            out[LAYER_KINDS[c]] = _init_dsa_kind(cfg, c, n[c], key)
    for c in "mwgc":  # "w" and "f": ONE stack
        L = n[c] + (n["f"] if c == "w" else 0)
        if L:
            out[LAYER_KINDS[c]] = _init_memory(cfg, c, L, key)
    return out


def _init_delta_family(cfg: LlamaConfig, kind: str, L: int, key):
    """The ``L`` stacked layers of kind ``"D"`` or ``"A"``, keys of their
    own a kind. Every norm gain is ZERO-CENTRED, ``1 + w``, and starts at
    ``w = 0`` (but the gated attention's query gain, :data:`DELTA_INIT`);
    the delta rule's output norm is plain and starts at one. ``A_log``
    ``log U(0.001, 16)`` (published: from 0); ``dt_bias``:
    :data:`DELTA_INIT`."""
    d, hd, f, fs = cfg.dim, cfg.head_dim, cfg.mlp_dim, cfg.shared_mlp_dim
    k = iter(jax.random.split(
        jax.random.fold_in(key, 4 + DELTA_KINDS.index(kind)), 16))
    dense = _dense_init
    out = {
        "attn_norm": jnp.zeros((L, d), jnp.float32),
        "mlp_norm": jnp.zeros((L, d), jnp.float32),
        "router": DELTA_INIT["router"] * dense(
            next(k), (L, d, cfg.router_experts or cfg.num_experts), d),
        "w_gate": dense(next(k), (L, cfg.num_experts, d, f), d),
        "w_up": dense(next(k), (L, cfg.num_experts, d, f), d),
        "w_down": dense(next(k), (L, cfg.num_experts, f, d), f),
    }
    if fs:
        out.update(w_sg=dense(next(k), (L, d), d),
                   ws_gate=dense(next(k), (L, d, fs), d),
                   ws_up=dense(next(k), (L, d, fs), d),
                   ws_down=dense(next(k), (L, fs, d), fs))
    if kind == "A":
        nq, nkv = cfg.n_heads, cfg.n_kv_heads
        out.update(
            wq=dense(next(k), (L, d, 2 * nq * hd), d),
            wk=dense(next(k), (L, d, nkv * hd), d),
            wv=dense(next(k), (L, d, nkv * hd), d),
            wo=DELTA_INIT["wo"] * dense(next(k), (L, nq * hd, d), nq * hd),
            q_norm=jnp.full((L, hd), DELTA_INIT["q_norm"] - 1.0, jnp.float32),
            k_norm=jnp.zeros((L, hd), jnp.float32))
        return out
    hv, kd = cfg.lin_value_heads, cfg.lin_key_heads * cfg.lin_key_dim
    vd = hv * cfg.lin_value_dim
    lo, hi = DELTA_INIT["dt"]
    dt = jnp.exp(jax.random.uniform(next(k), (L, hv), jnp.float32)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    out.update(
        w_qkvz=dense(next(k), (L, d, 2 * kd + 2 * vd), d),
        w_ba=dense(next(k), (L, d, 2 * hv), d),
        conv_w=dense(next(k), (L, cfg.lin_conv, 2 * kd + vd), cfg.lin_conv),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        A_log=jnp.log(jax.random.uniform(next(k), (L, hv), jnp.float32,
                                         1e-3, 16.0)),
        gate_norm=jnp.ones((L, cfg.lin_value_dim), jnp.float32),
        w_out=dense(next(k), (L, vd, d), vd))
    return out


def _init_latent_kind(cfg: LlamaConfig, kind: str, L: int, key):
    """The ``L`` stacked layers of kind ``"L"`` or ``"G"``, keys of their
    own a kind. Every matrix over the square root of its fan-in (``wq_b`` /
    ``wkv_b`` over ``sqrt(dim)`` where the config multiplies their inputs by
    ``sqrt(dim / rank)``, as the ``"S"`` layer's): q, k and v then have unit
    variance and a score is one unit wide. The choice bias starts at zero.
    ``cfg.seeded_scales`` (a configuration's data) multiplies ``wo`` and the
    routed experts' ``w_down``."""
    d, f, H = cfg.dim, cfg.mlp_dim, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kv = cfg.qk_nope_head_dim + cfg.v_head_dim
    k = iter(jax.random.split(
        jax.random.fold_in(key, 6 + LATENT_KINDS.index(kind)), 16))
    dense = _dense_init
    ones = lambda width: jnp.ones((L, width), jnp.float32)  # noqa: E731
    out = {
        "attn_norm": ones(d), "mlp_norm": ones(d),
        "wq_a": dense(next(k), (L, d, rq), d), "q_norm": ones(rq),
        "wq_b": dense(next(k), (L, rq, H * qk),
                      d if cfg.mla_scale_q_lora else rq),
        "wkv_a": dense(next(k), (L, d, cfg.latent_row), d),
        "kv_norm": ones(rkv),
        "wkv_b": dense(next(k), (L, rkv, H * kv),
                       d if cfg.mla_scale_kv_lora else rkv),
        "wo": dense(next(k), (L, H * cfg.v_head_dim, d), H * cfg.v_head_dim),
    }
    scales = dict(cfg.seeded_scales)  # the configuration's data, or none
    if "wo" in scales:
        out["wo"] = scales["wo"] * out["wo"]
    if cfg.hc_mult > 1:  # a key of their own: the leaves above stay theirs
        out.update(_init_hyper(cfg, L, jax.random.fold_in(
            key, 9 + LATENT_KINDS.index(kind))))
    if kind == "G":
        fd = cfg.dense_mlp_dim
        out.update(w_gate=dense(next(k), (L, d, fd), d),
                   w_up=dense(next(k), (L, d, fd), d),
                   w_down=dense(next(k), (L, fd, d), fd))
        return out
    held, wide = cfg.num_experts, cfg.router_experts or cfg.num_experts
    out.update(router=dense(next(k), (L, d, wide), d),
               router_bias=jnp.zeros((L, wide), jnp.float32),
               w_gate=dense(next(k), (L, held, d, f), d),
               w_up=dense(next(k), (L, held, d, f), d),
               w_down=dense(next(k), (L, held, f, d), f))
    if "expert_down" in scales:
        out["w_down"] = scales["expert_down"] * out["w_down"]
    if cfg.shared_mlp_dim:
        fs = cfg.shared_mlp_dim
        out.update(shared_gate=dense(next(k), (L, d, fs), d),
                   shared_up=dense(next(k), (L, d, fs), d),
                   shared_down=dense(next(k), (L, fs, d), fs))
    return out


def _init_dsa_kind(cfg: LlamaConfig, kind: str, L: int, key):
    """The ``L`` stacked layers of kind ``"Y"``, ``"Z"`` or ``"X"``, keys of
    their own a kind: the latent block's leaves (:func:`_init_latent_kind`,
    the routed one's or the dense one's, ``cfg.seeded_scales`` with them)
    and, for a FULL layer, the indexer's (:func:`_init_indexer`). A shared
    layer has none of them."""
    own = jax.random.fold_in(key, 14 + DSA_KINDS.index(kind))
    out = _init_latent_kind(cfg, "G" if kind == "X" else "L", L, own)
    if kind in DSA_FULL:
        out.update(_init_indexer(cfg, L, jax.random.fold_in(own, 1)))
    return out


def _init_indexer(cfg: LlamaConfig, L: int, key):
    """``L`` full layers' indexer leaves, over the square root of their
    fan-in as :data:`INDEX_INIT`'s reason has it: ``wqi`` reads the query's
    normed latent, ``wki`` and ``ww`` the normed stream; the LayerNorm at
    one and zero."""
    hi, di = cfg.index_heads, cfg.index_head_dim
    k = iter(jax.random.split(key, 3))
    return {
        "wqi": _dense_init(next(k), (L, cfg.q_lora_rank, hi * di),
                           cfg.q_lora_rank),
        "wki": _dense_init(next(k), (L, cfg.dim, di), cfg.dim),
        "ww": _dense_init(next(k), (L, cfg.dim, hi), cfg.dim),
        "ki_norm": jnp.ones((L, di), jnp.float32),
        "ki_bias": jnp.zeros((L, di), jnp.float32)}


def _init_mtp(cfg: LlamaConfig, key):
    """The prediction module's OWN leaves: the two norms in front of
    ``eh_proj`` ([2 dim, dim]: the embedding's half first), its ``"L"``
    blocks and its final norm. Embedding and head are the model's."""
    d = cfg.dim
    k_eh, k_block = jax.random.split(jax.random.fold_in(key, 8))
    return {"enorm": jnp.ones((d,), jnp.float32),
            "hnorm": jnp.ones((d,), jnp.float32),
            "eh_proj": _dense_init(k_eh, (2 * d, d), 2 * d),
            "layers": {"latent": _init_latent_kind(
                cfg, "L", cfg.mtp_layers, k_block)},
            "final_norm": jnp.ones((d,), jnp.float32)}


def _init_hyper(cfg: LlamaConfig, L: int, key):
    """A kind's ``L`` layers' hyper-connection leaves, ``[L, 2, ...]``: a
    sublayer each, the attention's then the MLP's (:data:`HC_INIT`)."""
    n, d = cfg.hc_mult, cfg.dim
    b = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                         HC_INIT["res_diag"] * jnp.eye(n).reshape(-1)])
    return {"hc_phi": _dense_init(key, (L, 2, n * d, 2 * n + n * n), n * d),
            "hc_b": jnp.broadcast_to(b, (L, 2, b.shape[0])),
            "hc_alpha": jnp.full((L, 2, 3), HC_INIT["alpha"], jnp.float32)}


def _init_parallel(cfg: LlamaConfig, L: int, key):
    """The ``L`` stacked parallel blocks, keys of their own. Every matrix
    over the square root of its fan-in but :data:`PARALLEL_INIT`'s; the norm
    at one. The ``shared_experts`` shared experts lie side by side: gate and
    up ``[dim, n * shared_mlp_dim]`` (expert ``j``: columns ``j * width ..``),
    down ``[n * shared_mlp_dim, dim]`` (its rows), each drawn over ONE
    expert's fan-in."""
    d, hd, f = cfg.dim, cfg.head_dim, cfg.mlp_dim
    nq, nkv, held = cfg.n_heads, cfg.n_kv_heads, cfg.num_experts
    fs, n = cfg.shared_mlp_dim, cfg.shared_experts
    k = iter(jax.random.split(jax.random.fold_in(key, 11), 12))
    dense = _dense_init
    return {
        "norm": jnp.ones((L, d), jnp.float32),
        "wq": PARALLEL_INIT["wq"] * dense(next(k), (L, d, nq * hd), d),
        "wk": dense(next(k), (L, d, nkv * hd), d),
        "wv": dense(next(k), (L, d, nkv * hd), d),
        "wo": PARALLEL_INIT["wo"] * dense(next(k), (L, nq * hd, d), nq * hd),
        "router": dense(next(k), (L, d, cfg.router_experts or held), d),
        "w_gate": dense(next(k), (L, held, d, f), d),
        "w_up": dense(next(k), (L, held, d, f), d),
        "w_down": dense(next(k), (L, held, f, d), f),
        "shared_gate": dense(next(k), (L, d, n * fs), d),
        "shared_up": dense(next(k), (L, d, n * fs), d),
        "shared_down": dense(next(k), (L, n * fs, d), fs),
    }


def _init_hybrid(cfg: LlamaConfig, kind: str, L: int, key):
    """The ``L`` stacked layers of kind ``"H"`` or ``"N"``, keys of their
    own a kind. ``"H"``: :func:`ray_tpu.ops.ssm.init_mamba2`'s leaves (its
    ``norm`` is the block's ``attn_norm``) with :data:`HYBRID_INIT`'s time
    steps, decay rates and convolution bias; ``"N"``: the block's attention,
    ``wq`` and ``wo`` at HYBRID_INIT's widths; both: the dense SwiGLU over
    the square root of its fan-in and the norms at one."""
    from ray_tpu.ops.ssm import init_mamba2

    d, hd, f = cfg.dim, cfg.head_dim, cfg.mlp_dim
    k = iter(jax.random.split(
        jax.random.fold_in(key, 12 + HYBRID_KINDS.index(kind)), 10))
    dense = _dense_init
    out = {"attn_norm": jnp.ones((L, d), jnp.float32),
           "mlp_norm": jnp.ones((L, d), jnp.float32),
           "w_gate": dense(next(k), (L, d, f), d),
           "w_up": dense(next(k), (L, d, f), d),
           "w_down": dense(next(k), (L, f, d), f)}
    if kind == "N":
        nq, nkv = cfg.n_heads, cfg.n_kv_heads
        out.update(
            wq=HYBRID_INIT["wq"] * dense(next(k), (L, d, nq * hd), d),
            wk=dense(next(k), (L, d, nkv * hd), d),
            wv=dense(next(k), (L, d, nkv * hd), d),
            wo=HYBRID_INIT["wo"] * dense(next(k), (L, nq * hd, d), nq * hd))
        return out
    (dt_lo, dt_hi), (a_lo, a_hi) = HYBRID_INIT["dt"], HYBRID_INIT["A"]
    mixer = init_mamba2(
        next(k), L, d, heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
        groups=cfg.ssm_groups, state=cfg.ssm_state, conv=cfg.ssm_conv,
        dt_min=dt_lo, dt_max=dt_hi, dt_floor=cfg.ssm_dt_floor)
    del mixer["norm"]
    mixer["A_log"] = jnp.log(jax.random.uniform(
        next(k), mixer["A_log"].shape, jnp.float32, a_lo, a_hi))
    mixer["conv_b"] = HYBRID_INIT["conv_b"] * jax.random.normal(
        next(k), mixer["conv_b"].shape, jnp.float32)
    return {**out, **mixer}


def _init_memory(cfg: LlamaConfig, kind: str, L: int, key):
    """The ``L`` stacked layers of one of the decoder-hybrid-decoder's
    stacks (``kind``: ``"m"``, ``"w"`` for the ``"w"`` / ``"f"`` layers in
    layer order, ``"g"``, ``"c"``), keys of their own a stack: the mixer's
    leaves at :data:`MEMORY_INIT`'s widths (``"m"``: :func:`ray_tpu.ops.s6.
    init_s6`), the dense SwiGLU over the square root of its fan-in, the
    LayerNorms' gains at one and their biases at zero. ``wq``'s columns are
    ``[q1 | q2]`` (a pair's first and second head, n_heads / 2 pairs of
    head_dim each), ``wkv``'s ``[k1 | k2 | v]`` (n_kv_heads / 2 pairs; a
    pair's value ONE head 2 head_dim wide): with seeded weights any consistent
    pairing is one model."""
    from ray_tpu.ops.s6 import init_s6

    d, hd, f = cfg.dim, cfg.head_dim, cfg.mlp_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    k = iter(jax.random.split(
        jax.random.fold_in(key, 20 + "mwgc".index(kind)), 16))
    dense, init = _dense_init, MEMORY_INIT
    out = {"attn_norm": jnp.ones((L, d), jnp.float32),
           "attn_norm_b": jnp.zeros((L, d), jnp.float32),
           "mlp_norm": jnp.ones((L, d), jnp.float32),
           "mlp_norm_b": jnp.zeros((L, d), jnp.float32),
           "w_gate": dense(next(k), (L, d, f), d),
           "w_up": dense(next(k), (L, d, f), d),
           "w_down": dense(next(k), (L, f, d), f)}
    if kind == "m":
        mixer = init_s6(
            next(k), L, d, inner=cfg.s6_inner, state=cfg.ssm_state,
            conv=cfg.ssm_conv, dt_rank=cfg.s6_dt_rank, dt=init["dt"],
            decay=init["A"], conv_b=init["conv_b"])
        return {**out, **mixer, "w_out": init["out"] * mixer["w_out"]}
    if kind == "g":
        return {**out, "wg_in": dense(next(k), (L, d, cfg.s6_inner), d),
                "wg_out": init["out"] * dense(
                    next(k), (L, cfg.s6_inner, d), cfg.s6_inner)}

    def normal(shape, width):
        return width * jax.random.normal(next(k), shape, jnp.float32)

    # a window layer's queries and a full or cross layer's: a width each
    wide = jnp.asarray([init["wq" if c == "w" else "wq_full"]
                        for c in cfg.kinds if c in ("wf" if kind == "w"
                                                    else "c")])
    out.update(
        wq=wide[:, None, None] * dense(next(k), (L, d, nq), d),
        bq=normal((L, nq), init["bias"]),
        wo=init["wo"] * dense(next(k), (L, nq, d), nq),
        bo=normal((L, d), init["bias"]),
        lam=normal((L, 4, hd), init["lam"]),
        sub_norm=jnp.ones((L, 2 * hd), jnp.float32))
    if kind == "w":
        out.update(wkv=dense(next(k), (L, d, 2 * nkv), d),
                   bkv=normal((L, 2 * nkv), init["bias"]))
    return out


def init_params(cfg: LlamaConfig, key) -> Dict[str, Any]:
    d, hd = cfg.dim, cfg.head_dim
    nq, nkv, L = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    dense = _dense_init
    if cfg.layer_pattern:
        k_emb, k_head, k_layers = jax.random.split(key, 3)
        wide = (HYBRID_INIT["embedding"]
                if set(cfg.kinds) & set(HYBRID_KINDS)
                else MEMORY_INIT["embedding"] * math.sqrt(d)
                if set(cfg.kinds) & set(MEMORY_KINDS) else 1.0)
        params = {"embedding": wide * dense(k_emb, (cfg.vocab_size, d), d),
                  "layers": _init_pattern_layers(cfg, k_layers),
                  "final_norm": (jnp.zeros if cfg.zero_centered
                                 else jnp.ones)((d,), jnp.float32)}
        if set(cfg.kinds) & set(MEMORY_KINDS):  # LayerNorm WITH a bias
            params["final_norm_b"] = jnp.zeros((d,), jnp.float32)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(k_head, (d, cfg.vocab_size), d)
        if cfg.mtp_layers:
            params["mtp"] = _init_mtp(cfg, key)
        return params
    k = iter(jax.random.split(key, 16))

    # a routed config's MLP weights carry an expert dimension after the
    # scan's; they are drawn from the keys the dense ones are drawn from
    experts = (cfg.num_experts,) if cfg.num_experts else ()
    f = cfg.mlp_dim
    params = {
        "embedding": dense(next(k), (cfg.vocab_size, d), d),
        "layers": {
            "wq": dense(next(k), (L, d, nq * hd), d),
            "wk": dense(next(k), (L, d, nkv * hd), d),
            "wv": dense(next(k), (L, d, nkv * hd), d),
            "wo": dense(next(k), (L, nq * hd, d), nq * hd),
            "w_gate": dense(next(k), (L, *experts, d, f), d),
            "w_up": dense(next(k), (L, *experts, d, f), d),
            "w_down": dense(next(k), (L, *experts, f, d), f),
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "mlp_norm": jnp.ones((L, d), jnp.float32),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), (d, cfg.vocab_size), d)
    # what a dense config lacks is drawn last, so that a dense config's
    # parameters are what they were for the same key
    layers = params["layers"]
    if cfg.num_experts:
        layers["router"] = dense(next(k), (L, d, cfg.num_experts), d)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, nq * hd), jnp.float32)
        layers["k_norm"] = jnp.ones((L, nkv * hd), jnp.float32)
    return params


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #


def _shard_mapped(fn, mesh, seq_axis):
    """Wrap an attention body in shard_map: batch over data/fsdp, heads
    over tensor, seq over ``seq_axis`` (None = unsharded)."""
    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in ("slice", "data", "fsdp")
                       if a in mesh.axis_names)
    ha = "tensor" if "tensor" in mesh.axis_names else None
    spec = P(batch_axes if batch_axes else None, seq_axis, ha, None)
    from ray_tpu.util.jax_compat import shard_map

    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check=False)


def _attention(cfg: LlamaConfig, q, k, v, mesh):
    """Dispatch: ring attention when the mesh shards sequence, else the
    Pallas flash kernel (GQA-aware, no [B,H,T,T] materialization) on TPU,
    else plain XLA attention."""
    B, T, H, D = q.shape
    if mesh is not None and "seq" in mesh.axis_names and mesh.shape["seq"] > 1:
        # ring path takes pre-repeated kv heads
        rep = cfg.n_heads // cfg.n_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        fn = _shard_mapped(
            partial(ring_attention_local, axis_name="seq", causal=True),
            mesh, "seq")
        return fn(q, k, v)
    from ray_tpu.ops.flash_attention import flash_attention

    if mesh is not None and mesh.size > 1:
        # pallas_call does not auto-partition under GSPMD: run the kernel
        # per-shard via shard_map (seq unsharded on this path)
        fn = _shard_mapped(partial(flash_attention, causal=True), mesh, None)
        return fn(q, k, v)
    return flash_attention(q, k, v, causal=True)


def _qkv(cfg: LlamaConfig, p, h, n_q: int, n_kv: int, positions,
         rope: Optional[bool] = None):
    """The attention half's inputs: ``h`` [B, T, dim] (normed, cfg.dtype)
    through wq / wk / wv, QK-norm where the config has it (RMSNorm over the
    whole projected vector, before the split into heads), heads split,
    RoPE on q and k (unless the config attends without rotation; ``rope``:
    a layer kind's own answer in place of the config's). ``n_q`` /
    ``n_kv``: the heads THESE weights hold."""
    cd, hd = cfg.dtype, cfg.head_dim
    B, T, _ = h.shape

    def project(w, heads, norm=None):
        y = h @ p[w].astype(cd)
        if norm is not None:
            y = rms_norm(y, p[norm], cfg.norm_eps)
        return y.reshape(B, T, heads, hd)

    q = project("wq", n_q, "q_norm" if cfg.qk_norm else None)
    kk = project("wk", n_kv, "k_norm" if cfg.qk_norm else None)
    vv = project("wv", n_kv)
    if cfg.rope if rope is None else rope:
        if cfg.rope_interleaved:
            return (*(rotate_pairs(a, positions, cfg.rope_theta)
                      for a in (q, kk)), vv)
        q, kk = rotary_embedding(q, kk, positions, cfg.rope_theta)
    return q, kk, vv


def _mlp_half(cfg: LlamaConfig, p, h, stat_axes=(), layer=None,
              router_in=None):
    """The MLP half on ``h`` [B, T, dim] (normed, cfg.dtype), before the
    residual add (and before a row-parallel caller's psum): the dense
    SwiGLU, or, where the config has experts, the dropless routed one
    (:func:`ray_tpu.ops.moe.routed_mlp`). Returns ``(y, stats)``; ``stats``
    is ``{}`` for a dense layer and the router's scalars for a routed one.
    ``stat_axes``, ``layer`` for experts that come as their kind's whole
    stack, and ``router_in`` for a router that reads something else than its
    experts do: see ``routed_mlp``."""
    cd = cfg.dtype
    if cfg.num_experts:
        from ray_tpu.ops.moe import routed_mlp

        wide = cfg.router_experts or cfg.num_experts
        y, stats = routed_mlp(
            h, p["router"], p.get("w_gate"), p["w_up"], p["w_down"],
            top_k=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
            stat_axes=stat_axes, scoring=cfg.router_scoring,
            choice_bias=p.get("router_bias"), scale=cfg.routed_scale,
            held=((cfg.first_expert, cfg.num_experts)
                  if wide != cfg.num_experts else None),
            # the shared expert has its experts' form: two matrices, or a
            # SwiGLU's three with no gate in front of it
            shared=((p["shared_up"], p["shared_down"])
                    if cfg.shared_mlp_dim and "shared_gate" not in p
                    else None),
            shared_gated=((None, p["shared_gate"], p["shared_up"],
                           p["shared_down"]) if "shared_gate" in p else None),
            zero_experts=cfg.zero_experts, layer=layer,
            router_input=router_in, act=cfg.mlp_act)
        return y.astype(cd), stats
    return _dense_mlp(cfg, p, h), {}


def _dense_mlp(cfg: LlamaConfig, p, h):
    """The dense SwiGLU on ``h`` [B, T, dim] (normed, cfg.dtype)."""
    cd = cfg.dtype
    # the two products may be kept (KEEP_GROUPS); silu is made again
    g = jax.nn.silu(checkpoint_name(h @ p["w_gate"].astype(cd), "mlp"))
    u = checkpoint_name(h @ p["w_up"].astype(cd), "mlp")
    return (g * u) @ p["w_down"].astype(cd)


def add_router_losses(cfg: LlamaConfig, nll, stats):
    """``stats``: what the layer scan stacked, ``[L]`` a leaf (``{}`` for a
    dense model). Returns ``(total, report)``: the cross-entropy plus the
    weighted router losses, each averaged over layers, and the scalars a
    step reports (``{}`` for a dense model, whose total is ``nll``)."""
    if not stats:
        return nll, {}
    report = {}
    if "lb_loss" in stats:  # a sigmoid router has no router loss
        report = {"lb_loss": stats["lb_loss"].mean(),
                  "z_loss": stats["z_loss"].mean()}
    report.update(max_load_ratio=stats["max_load_ratio"].max(),
                  dropped=stats["dropped"].sum())
    for share in ("held_share", "held_chunks", "zero_share"):
        if share in stats:  # the layers hold a range; have identity experts
            report[share] = stats[share].mean()
    if "lb_loss" not in stats:
        return nll, report
    total = (nll + cfg.lb_loss_coef * report["lb_loss"]
             + cfg.z_loss_coef * report["z_loss"])
    return total, report


def positions_of(batch: int, seq: int):
    """``[batch, seq]`` int32: every row counts 0 .. seq - 1."""
    return jnp.arange(seq, dtype=jnp.int32)[None, :].repeat(batch, axis=0)


def decoder_block(cfg: LlamaConfig, x, p, positions, attend, *,
                  col_in=None, row_out=None, stat_axes=()):
    """THE decoder block, for the train steps and for the serving programs:
    ``attn_norm`` -> q / k / v (:func:`_qkv`) -> RoPE -> ``attend`` -> ``wo``
    -> residual -> ``mlp_norm`` -> MLP (:func:`_mlp_half`) -> residual.

    ``x``: the residual stream ``[B, T, dim]``; ``p``: ONE layer's weights,
    whole or a tensor-parallel shard (the head counts are read off ``wq`` /
    ``wk``, which on whole weights are the config's); ``attend(q, k, v)``:
    what the caller attends over, ``[B, T, n_q, head_dim]`` back, with
    ``k`` / ``v`` rotated and not yet repeated for GQA. ``col_in`` /
    ``row_out``: a manual tensor-parallel caller's collectives where the
    normed stream enters the column-parallel products and where the
    row-parallel products (``wo``, the MLP's last) leave them; None where
    nothing is split. ``stat_axes``: see :func:`_mlp_half`.

    Returns ``(x, stats, (k, v))``: the residual stream, the MLP half's
    stats, and this call's rotated keys and values, which a cache writer
    keeps and a train step drops."""
    same = lambda a: a  # noqa: E731
    col_in, row_out = col_in or same, row_out or same
    cd = cfg.dtype
    h = col_in(rms_norm(x, p["attn_norm"], cfg.norm_eps).astype(cd))
    y, k, v = _attn_half(cfg, p, h, positions, attend)
    x = x + row_out(y).astype(x.dtype)
    h = col_in(rms_norm(x, p["mlp_norm"], cfg.norm_eps).astype(cd))
    y, stats = _mlp_half(cfg, p, h, stat_axes)
    return x + row_out(y).astype(x.dtype), stats, (k, v)


def _attn_half(cfg: LlamaConfig, p, h, positions, attend, rope=None):
    """The attention half on ``h`` [B, T, dim] (normed, cfg.dtype), before
    the residual add: q / k / v (:func:`_qkv`, which takes ``rope``),
    ``attend``, ``wo``. The head counts are read off ``wq`` / ``wk``.
    Returns ``(y, k, v)``."""
    hd = cfg.head_dim
    B, T, _ = h.shape
    nq, nkv = p["wq"].shape[-1] // hd, p["wk"].shape[-1] // hd
    q, k, v = (checkpoint_name(a, "attn")
               for a in _qkv(cfg, p, h, nq, nkv, positions, rope))
    attn = attend(q, k, v).reshape(B, T, nq * hd)
    return checkpoint_name(attn @ p["wo"].astype(cfg.dtype), "attn"), k, v


# prefill's attention goes a block of queries against a block of keys at a
# time. In the XLA tile loop (every backend but the TPU) the float32 scores
# alive are [heads, this, this] (67 MB at 64 heads), where [heads, T, T] would
# be 17 GB at 8,192 positions; on the TPU one head's [this, this] stay in
# VMEM inside the kernel (ops/flash_prefill.py picks its own block, this one
# where it divides T)
LATENT_QUERY_BLOCK = 512


def _latent_half(cfg: LlamaConfig, p, h, positions, attend):
    """The latent attention half (``_attn_half``'s counterpart) on ``h``
    [B, T, dim] (normed, cfg.dtype), before the residual add. ``p``: ONE
    sublayer's ``wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo``.

    ``q = RMSNorm(h wq_a) wq_b`` times ``sqrt(dim / q_lora_rank)``, a head
    ``[nope | rope]``; ``h wkv_a = [c | kr]``, ``c = RMSNorm(c) *
    sqrt(dim / kv_lora_rank)`` (each scale where the config sets it,
    ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: the first published model
    of this kind sets both, the second neither); RoPE over pairs ``(2i,
    2i + 1)`` of ``q``'s rope slice and of ``kr``, the ONE rotated key slice
    all heads share.
    ``attend(q, latent, wkv_b)`` -> ``[B, T, heads, v_head_dim]`` decides
    whether ``c wkv_b = [k_nope | v]`` a head is ever made (prefill) or
    absorbed into the query and the output (decode). Under ``cfg.rope_yarn``
    the rope slice turns at :func:`yarn_frequencies` and the scores' factor
    (:func:`yarn_softmax_factor`) rides on the query's gain, so that every
    ``attend`` keeps its ``1 / sqrt(score width)``. Returns ``(y,
    latent)``: ``latent`` ``[B, T, kv_lora_rank + qk_rope_head_dim]`` is
    ``[c | kr]``, normed, scaled and rotated: what a cache keeps."""
    cd = cfg.dtype
    q, latent, _ = _latent_project(cfg, p, h, positions)
    with jax.named_scope("mla.attend"):
        o = attend(q, latent, p["wkv_b"].astype(cd))
    with jax.named_scope("mla.out"):
        y = o.reshape(*h.shape[:2], -1) @ p["wo"].astype(cd)
    return y, latent


def _latent_project(cfg: LlamaConfig, p, h, positions):
    """:func:`_latent_half`'s projections (scope ``mla.project``): ``(q,
    latent, cq)``, the rotated query ``[B, T, heads, nope + rope]``, the
    row a cache keeps and ``cq`` ``[B, T, q_lora_rank]``, the query's normed
    latent (what an indexer's query reads, :func:`dsa_block`)."""
    cd, d, eps = cfg.dtype, cfg.dim, cfg.norm_eps
    B, T, _ = h.shape
    H, r, dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    with jax.named_scope("mla.project"):
        def gain(w, scaled, rank):
            # a norm's weight times the scale: one rounding, not two
            return w * math.sqrt(d / rank) if scaled else w

        qa = h @ p["wq_a"].astype(cd)
        q_gain = gain(p["q_norm"], cfg.mla_scale_q_lora, cfg.q_lora_rank)
        turn = {}
        if cfg.rope_yarn:  # the scores' factor rides on q's gain, as above
            q_gain = q_gain * yarn_softmax_factor(cfg)
            turn["inv_freq"] = yarn_frequencies(cfg)
        cq = rms_norm(qa, q_gain, eps)
        q = (cq @ p["wq_b"].astype(cd)).reshape(B, T, H, -1)
        ckr = h @ p["wkv_a"].astype(cd)
        c = rms_norm(ckr[..., :r], gain(
            p["kv_norm"], cfg.mla_scale_kv_lora, r), eps)
        q_rope, kr = rotary_embedding(
            q[..., dn:], ckr[:, :, None, r:], positions, cfg.rope_theta,
            interleaved=True, **turn)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        latent = jnp.concatenate([c, kr[:, :, 0]], axis=-1)
    return q, latent, cq


def attend_latent_expanded(cfg: LlamaConfig, q, latent, wkv_b):
    """``_latent_half``'s ``attend`` over the call's own positions, causal
    (the full forward and prefill): every position's per-head ``[k_nope |
    v]`` is made, ``c wkv_b``, and attended with :func:`attend_tiles`. The
    ONE rotated key slice all heads share goes down as it is (``shared``):
    the flash kernel scores it as a second operand, and only the tile loop
    repeats it for every head."""
    B, T, H, _ = q.shape
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = (latent[..., :r] @ wkv_b).reshape(B, T, H, -1)
    return attend_tiles(q, kv[..., :dn], kv[..., dn:], cfg.dtype,
                        shared=latent[..., r:])


# --------------------------------------------------------------------------- #
# Latent blocks outside "S" (kinds "L" and "G"; TRAINED kinds, and SERVED)
# and the stream as hc_mult rows a token (manifold-constrained
# hyper-connections)
# --------------------------------------------------------------------------- #


def attend_latent_heads(cfg: LlamaConfig, attend, q, latent, wkv_b):
    """``_latent_half``'s ``attend`` for a TRAIN step: every position's
    per-head ``[k_nope | v]`` is made, ``c wkv_b``, the ONE rotated key
    slice is repeated for every head, and ``attend(q, k, v)`` (the caller's:
    the flash kernel, forward AND backward) sees plain attention of
    ``n_heads`` on ``n_heads`` at the score's width, which is the value's.
    Not :func:`attend_latent_expanded`: prefill's kernel takes the shared
    slice as an operand of its own, but it is forward only, and its
    transpose is the XLA tile loop. The repeat costs ``qk_rope_head_dim`` of
    ``head width`` more key bytes, and buys the kernels' backward."""
    B, T, H, _ = q.shape
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = (latent[..., :r] @ wkv_b).reshape(B, T, H, -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        latent[:, :, None, r:], (B, T, H, cfg.qk_rope_head_dim))], axis=-1)
    q, k, v = (checkpoint_name(a, "attn") for a in (q, k, kv[..., dn:]))
    return attend(q, k, v)


def widen_stream(cfg: LlamaConfig, x, apart=False):
    """The embedding ``[B, T, dim]`` as the stream's first state: every one
    of the ``hc_mult`` rows a token is a copy of it, side by side ``[B, T,
    hc_mult * dim]`` (row ``j`` is ``[..., j * dim:(j + 1) * dim]``, whole
    lanes; a train step and the full forward keep reading ``x.shape[:2]``
    and slicing positions as they did. The rows as a dimension of their own
    cost the same on the chip, 12.6 against 12.3 ms a sublayer's passes at
    16,384 positions: the compiler lays either out positions-minor;
    ``sweep/xing4_check.md``), or ``apart`` as a TUPLE of ``hc_mult`` rows
    ``[B, T, dim]``, which is how the serving programs carry it: rows side
    by side are put together by every sublayer's write-back and cut apart by
    the next, a pass over 0.94 GB each at 16,384 positions and, across a
    layer scan's carry, a copy more (PERF.md, PR 61). ``hc_mult`` 1: ``x``
    as it is."""
    if cfg.hc_mult == 1:
        return x
    return (x,) * cfg.hc_mult if apart else jnp.tile(x, (1, 1, cfg.hc_mult))


def collapse_stream(cfg: LlamaConfig, x):
    """The stream's end, in either of :func:`widen_stream`'s forms: its rows
    SUMMED (float32) to ``[B, T, dim]``, what the final norm reads.
    ``hc_mult`` 1: ``x`` as it is."""
    if cfg.hc_mult == 1:
        return x
    with jax.named_scope("hc.sum"):
        if isinstance(x, tuple):
            return sum(a.astype(jnp.float32) for a in x).astype(x[0].dtype)
        rows = x.reshape(*x.shape[:2], cfg.hc_mult, -1)
        return rows.astype(jnp.float32).sum(axis=2).astype(x.dtype)


def hyper_mix(cfg: LlamaConfig, phi, b, alpha, x):
    """One sublayer's three mixes from the stream ``x`` [B, T, n * dim] (or
    its ``n`` rows ``[B, T, dim]``, a tuple: the product and the mean square
    are then summed over the rows), all float32 whatever the stream's type,
    the positions LAST (lanes: twenty normalisations of ``[n, n]`` minor
    would run on padded tiles)::

        m      = (x~ phi) / sqrt(mean(x~ ** 2) + eps)        x~ = vec(X)
        H_pre  = sigmoid(alpha_0 m[:n] + b[:n])              [n, B, T]
        H_post = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n])        [n, B, T]
        H_res  = Sinkhorn(exp(clip(alpha_2 m[2n:] + b[2n:])))  [n, n, B, T]

    ``phi`` [n * dim, 2n + n * n] carries the norm's gain; Sinkhorn:
    ``hc_sinkhorn_iters`` times every row over its sum, then every column
    over its sum, ``+ eps`` in each divisor. Returns ``(H_pre, H_post, H_res,
    error)``: ``error`` the largest ``|row sum - 1|`` or ``|column sum - 1|``
    of ``H_res``."""
    f32, n, eps = jnp.float32, cfg.hc_mult, cfg.hc_eps

    def product(x32, phi):
        return jnp.einsum("btc,cm->mbt", x32, phi,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=f32)

    if isinstance(x, tuple):
        rows = [a.astype(f32) for a in x]
        m = sum(map(product, rows, jnp.split(phi, n)))
        square = sum((a * a).sum(axis=-1) for a in rows) / phi.shape[0]
    else:
        x32 = x.astype(f32)
        m, square = product(x32, phi), jnp.mean(x32 * x32, axis=-1)
    m = m * jax.lax.rsqrt(square + eps)
    b = b[:, None, None]
    pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(alpha[2] * m[2 * n:] + b[2 * n:],
                           cfg.hc_res_clamp_min, cfg.hc_res_clamp_max))
    res = res.reshape(n, n, *m.shape[1:])  # [i, j]: onto row i from row j
    for _ in range(cfg.hc_sinkhorn_iters):
        res = res / (res.sum(axis=1, keepdims=True) + eps)
        res = res / (res.sum(axis=0, keepdims=True) + eps)
    error = jnp.maximum(jnp.abs(res.sum(axis=1) - 1.0).max(),
                        jnp.abs(res.sum(axis=0) - 1.0).max())
    return pre, post, res, error


def hyper_connected(cfg: LlamaConfig, hc, x, sublayer):
    """ONE sublayer on the stream, for every kind that could take it:
    ``sublayer(h) -> (y, aux)`` reads ``h`` [B, T, dim] (its own norm is
    its own) and returns what it adds. ``hc`` None (``hc_mult`` 1)::

        x' = x + y                         the plain residual block

    ``hc = (phi, b, alpha)``, this sublayer's, on ``x`` in either of
    :func:`widen_stream`'s forms, [B, T, n * dim] or the ``n`` rows apart
    (the mixes: :func:`hyper_mix`, scope ``hc.mix``)::

        h     = sum_j H_pre[j] X[j]                          ``hc.read``
        X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y         ``hc.write``

    in float32, stored in the stream's type and form. Returns ``(x', aux,
    error)``, ``error`` :func:`hyper_mix`'s (None without ``hc``)."""
    if hc is None:
        y, aux = sublayer(x)
        return x + y.astype(x.dtype), aux, None
    f32, n = jnp.float32, cfg.hc_mult
    apart = isinstance(x, tuple)
    with jax.named_scope("hc.mix"):
        pre, post, res, error = hyper_mix(cfg, *hc, x)
    x = x if apart else jnp.split(x, n, axis=-1)
    dtype, rows = x[0].dtype, [a.astype(f32) for a in x]
    with jax.named_scope("hc.read"):
        h = sum(pre[j][..., None] * rows[j] for j in range(n))
    y, aux = sublayer(h.astype(dtype))
    with jax.named_scope("hc.write"):
        y = y.astype(f32)
        out = tuple(sum(res[i, j][..., None] * rows[j] for j in range(n))
                    + post[i][..., None] * y for i in range(n))
        if not apart:
            out = jnp.concatenate(out, axis=-1)
    return jax.tree.map(lambda a: a.astype(dtype), out), aux, error


def _latent_sublayers(cfg: LlamaConfig, kind: str, attend, x, p, positions,
                      stat_axes, layer):
    """The latent block's two sublayers (:func:`latent_block` has the
    equations), each through :func:`hyper_connected`. ``attend(q, latent,
    wkv_b)`` as :func:`_latent_half` takes it; ``p``: ONE layer's leaves,
    its experts that layer's (``layer`` None) or the kind's whole stack with
    ``layer`` its number (``routed_mlp``). Returns ``(x, stats, latent)``:
    ``latent`` ``[B, T, latent_row]``, what a cache keeps; ``stats`` with
    ``hc_sinkhorn_error`` where the stream is mixed."""
    cd, eps = cfg.dtype, cfg.norm_eps
    hc = [None, None] if cfg.hc_mult == 1 else [
        (p["hc_phi"][j], p["hc_b"][j], p["hc_alpha"][j]) for j in (0, 1)]

    def mla(h):
        y, latent = _latent_half(
            cfg, p, rms_norm(h, p["attn_norm"], eps).astype(cd), positions,
            attend)
        return checkpoint_name(y, "attn"), latent

    def mlp(h):
        h = rms_norm(h, p["mlp_norm"], eps).astype(cd)
        if kind == "G":
            with jax.named_scope("ffn.dense"):
                return _dense_mlp(cfg, p, h), {}
        return _mlp_half(cfg, p, h, stat_axes, layer=layer)

    x, latent, first = hyper_connected(cfg, hc[0], x, mla)
    x, stats, second = hyper_connected(cfg, hc[1], x, mlp)
    if first is not None:
        stats = {**stats, "hc_sinkhorn_error": jnp.maximum(first, second)}
    return x, stats, latent


def latent_block(cfg: LlamaConfig, kind: str, attend, x, p, stat_axes=()):
    """THE latent block (kinds ``"L"`` and ``"G"``), for the train steps and
    the full forward (the serving programs': :func:`serve_latent_block`, over
    the same :func:`_latent_sublayers`)::

        a   = x + MLA(N(x))          (:func:`_latent_half`, no sqrt(dim / rank)
                                      factor unless the config sets it)
        out = a + MLP(N(a))          "G": the dense SwiGLU (dense_mlp_dim,
                                     scope ``ffn.dense``); "L": the routed MLP
                                     (:func:`_mlp_half`: sigmoid or softmax
                                     scores, the choice bias, a held range)
                                     with its ungated shared SwiGLU expert

    each ``+`` a hyper-connection where ``cfg.hc_mult > 1``
    (:func:`hyper_connected`; ``x`` is then ``[B, T, hc_mult * dim]``).
    ``attend(q, k, v)`` as :func:`decoder_block` takes it: the block makes
    per-head keys and values itself (:func:`attend_latent_heads`), so the
    trainer's flash kernel runs forward, dQ and dK/dV on them; where the
    score's width is not the value's (no train step takes that) the full
    forward attends as prefill does (:func:`attend_latent_expanded`). ``p``:
    this layer's weights. Returns ``(x, stats)``."""
    one_width = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                 == cfg.v_head_dim)
    x, stats, _ = _latent_sublayers(
        cfg, kind, partial(attend_latent_heads, cfg, attend) if one_width
        else partial(attend_latent_expanded, cfg), x, p,
        positions_of(*x.shape[:2]), stat_axes, None)
    # a dense layer's stats stay {}: pattern_stack stacks the routed ones'
    return x, {k: v for k, v in stats.items() if k != "hc_sinkhorn_error"}


def serve_latent_block(cfg: LlamaConfig, kind: str, x, layers, i, positions,
                       attend, stat_axes=()):
    """:data:`SERVED`'s block of the kinds ``"L"`` and ``"G"``:
    :func:`latent_block`'s arithmetic on the kind's stacked weights
    ``layers`` ``[n, ...]``, layer ``i``, over ``attend(q, latent, wkv_b)``
    (prefill: :func:`attend_latent_expanded`; decode:
    :func:`_attend_latent_cached`). Every leaf is cut out ``[i]`` but the
    routed experts, which go down whole with ``layer=i``
    (:func:`shortcut_layer` says why). Returns ``(x, stats, latent)``:
    the layer's ``[c | k_r]`` rows ``[B, T, latent_row]``."""
    whole = () if kind == "G" else ("w_gate", "w_up", "w_down")
    p = {w: a if w in whole else a[i] for w, a in layers.items()}
    return _latent_sublayers(cfg, kind, attend, x, p, positions, stat_axes,
                             None if kind == "G" else i)


# every distinct prefill attention traced in this process and the way it
# went: (kind, shapes, window, path) -> {.., "reason", "calls"}; programs are
# traced on whatever thread first calls them (one lock for this record and
# the experts' products' below)
_paths_lock = threading.Lock()
_prefill_attend_taken: Dict[tuple, dict] = {}


def prefill_attend_path(q, k, v, cd, shared=None) -> Tuple[str, str]:
    """``(path, reason)`` :func:`attend_tiles` takes for these operands in
    this process: ``"kernel"`` on a TPU backend for what
    ``ops/flash_prefill.py`` takes (positions a multiple of one of its
    blocks, operands all in ``cd``, key and value widths whole lanes),
    ``"tiles"`` with what stands in the way otherwise. Read from the
    backend and the shapes alone."""
    platform = jax.default_backend()
    if platform != "tpu":
        return "tiles", f"backend is {platform!r}, not tpu"
    from ray_tpu.ops.flash_prefill import pick_blocks

    if pick_blocks(q.shape[1]) is None:
        return "tiles", (f"{q.shape[1]} positions are no multiple of a "
                         "block of the kernel's")
    types = {a.dtype.name for a in (q, k, v, shared) if a is not None}
    if types != {jnp.dtype(cd).name}:
        return "tiles", (f"operands in {sorted(types)}, products in "
                         f"{jnp.dtype(cd).name}")
    if k.shape[-1] % 128 or v.shape[-1] % 128:
        return "tiles", (f"key width {k.shape[-1]} or value width "
                         f"{v.shape[-1]} is no multiple of 128 lanes")
    return "kernel", "tpu backend"


def prefill_attend_paths() -> list:
    """Every distinct prefill attention (kind, shapes, window, path) traced
    in this process, with its reason and how often: how a run proves which
    attention its prefill programs hold (the trainer's kernels:
    ``ops.flash_attention.paths_taken``)."""
    with _paths_lock:
        return [dict(rec) for rec in _prefill_attend_taken.values()]


def _note_prefill_attend(kind, q, k, window, path, reason, xla="tiles",
                         step=None):
    """``step``: what a grid step of the kind's kernel holds, where the
    kernel chooses it from the shapes (``ops/sparse_prefill.py
    flash_step``)."""
    key = (kind, q.shape, k.shape, window, path)
    with _paths_lock:
        rec = _prefill_attend_taken.setdefault(key, {
            "kind": kind, "q_shape": list(q.shape), "k_shape": list(k.shape),
            "window": window, "path": path, "reason": reason, "calls": 0,
            **(step or {})})
        rec["calls"] += 1
        counts = {way: sum(r["calls"] for r in _prefill_attend_taken.values()
                           if (r["kind"], r["path"]) == (kind, way))
                  for way in ("kernel", xla)}
    for way, n in counts.items():
        _g_engine_prefill_attend.set(float(n),
                                     tags={"kind": kind, "path": way})


# the same for the experts' products on stacked leaves: (rows, stack, type,
# path, reason) -> {.., "calls"}
_expert_products_taken: Dict[tuple, dict] = {}


def expert_product_paths() -> list:
    """Every distinct call of the experts' products on stacked leaves (rows,
    stack, type, path) traced in this process since its first routed engine
    was built, with its reason (``ops.moe.expert_product_path``'s) and how
    often: how a run proves which products its programs hold."""
    with _paths_lock:
        return [dict(rec) for rec in _expert_products_taken.values()]


def _note_expert_products(xs, w_up, path, reason) -> None:
    key = (xs.shape, w_up.shape, xs.dtype.name, path, reason)
    with _paths_lock:
        rec = _expert_products_taken.setdefault(key, {
            "rows": list(xs.shape), "stack": list(w_up.shape),
            "dtype": xs.dtype.name, "path": path, "reason": reason,
            "calls": 0})
        rec["calls"] += 1
        counts = {way: sum(r["calls"]
                           for r in _expert_products_taken.values()
                           if r["path"] == way) for way in ("kernel", "xla")}
    for way, n in counts.items():
        _g_engine_expert_products.set(float(n), tags={"path": way})


# counted where a program is traced (ops/moe.py _held_chunks / _held_blocks
# tell an engine's process through watch_held_sums): in an engine's process on
# the chip a prefill program over more than one chunk of places reads
# path=kernel (ops/row_sum.py) and a decode program (one straight block)
# path=xla; held_sum_paths() keeps the reason beside the count
_g_engine_held_sums = Gauge(
    "ray_tpu_serve_engine_held_sums",
    "Sums of the held experts' rows onto tokens traced in a decode engine's "
    "process, by the path they took: the Pallas gather-sum or XLA's "
    "scatter-add", tag_keys=("path",))

# (rows, tokens, type, path, reason) -> {.., "calls"}
_held_sums_taken: Dict[tuple, dict] = {}


def held_sum_paths() -> list:
    """Every distinct sum of the held path on stacked leaves (rows, tokens,
    type, path) traced in this process since its first routed engine was
    built, with its reason (``ops.moe.held_sum_path``'s) and how often."""
    with _paths_lock:
        return [dict(rec) for rec in _held_sums_taken.values()]


def _note_held_sums(ys, n_tokens, path, reason) -> None:
    dtype = jnp.dtype(ys.dtype).name
    key = (ys.shape, n_tokens, dtype, path, reason)
    with _paths_lock:
        rec = _held_sums_taken.setdefault(key, {
            "rows": list(ys.shape), "tokens": n_tokens, "dtype": dtype,
            "path": path, "reason": reason, "calls": 0})
        rec["calls"] += 1
        counts = {way: sum(r["calls"] for r in _held_sums_taken.values()
                           if r["path"] == way) for way in ("kernel", "xla")}
    for way, n in counts.items():
        _g_engine_held_sums.set(float(n), tags={"path": way})


def _watch_routed_calls() -> None:
    """What a routed engine registers with ``ops/moe.py`` where it is built:
    the counts of its experts' products and of its held sums by path."""
    from ray_tpu.ops.moe import watch_held_sums, watch_stacked_calls

    watch_stacked_calls(_note_expert_products)
    watch_held_sums(_note_held_sums)


# what the held path made of the last prefill's assignments: the places with
# a held expert (``live``: held_share of tokens x top_k, a routed layer's
# mean) and the places a row was gathered, multiplied and summed for
# (``made``: ops/moe.py held_places_made, whole chunks up to ``live``). Set by
# an engine whose layers hold a range of the router's experts, by no other
_g_moe_places = Gauge(
    "ray_tpu_serve_moe_places",
    "Sorted places of the last prefill's routed layers (a layer's mean) "
    "that fell on experts held here, and the places a row was made for",
    tag_keys=("state",))


def _note_assignments(shares, places: int, cfg) -> None:
    """Set ``ray_tpu_serve_moe_assignment_share{part}`` from a prefill's
    ``shares`` (a routed layer's mean of ``routed_mlp``'s ``held_share`` /
    ``zero_share``; neither: every expert is here) and, where a range is
    held, ``ray_tpu_serve_moe_places{state}`` of its ``places`` (tokens x
    ``top_k``, pads and all: the program routes them too)."""
    from ray_tpu.ops.moe import held_places_made

    held = float(shares.get("held_share", 1.0))
    zero = float(shares.get("zero_share", 0.0))
    for part, share in (("held", held), ("zero", zero),
                        ("elsewhere", 1.0 - held - zero)):
        _g_moe_assignment_share.set(share, tags={"part": part})
    if "held_share" in shares:
        live = round(held * places)
        wide = (cfg.router_experts or cfg.num_experts) + cfg.zero_experts
        made = held_places_made(places, live, cfg.num_experts, wide)
        for state, n in (("live", live), ("made", made)):
            _g_moe_places.set(float(n), tags={"state": state})


# bytes ONE position of the residual stream takes as the serving programs
# carry it between sublayers: hc_mult rows of dim in the stream's type
# (float32 for the kinds whose row of SERVED says so); set where an engine
# is built
_g_engine_stream_bytes = Gauge(
    "ray_tpu_serve_engine_stream_bytes",
    "Bytes one position of the residual stream takes as the decode engine's "
    "programs carry it (hc_mult rows of dim in the stream's type)")
# how far the last prefill's residual mixes were from doubly stochastic: the
# largest |row sum - 1| or |column sum - 1| of H_res over its positions and
# sublayers, read with the logits; an engine without hyper-connections sets
# none
_g_hc_sinkhorn_error = Gauge(
    "ray_tpu_serve_hc_sinkhorn_error",
    "Largest distance of a row or column sum of the last prefill's "
    "hyper-connection residual mixes from 1")


def _note_stream(shares) -> None:
    """Take what a prefill says of its stream out of ``shares`` (the
    walker's ``hc_sinkhorn_error``) and set its gauge."""
    error = shares.pop("hc_sinkhorn_error", None)
    if error is not None:
        _g_hc_sinkhorn_error.set(float(error))


# a walker's statistic over its layers (_serve_layers): a share is their
# mean, an error their largest
OVER_LAYERS = {"held_share": jnp.mean, "zero_share": jnp.mean,
               "hc_sinkhorn_error": jnp.max}


def attend_tiles(q, k, v, cd, window: int = 0, shared=None, kind=None):
    """Causal attention over the call's own positions without the ``[heads,
    T, T]`` scores: ``q`` [B, T, H, D], ``k`` / ``v`` [B, T, Hkv, Dk / Dv]
    (``Hkv`` divides ``H``), ``shared`` [B, T, D - Dk] or None: a slice of
    every head's key that all heads share, scored against ``q[..., Dk:]``
    (latent attention's rotated slice). Products in ``cd``, scores and
    softmax float32, the scale ``1 / sqrt(D)``. With a ``window`` position
    ``i`` sees ``j <= i`` with ``j > i - window``. ``kind``: the name the
    call is counted under where the shapes do not say it.

    Two paths, ONE arithmetic (:func:`prefill_attend_path` says which and
    why; ``ray_tpu_serve_engine_prefill_attend{kind, path}`` and
    :func:`prefill_attend_paths` count them where a program is traced). On
    a TPU backend one Pallas call, forward only
    (``ops/flash_prefill.py``, imported here and nowhere else): a head's
    scores ``[block, block]`` and its running maximum, sum and weighted
    values never leave VMEM, GQA and the shared slice are index maps and
    not copies, and a window layer's loop over key blocks starts at its
    band. Its transpose is the tile loop's (``jax.grad`` works, at the tile
    loop's cost). On every other backend, and for what the kernel does not
    take, the XLA tile loop (:func:`_tile_loop`): float32 scores ``[heads,
    block, block]`` and accumulators through memory at every tile, the keys
    and values of a tile repeated for GQA and ``shared`` repeated for every
    head first. Both skip the key blocks wholly behind the band as those
    ahead of the query block, so a window layer's work follows the band's
    area and not the triangle's."""
    kind = kind or ("latent" if shared is not None
                    else "window" if window else "full")
    path, reason = prefill_attend_path(q, k, v, cd, shared)
    _note_prefill_attend(kind, q, k, window, path, reason)
    if path == "kernel":
        return _attend_kernel(q, k, v, shared, window, cd)
    return _tile_loop(q, k, v, shared, window, cd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend_kernel(q, k, v, shared, window, cd):
    from ray_tpu.ops.flash_prefill import flash_prefill

    # interpreted where a test has steered a CPU process onto this path
    return flash_prefill(q, k, v, shared=shared, window=window,
                         interpret=jax.default_backend() != "tpu")


def _attend_kernel_fwd(q, k, v, shared, window, cd):
    return _attend_kernel(q, k, v, shared, window, cd), (q, k, v, shared)


def _attend_kernel_bwd(window, cd, operands, g):
    # forward only: the kernel keeps no log-sum-exp, so its transpose is the
    # tile loop's, run again from the operands
    return jax.vjp(lambda *a: _tile_loop(*a, window, cd), *operands)[1](g)


_attend_kernel.defvjp(_attend_kernel_fwd, _attend_kernel_bwd)


def _tile_loop(q, k, v, shared, window: int, cd):
    """:func:`attend_tiles` in XLA. Queries go in blocks of
    ``LATENT_QUERY_BLOCK`` (``lax.map``), each over the key blocks it may
    see (a ``lax.scan`` whose other steps are skipped), the softmax carried
    across key blocks in float32 (running maximum, sum and weighted
    values), so the scores alive are ``[heads, block, block]``."""
    f32 = jnp.float32
    B, T, H, D = q.shape
    if shared is not None:
        k = jnp.concatenate([k, jnp.broadcast_to(
            shared[:, :, None], (B, T, k.shape[2], D - k.shape[-1]))],
            axis=-1)
    rep = H // k.shape[2]
    block = math.gcd(T, LATENT_QUERY_BLOCK)
    at = jnp.arange(block, dtype=jnp.int32)
    numbers = jnp.arange(T // block, dtype=jnp.int32)

    def cut(a, n):  # block ``n`` of the positions, where it is used
        return jax.lax.dynamic_slice_in_dim(a, n * block, block, axis=1)

    def query_block(i):
        q_b = cut(q, i)

        def key_block(carry, j):
            def seen(carry):
                k_b, v_b = cut(k, j), cut(v, j)
                if rep > 1:
                    k_b, v_b = (jnp.repeat(a, rep, axis=2)
                                for a in (k_b, v_b))
                top, total, acc = carry
                s = jnp.einsum("bqhd,bkhd->bhqk", q_b, k_b,
                               preferred_element_type=f32) / math.sqrt(D)
                at_q, at_k = (i * block + at)[:, None], \
                    (j * block + at)[None, :]
                visible = at_q >= at_k
                if window:
                    visible &= at_q - at_k < window
                s = jnp.where(visible, s, -1e30)
                new_top = jnp.maximum(top, s.max(axis=-1))
                probs = jnp.exp(s - new_top[..., None])
                old = jnp.exp(top - new_top)
                acc = acc * old[..., None] + jnp.einsum(
                    "bhqk,bkhd->bhqd", probs.astype(cd), v_b,
                    preferred_element_type=f32)
                return new_top, total * old + probs.sum(axis=-1), acc

            # key blocks ahead of the query block hold nothing it may
            # see, nor do those wholly behind its first query's window (a
            # row whose keys in a visited block are all masked carries
            # exp(0) sums until its first real score rescales them by 0)
            near = j <= i
            if window:
                near &= j >= (i * block - window + 1) // block
            return jax.lax.cond(near, seen, lambda c: c, carry), None

        start = (jnp.full((B, H, block), -1e30, f32),
                 jnp.zeros((B, H, block), f32),
                 jnp.zeros((B, H, block, v.shape[-1]), f32))
        (_, total, acc), _ = jax.lax.scan(key_block, start, numbers)
        return jnp.moveaxis(acc / total[..., None], 1, 2).astype(cd)

    o = jax.lax.map(query_block, numbers)  # [T / block, B, block, H, dv]
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, -1)


def shortcut_layer(cfg: LlamaConfig, x, layers, i, positions, attend,
                   stat_axes=()):
    """THE shortcut-connected double layer (kind ``"S"``), for the full
    forward and for the serving programs::

        a0  = x  + MLA_0(N(x))
        h0  = N(a0);  m = MoE(h0)          # the shortcut: m waits
        b0  = a0 + FFN_0(h0)
        a1  = b0 + MLA_1(N(b0))
        b1  = a1 + FFN_1(N(a1))
        out = b1 + m                       # attention 1 and FFN 1 never see m

    ``layers``: the kind's stacked weights ``[n_S, ...]``, ``i``: which
    layer (a number, or traced in a scan over layers). The two sublayers'
    norms, latent attention (:func:`_latent_half`) and dense SwiGLU are
    stacked ``[n_S, 2, ...]``, router and experts are :func:`_mlp_half`'s.
    Every matrix is cut out where it is used, ``[i, j]`` at once: a whole
    layer cut out first is a copy of 2.5 GB a layer at the published
    widths (30 of a decode call's 37 ms; my chip run, PR 33). The router
    and its bias are small and are cut out ``[i]``. The EXPERTS are not cut
    out at all: their grouped product is a kernel and no fusion, so
    ``[i]`` before it is three copies of 403 MB a layer at every prefill
    and decode call (14.7 ms a call, 32% of the LongCat cell's device
    time; ledger, PR 35). They go down whole with ``layer=i`` and the
    product places the layer's groups in the stack (``routed_mlp``).
    ``attend(j, q, latent, wkv_b)``: what sublayer ``j`` attends over.
    Returns ``(x, stats, latents)``: ``latents`` ``[2, B, T, latent_row]``,
    what each sublayer's cache keeps."""
    cd = cfg.dtype

    def norm(y, name, j):
        return rms_norm(y, layers[name][i, j], cfg.norm_eps).astype(cd)

    latents = []

    def mla(j, y):
        sub = {w: layers[w][i, j] for w in ("wq_a", "q_norm", "wq_b", "wkv_a",
                                            "kv_norm", "wkv_b", "wo")}
        out, latent = _latent_half(cfg, sub, norm(y, "attn_norm", j),
                                   positions, partial(attend, j))
        latents.append(latent)
        return y + out.astype(y.dtype)

    def ffn(j, h):
        with jax.named_scope("ffn.dense"):
            g = jax.nn.silu(h @ layers["ffn_gate"][i, j].astype(cd))
            return (g * (h @ layers["ffn_up"][i, j].astype(cd))) \
                @ layers["ffn_down"][i, j].astype(cd)

    a0 = mla(0, x)
    h0 = norm(a0, "mlp_norm", 0)
    m, stats = _mlp_half(
        cfg, {"router": layers["router"][i],
              "router_bias": layers["router_bias"][i],
              **{w: layers[w] for w in ("w_gate", "w_up", "w_down")}},
        h0, stat_axes, layer=i)
    b0 = a0 + ffn(0, h0).astype(x.dtype)
    a1 = mla(1, b0)
    b1 = a1 + ffn(1, norm(a1, "mlp_norm", 1)).astype(x.dtype)
    return b1 + m.astype(x.dtype), stats, jnp.stack(latents)


def window_block(cfg: LlamaConfig, kind: str, x, layers, i, positions,
                 attend, stat_axes=()):
    """THE whole block of the kinds ``"F"`` and ``"W"``, for the full
    forward and for the serving programs::

        a   = N(x)
        h   = x + Attention(a)        # "W": rotated, over the last
                                      # cfg.window positions; "F": not
                                      # rotated, over every earlier one
        out = h + MoE(N(h); router reads a)

    The ROUTER reads ``a``, the attention's input, and its experts
    ``N(h)`` (``routed_mlp``'s ``router_input``). ``layers``: the ``block``
    stack ``[L, ...]`` both kinds share, ``i``: which layer (a number, or
    traced in a scan). The attention's matrices, the norms and the router
    are cut out ``[i]`` where they are used; the experts go down whole with
    ``layer=i`` (:func:`shortcut_layer` says why). The stream keeps the
    type it comes in (the serving programs carry it in float32: a top-k
    choice is a hard one, and a stream rounded to ``cfg.dtype`` at every
    layer moves the last of a token's choices often enough to show in the
    logits; my chip runs, PR 38), every product runs in ``cfg.dtype``.
    ``attend(q, k, v)`` as :func:`decoder_block` takes it: what this layer
    attends over, under the device scope ``attn.window`` / ``attn.full``.
    Returns ``(x, stats, (k, v))``: ``k`` rotated for ``"W"`` and as
    projected for ``"F"``."""
    cd = cfg.dtype
    scope = "attn.window" if kind == "W" else "attn.full"

    def scoped(q, k, v):
        with jax.named_scope(scope):
            return attend(q, k, v)

    # the router reads the norm's output as the stream's type gives it (the
    # engine carries the stream in float32), the products its rounding
    a = rms_norm(x, layers["attn_norm"][i], cfg.norm_eps)
    y, k, v = _attn_half(
        cfg, {w: layers[w][i] for w in ("wq", "wk", "wv", "wo")},
        a.astype(cd), positions, scoped, rope=kind == "W")
    h = x + y.astype(x.dtype)
    m = rms_norm(h, layers["mlp_norm"][i], cfg.norm_eps).astype(cd)
    y, stats = _mlp_half(
        cfg, {"router": layers["router"][i],
              **{w: layers[w] for w in ("w_gate", "w_up", "w_down")}},
        m, stat_axes, layer=i, router_in=a)
    return h + y.astype(x.dtype), stats, (k, v)


def attend_window_tiles(cfg: LlamaConfig, kind: str, q, k, v):
    """:func:`window_block`'s ``attend`` over the call's own positions (the
    full forward and prefill): :func:`attend_tiles` (the flash kernel on a
    TPU backend, XLA tiles elsewhere), with the band for ``"W"``."""
    return attend_tiles(q, k, v, cfg.dtype,
                        window=cfg.window if kind == "W" else 0)


# --------------------------------------------------------------------------- #
# The indexed block (kind "I"): attention over the keys an indexer picks
# --------------------------------------------------------------------------- #


def mrope_rotate(x, positions, theta: float, sections=()):
    """Rotate ``x`` [B, T, H, D] by ``positions`` [3, B, T] (temporal,
    height, width; or [B, T]: the three equal, which is the 1-D rotation),
    half-split pairs ``(i, i + D/2)``. Frequency ``i`` of ``D/2`` takes its
    angle from the component whose section of ``sections`` it falls in
    (boundaries at their running sums; beyond the last, the last): a head
    with fewer frequencies than the sections' sum has the first of them
    only. Without sections every frequency reads component 0."""
    import numpy as np

    D = x.shape[-1]
    if positions.ndim == 2:
        positions = jnp.broadcast_to(positions, (3, *positions.shape))
    at = np.arange(D // 2)
    part = (np.minimum(np.searchsorted(np.cumsum(sections), at, "right"),
                       len(sections) - 1) if len(sections) else 0 * at)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    # [D/2, B, T] -> [B, T, 1, D/2]
    angles = jnp.moveaxis(positions[part].astype(jnp.float32), 0, -1) \
        * inv_freq
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def yarn_frequencies(cfg: LlamaConfig):
    """The latent half's rotation frequencies under ``cfg.rope_yarn``
    (``[qk_rope_head_dim / 2]`` float32, host arithmetic): frequency ``i``
    of ``f_i = theta ** (-2i / D)`` is kept where it turns more than
    ``beta_fast`` times within the original context, divided by ``factor``
    where fewer than ``beta_slow`` times, and blended linearly between::

        low  = floor(D ln(original / (beta_fast 2 pi)) / (2 ln theta))
        high = ceil(D ln(original / (beta_slow 2 pi)) / (2 ln theta))
        ramp_i = clip((i - low) / (high - low), 0, 1)   both within [0, D-1]
        f_i (1 - ramp_i) + f_i / factor ramp_i"""
    import numpy as np

    y, D = dict(cfg.rope_yarn), cfg.qk_rope_head_dim
    f = cfg.rope_theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)

    def turns(beta):
        return (D * math.log(y["original_max_position_embeddings"]
                             / (beta * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = min(max(math.floor(turns(y.get("beta_fast", 32))), 0), D - 1)
    high = min(max(math.ceil(turns(y.get("beta_slow", 1))), 0), D - 1)
    ramp = np.clip((np.arange(D // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (f * (1 - ramp) + f / y["factor"] * ramp).astype(np.float32)


def yarn_softmax_factor(cfg: LlamaConfig) -> float:
    """What multiplies the latent half's scores beside ``1 / sqrt(score
    width)`` under ``cfg.rope_yarn``: ``(0.1 mscale_all_dim ln(factor) +
    1) ** 2`` (1 without it)."""
    y = dict(cfg.rope_yarn)
    if not y or y["factor"] <= 1:
        return 1.0
    return (0.1 * y.get("mscale_all_dim", 1) * math.log(y["factor"])
            + 1.0) ** 2


def _order_key(score):
    """float32 -> int32 that orders as the floats do (-0.0 under +0.0)."""
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


_INT_MIN = -2 ** 31


def select_top(score, visible, k: int):
    """The mask of the ``k`` ``visible`` entries of largest ``score``
    (float32) along the last axis, all of them where there are no more than
    ``k``; of entries that tie at the last place the LOWER indices. No sort
    and no top-k: the ``k``-th largest value a row is found by bisection
    over the floats' ordered bit patterns (32 counts of the row), the last
    tie's index by bisection over indices, and the mask is two comparisons.
    ``jax.lax.top_k`` for 2,048 of 32,768 a row is a sort of every row."""
    n = score.shape[-1]
    if k >= n:
        return visible
    i32 = jnp.int32
    key = jnp.where(visible, _order_key(score), i32(_INT_MIN))

    def count(m):
        return jnp.sum(m, axis=-1, dtype=i32, keepdims=True)

    def value_bit(b, tau):  # the largest tau that k entries reach
        cand = tau | jnp.left_shift(i32(1), 30 - b)
        return jnp.where(count(key >= cand) >= k, cand, tau)

    tau = jax.lax.fori_loop(
        0, 31, value_bit,
        jnp.where(count(key >= 0) >= k, i32(0), i32(_INT_MIN)))
    above = key > tau
    tie = visible & (key == tau)
    need = k - count(above)
    at = jnp.arange(n, dtype=i32)
    bits = (n - 1).bit_length()

    def index_bit(b, cut):  # the largest cut with fewer than need ties under it
        cand = cut | jnp.left_shift(i32(1), bits - 1 - b)
        return jnp.where(count(tie & (at < cand)) < need, cand, cut)

    cut = jax.lax.fori_loop(0, bits, index_bit, jnp.zeros_like(tau))
    return above | (tie & (at <= cut))


def index_scores(qi, ki, w):
    """The indexer's score of every (query, key) pair: ``qi`` [B, Tq, Hi,
    Di] and ``ki`` [B, Tk, Di] (ONE key head; both rotated, compute type),
    ``w`` [B, Tq, Hi] float32, a query head's weight with the two constant
    scales in it. ``sum_j w_j relu(qi_j . ki)``, float32, [B, Tq, Tk]."""
    s = jnp.einsum("bqhd,bkd->bhqk", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * jnp.moveaxis(w, -1, 1)[..., None],
                   axis=1)


def _indexer(cfg: LlamaConfig, p, a, positions, cq=None):
    """The indexer's three projections of ``a`` [B, T, dim] (normed, the
    stream's type): ``qi`` [B, T, Hi, Di] and ``ki`` [B, T, Di] =
    LayerNorm(a wki) (scale and bias), their FIRST HALF rotated as a rotary
    of its own (``Di / 4`` frequencies, half-split, by the temporal
    position), both in the compute type; ``w`` [B, T, Hi] float32 = ``a ww
    / sqrt(Hi Di)``. ``ki`` is what a cache keeps (as float32). ``cq`` [B,
    T, q_lora_rank]: the query's normed latent, which ``qi`` reads INSTEAD
    of ``a`` where the attention has one (:func:`dsa_block`); the first half
    then turns in pairs ``(2i, 2i + 1)``, as that attention's rope slice."""
    cd = cfg.dtype
    B, T, _ = a.shape
    hi, di = cfg.index_heads, cfg.index_head_dim
    h = a.astype(cd)
    qi = ((h if cq is None else cq.astype(cd))
          @ p["wqi"].astype(cd)).reshape(B, T, hi, di)
    ki = (h @ p["wki"].astype(cd)).astype(jnp.float32)
    mean = jnp.mean(ki, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
    ki = (ki - mean) * jax.lax.rsqrt(var + cfg.norm_eps) * p["ki_norm"] \
        + p["ki_bias"]
    when = positions[0] if positions.ndim == 3 else positions

    def rotated(x):  # [B, T, H, Di]: the first half turns
        if cq is None:
            turned = mrope_rotate(x[..., :di // 2], when, cfg.rope_theta)
        else:
            turned = rotate_pairs(x[..., :di // 2], when, cfg.rope_theta)
        return jnp.concatenate([turned, x[..., di // 2:]], axis=-1)

    w = jnp.dot(a.astype(jnp.float32), p["ww"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST) / math.sqrt(hi * di)
    return rotated(qi), rotated(ki[:, :, None])[:, :, 0].astype(cd), w


def index_block(cfg: LlamaConfig, x, layers, i, positions, attend,
                stat_axes=()):
    """THE indexed block (kind ``"I"``), for the full forward and for the
    serving programs::

        a       = N(x)
        q, k, v = a Wq, a Wk, a Wv; q, k: RMSNorm over each head's width
                  (one gain for q, one for k), then rotated (sections)
        qI, kI, w = the indexer's (:func:`_indexer`)
        I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))           float32
        S(t)    = the index_topk positions s <= t of largest I(t, s)
        h       = x + Wo softmax_{s in S(t)}(q(t) . k(s) / sqrt(D)) v(s)
        out     = h + MoE(N(h))        # the router reads N(h), in float32

    ``layers``: the ``index`` stack ``[L, ...]``, ``i``: which layer (a
    number, or traced in a scan); small leaves are cut out ``[i]`` where
    they are used, the experts go down whole with ``layer=i``
    (:func:`shortcut_layer` says why). The stream keeps the type it comes
    in (the serving programs carry it in float32, as :func:`window_block`'s
    do). ``positions`` [B, T] or [3, B, T]. ``attend(q, k, v, qi, ki, w)``
    -> ``[B, T, heads, head_dim]``: what this layer scores, selects and
    attends over, under the device scopes ``dsa.index``, ``dsa.select`` and
    ``dsa.attend``. Returns ``(x, stats, (k, v, ki))``: what a cache keeps
    (``k`` normed and rotated, ``ki`` normed and rotated)."""
    cd, hd, eps = cfg.dtype, cfg.head_dim, cfg.norm_eps
    B, T, _ = x.shape
    p = {w: layers[w][i] for w in (
        "wq", "wk", "wv", "wo", "q_norm", "k_norm", "wqi", "wki", "ww",
        "ki_norm", "ki_bias")}
    a = rms_norm(x, layers["attn_norm"][i], eps)
    h = a.astype(cd)
    q = rms_norm((h @ p["wq"].astype(cd)).reshape(B, T, -1, hd),
                 p["q_norm"], eps)
    k = rms_norm((h @ p["wk"].astype(cd)).reshape(B, T, -1, hd),
                 p["k_norm"], eps)
    v = (h @ p["wv"].astype(cd)).reshape(B, T, -1, hd)
    q, k = (mrope_rotate(y, positions, cfg.rope_theta, cfg.mrope_section)
            for y in (q, k))
    with jax.named_scope("dsa.index"):
        qi, ki, w = _indexer(cfg, p, a, positions)
    o = attend(q, k, v, qi, ki, w).reshape(B, T, -1)
    h = x + (o @ p["wo"].astype(cd)).astype(x.dtype)
    m = rms_norm(h, layers["mlp_norm"][i], eps)
    y, stats = _mlp_half(
        cfg, {"router": layers["router"][i],
              **{w: layers[w] for w in ("w_gate", "w_up", "w_down")}},
        m.astype(cd), stat_axes, layer=i, router_in=m)
    return h + y.astype(x.dtype), stats, (k, v, ki)


def selected_attend_path(q, cd) -> Tuple[str, str]:
    """``(path, reason)`` :func:`attend_selected` takes: ``"kernel"`` on a
    TPU backend for what ``ops/sparse_prefill.py`` takes, ``"tiles"`` with
    what stands in the way otherwise."""
    platform = jax.default_backend()
    if platform != "tpu":
        return "tiles", f"backend is {platform!r}, not tpu"
    if q.shape[1] % 128 or q.shape[-1] % 128:
        return "tiles", (f"{q.shape[1]} positions or a head of "
                         f"{q.shape[-1]} are no multiple of 128")
    if q.dtype != jnp.dtype(cd):
        return "tiles", f"operands in {q.dtype.name}"
    return "kernel", "tpu backend"


def _masked_flash_step(path, q, k, v):
    """What a grid step of the selection's attention kernel holds for these
    operands on the kernel's ``path`` (``ops/sparse_prefill.py flash_step``,
    which ``masked_flash`` itself calls), None on the other."""
    if path != "kernel":
        return None
    from ray_tpu.ops.sparse_prefill import flash_step

    return flash_step(q, k, v)


def attend_selected(cfg: LlamaConfig, q, k, v, qi, ki, w):
    """:func:`index_block`'s ``attend`` over the call's own positions (the
    full forward and prefill): index scores of every visible pair, the
    ``index_topk`` best a query (:func:`select_top`'s rule), softmax
    attention under that selection. Two paths, ONE arithmetic
    (:func:`selected_attend_path`; counted as kind ``selected`` where
    :func:`attend_tiles`' kinds are). On a TPU backend two Pallas calls
    (``ops/sparse_prefill.py``, imported here and nowhere else): one scores
    a block of queries against every visible key, finds each row's
    threshold by bisection inside VMEM and writes the selection as a mask;
    one is flash attention under that mask, a KV group's query heads
    stacked into one product. On every other backend, and as the oracle,
    :func:`_selected_tiles` in XLA."""
    path, reason = selected_attend_path(q, cfg.dtype)
    _note_prefill_attend("selected", q, k, 0, path, reason,
                         step=_masked_flash_step(path, q, k, v))
    if path == "kernel":
        from ray_tpu.ops.sparse_prefill import index_select, masked_flash

        with jax.named_scope("dsa.select"):  # scores and selection, fused
            chosen = index_select(qi, ki, w, cfg.index_topk)
        with jax.named_scope("dsa.attend"):
            return masked_flash(q, k, v, chosen)
    return _selected_tiles(q, k, v, qi, ki, w, cfg.index_topk, cfg.dtype,
                           cfg.index_chunk)


def _selected_tiles(q, k, v, qi, ki, w, topk: int, cd, chunk: int):
    """:func:`attend_selected` in XLA: queries in blocks of ``chunk``
    (``lax.map``), each against ALL keys: index scores ``[block, T]``, the
    selection, float32 attention scores ``[heads, block, T]`` under it."""
    f32 = jnp.float32
    B, T, H, D = q.shape
    G = k.shape[2]
    block = math.gcd(T, chunk)
    at = jnp.arange(block, dtype=jnp.int32)
    keys = jnp.arange(T, dtype=jnp.int32)

    def query_block(n):
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, n * block, block, axis=1)

        visible = keys[None, :] <= (n * block + at)[:, None]
        with jax.named_scope("dsa.index"):
            score = index_scores(cut(qi), ki, cut(w))
        with jax.named_scope("dsa.select"):
            chosen = select_top(score, visible[None], topk)
        with jax.named_scope("dsa.attend"):
            s = jnp.einsum("bqgrd,bkgd->bgrqk",
                           cut(q).reshape(B, block, G, H // G, D), k,
                           preferred_element_type=f32) / math.sqrt(D)
            s = jnp.where(chosen[:, None, None], s, -1e30)
            probs = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(cd), v,
                           preferred_element_type=f32)
        return o.reshape(B, block, H, -1).astype(cd)

    o = jax.lax.map(query_block, jnp.arange(T // block, dtype=jnp.int32))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, -1)


# --------------------------------------------------------------------------- #
# Latent attention UNDER a selection that layers share (kinds "Y", "Z", "X")
# --------------------------------------------------------------------------- #


class Selecting(NamedTuple):
    """The serving stream of the kinds that attend under a selection which
    ANOTHER layer may have made (:data:`DSA_KINDS`): ``stream`` ``[B, T,
    dim]`` and, beside it, ``chosen``, the newest selection: what a FULL
    layer writes and every layer behind it reads until the next full one.
    A pytree like any other stream: the walker's scans carry it (through a
    scanned run of shared layers it is the scan's constant), and the
    programs make it behind the embedding (:func:`selecting_stream`) and
    drop it in front of the head (:func:`stream_of`). ``chosen`` of a
    prefill: the mask of the call's (query, key) pairs, int8, ``[B, T, T]``
    or, where the Pallas calls run, their tiles
    (``ops/sparse_prefill.py index_select``); of a decode call: ``(where
    [index_topk] int32, seen [index_topk + 1] bool)``, the chosen EARLIER
    positions in order (:func:`_pick_rows`)."""
    stream: Any
    chosen: Any


def selected_prefill_path(cfg: LlamaConfig, T: int) -> Tuple[str, str]:
    """:func:`selected_attend_path` for a latent block's prefill of ``T``
    positions, from the sizes alone (the stream's first selection has to be
    shaped before any layer has made a query)."""
    width = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return selected_attend_path(jax.ShapeDtypeStruct(
        (1, T, cfg.n_heads, width), jnp.dtype(cfg.dtype)), cfg.dtype)


def selecting_stream(cfg: LlamaConfig, x, decode: bool = False):
    """The stream ``x`` as the serving programs carry it through a stack of
    :data:`DSA_KINDS`: :class:`Selecting`, with a selection of the right
    shape and nothing chosen (the stack's first layer is a full one and
    writes it; a scanned period carries it in). Any other stack: ``x``."""
    if not set(cfg.kinds) & set(DSA_KINDS):
        return x
    B, T, _ = x.shape
    K = cfg.index_topk
    if decode:
        return Selecting(x, (jnp.zeros((K,), jnp.int32),
                             jnp.zeros((K + 1,), bool)))
    if selected_prefill_path(cfg, T)[0] == "kernel":
        from ray_tpu.ops.sparse_prefill import mask_tiles_shape

        return Selecting(x, jnp.zeros(mask_tiles_shape(B, T), jnp.int8))
    return Selecting(x, jnp.zeros((B, T, T), jnp.int8))


def stream_of(x):
    """The stream alone: :class:`Selecting`'s or :class:`Remembering`'s, or
    ``x`` as it is."""
    return x.stream if isinstance(x, (Selecting, Remembering)) else x


def dsa_block(cfg: LlamaConfig, kind: str, x, layers, i, positions, attend,
              stat_axes=()):
    """THE latent block under a selection (kinds ``"Y"``, ``"Z"``, ``"X"``),
    for the serving programs. ``x``: :class:`Selecting`::

        a        = N(x)
        q, [c|kr], cq = the latent projections (:func:`_latent_project`)
        FULL:    qI, kI, w = the indexer's (:func:`_indexer`; qI reads cq)
                 S(t) = the index_topk positions s <= t of largest
                        sum_j w_j(t) relu(qI_j(t) . kI(s))        float32
        SHARED:  S(t) = the S(t) that came with the stream
        h        = x + Wo softmax_{s in S(t)}(q(t) . k(s) / sqrt(nope + rope))
                   v(s),  k = [c Wkb | kr], v = c Wvb a head
        out      = h + MLP(N(h))   "X": the dense SwiGLU; "Y" / "Z": the
                   routed MLP with its ungated shared expert (:func:`_mlp_half`)

    ``layers``: the kind's stack ``[n, ...]``, layer ``i``; every leaf is cut
    out ``[i]`` but the routed experts (:func:`shortcut_layer` says why).
    ``attend(q, latent, wkv_b, index, chosen)`` -> ``(o [B, T, heads,
    v_head_dim], chosen)``: ``index`` is ``(qI, kI, w)`` in a full layer
    (the selection is made, and returned) and None in a shared one (the one
    given is used, and returned). A shared layer computes no index score and
    keeps no index key. Device scopes: ``mla.project``, ``dsa.index``,
    ``dsa.select`` (full layers only), ``dsa.attend`` / ``dsa.attend_shared``,
    ``mla.out``. Returns ``(Selecting, stats, rows)``: ``rows`` ``(latent,
    kI)`` or ``latent`` alone, what a cache keeps."""
    cd, eps = cfg.dtype, cfg.norm_eps
    x, chosen = x
    B, T, _ = x.shape
    whole = () if kind == "X" else ("w_gate", "w_up", "w_down")
    p = {w: a if w in whole else a[i] for w, a in layers.items()}
    a = rms_norm(x, p["attn_norm"], eps)
    q, latent, cq = _latent_project(cfg, p, a.astype(cd), positions)
    index = None
    if kind in DSA_FULL:
        with jax.named_scope("dsa.index"):
            index = _indexer(cfg, p, a, positions, cq=cq)
    o, chosen = attend(q, latent, p["wkv_b"].astype(cd), index, chosen)
    with jax.named_scope("mla.out"):
        y = o.reshape(B, T, -1) @ p["wo"].astype(cd)
    h = x + y.astype(x.dtype)
    m = rms_norm(h, p["mlp_norm"], eps).astype(cd)
    if kind == "X":
        with jax.named_scope("ffn.dense"):
            y, stats = _dense_mlp(cfg, p, m), {}
    else:
        y, stats = _mlp_half(cfg, p, m, stat_axes, layer=i)
    rows = latent if index is None else (latent, index[1])
    return Selecting(h + y.astype(x.dtype), chosen), stats, rows


def _latent_heads(cfg: LlamaConfig, latent, wkv_b):
    """Every position's per-head key ``[c Wkb | kr]`` and value ``c Wvb``
    from its latent row, ``[B, T, heads, nope + rope]`` and ``[B, T, heads,
    v_head_dim]``: the ONE rotated slice repeated for every head."""
    B, T, _ = latent.shape
    H, r, dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = (latent[..., :r] @ wkv_b).reshape(B, T, H, -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        latent[:, :, None, r:], (B, T, H, cfg.qk_rope_head_dim))], axis=-1)
    return k, kv[..., dn:]


def attend_latent_selected(cfg: LlamaConfig, q, latent, wkv_b, index, chosen):
    """:func:`dsa_block`'s ``attend`` over the call's own positions
    (prefill): per-head keys and values expanded from the latent rows
    (:func:`_latent_heads`), softmax attention under the selection, which a
    full layer makes here (``index`` given: :func:`attend_selected`'s two
    paths and ONE arithmetic, counted as kind ``latent_selected``) and a
    shared layer takes as it came. Returns ``(o, chosen)``."""
    path, reason = selected_prefill_path(cfg, q.shape[1])
    k, v = _latent_heads(cfg, latent, wkv_b)
    _note_prefill_attend("latent_selected", q, latent, 0, path, reason,
                         step=_masked_flash_step(path, q, k, v))
    if path == "kernel":
        from ray_tpu.ops.sparse_prefill import index_select, masked_flash

        if index is not None:
            with jax.named_scope("dsa.select"):  # scores and selection
                chosen = index_select(*index, cfg.index_topk)
        with jax.named_scope("dsa.attend" if index is not None
                             else "dsa.attend_shared"):
            return masked_flash(q, k, v, chosen), chosen
    return _latent_selected_tiles(q, k, v, index, chosen, cfg.index_topk,
                                  cfg.dtype, cfg.index_chunk)


def _latent_selected_tiles(q, k, v, index, chosen, topk: int, cd, chunk: int):
    """:func:`attend_latent_selected` in XLA, as :func:`_selected_tiles`
    but for the selection, which is an OUTPUT (``[B, T, T]`` int8) where
    ``index`` is given and an input where it is None."""
    f32 = jnp.float32
    B, T, H, D = q.shape
    block = math.gcd(T, chunk)
    at = jnp.arange(block, dtype=jnp.int32)
    keys = jnp.arange(T, dtype=jnp.int32)

    def query_block(n):
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, n * block, block, axis=1)

        if index is None:
            mine = cut(chosen) != 0
        else:
            qi, ki, w = index
            visible = keys[None, :] <= (n * block + at)[:, None]
            with jax.named_scope("dsa.index"):
                score = index_scores(cut(qi), ki, cut(w))
            with jax.named_scope("dsa.select"):
                mine = select_top(score, visible[None], topk)
        with jax.named_scope("dsa.attend" if index is not None
                             else "dsa.attend_shared"):
            s = jnp.einsum("bqhd,bkhd->bhqk", cut(q), k,
                           preferred_element_type=f32) / math.sqrt(D)
            probs = jax.nn.softmax(jnp.where(mine[:, None], s, -1e30),
                                   axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cd), v,
                           preferred_element_type=f32)
        return o.astype(cd), mine.astype(jnp.int8)

    o, mask = jax.lax.map(query_block,
                          jnp.arange(T // block, dtype=jnp.int32))
    return (jnp.moveaxis(o, 0, 1).reshape(B, T, H, -1),
            jnp.moveaxis(mask, 0, 1).reshape(B, T, T))


# --------------------------------------------------------------------------- #
# The delta-rule family (kinds "D" and "A"): a state a sequence, beside pages
# --------------------------------------------------------------------------- #


def _routed_half(cfg: LlamaConfig, h, layers, i, stat_axes):
    """The "D" / "A" blocks' second half on the stream ``h`` (its type
    kept): the zero-centred norm, then the routed MLP whose router reads its
    own input in float32, the held experts and the ONE shared SwiGLU expert
    behind its per-token sigmoid gate (``routed_mlp``'s ``shared_gated``).
    Small leaves are cut out ``[i]``, the experts go down whole with
    ``layer=i`` (:func:`shortcut_layer` says why)."""
    from ray_tpu.ops.moe import routed_mlp

    m = rms_norm(h, 1.0 + layers["mlp_norm"][i], cfg.norm_eps)
    wide = cfg.router_experts or cfg.num_experts
    # the float32 sum goes onto the float32 stream as it is (_mlp_half would
    # round it to cfg.dtype first)
    y, stats = routed_mlp(
        m.astype(cfg.dtype), layers["router"][i], layers["w_gate"],
        layers["w_up"], layers["w_down"], top_k=cfg.experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob, stat_axes=stat_axes,
        held=((cfg.first_expert, cfg.num_experts)
              if wide != cfg.num_experts else None),
        layer=i, router_input=m,
        shared_gated=(tuple(layers[w][i] for w in (
            "w_sg", "ws_gate", "ws_up", "ws_down"))
            if cfg.shared_mlp_dim else None))
    return h + y.astype(h.dtype), stats


def delta_block(cfg: LlamaConfig, x, layers, i, positions, attend,
                stat_axes=()):
    """THE delta-rule block (kind ``"D"``), for the full forward and for the
    serving programs (``N``: the zero-centred RMSNorm, ``x / rms(x) (1 +
    w)``)::

        a   = N(x)
        [q | k | v | z] = a W_qkvz                [b | a'] = a W_ba
        beta = sigmoid(b)       g = -exp(A_log) softplus(a' + dt_bias)
        o, state, tail = attend([q | k | v], g, beta, conv_w)
        y   = gate_norm * o / rms(o) * silu(z)    a value head; the norm
                                                  BEFORE the gate, plain
        h   = x + y W_out
        out = h + MoE(N(h))                       (:func:`_routed_half`)

    ``attend`` is all that knows where the call stands in its sequence: the
    causal convolution over ``[q | k | v]`` and ``silu``, the heads' split
    and l2-norm (:func:`_delta_heads`) and the gated delta rule
    (``ops/gdn.py``), over the call's own positions from an empty state
    (:func:`attend_delta`: on a TPU all of it is ONE Pallas call,
    ``ops/gdn_prefill.py``, which reads ``[q | k | v]`` where the product
    below wrote it) or one token on from a kept state and tail
    (:func:`_attend_state`). It returns ``o`` [B, T, value heads, value dim]
    float32 and what a cache keeps A SEQUENCE: the state after the last real
    position ``[B, 1, value heads, key dim, value dim]`` float32 and the
    convolution's tail, the last ``lin_conv - 1`` rows of ``[q | k | v]``
    ``[B, 1, lin_conv - 1, width]``. Device scopes ``gdn.in_proj``,
    ``gdn.conv``, ``gdn.scan`` / ``gdn.step``, ``gdn.gate_norm``,
    ``gdn.out_proj``. Returns ``(x, stats, (state, tail))``."""
    cd, f32, eps = cfg.dtype, jnp.float32, cfg.norm_eps
    B, T, _ = x.shape
    hv, dv = cfg.lin_value_heads, cfg.lin_value_dim
    a = rms_norm(x, 1.0 + layers["attn_norm"][i], eps).astype(cd)
    with jax.named_scope("gdn.in_proj"):
        # one stored matrix, two products: a split of ONE product's columns
        # is two copies of it (1.3 GB at 32,768 positions)
        w = layers["w_qkvz"][i].astype(cd)
        qkv, z = a @ w[:, :-hv * dv], a @ w[:, -hv * dv:]
        ba = jnp.dot(a, layers["w_ba"][i].astype(cd),
                     preferred_element_type=f32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(layers["A_log"][i]) * jax.nn.softplus(
            ba[..., hv:] + layers["dt_bias"][i])
    o, state, tail = attend(qkv, g, beta, layers["conv_w"][i])
    with jax.named_scope("gdn.gate_norm"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        y = o * layers["gate_norm"][i] * jax.nn.silu(
            z.astype(f32)).reshape(B, T, hv, dv)
    with jax.named_scope("gdn.out_proj"):
        y = y.reshape(B, T, hv * dv).astype(cd) @ layers["w_out"][i].astype(
            cd)
    out, stats = _routed_half(cfg, x + y.astype(x.dtype), layers, i,
                              stat_axes)
    return out, stats, (state, tail)


def _delta_heads(cfg: LlamaConfig, mixed):
    """``mixed`` [B, T, width] float32 (``[q | k | v]`` behind the
    convolution and ``silu``) as the delta rule's operands in the compute
    type: ``q`` / ``k`` [B, T, value heads, key dim], each l2-normalised
    (eps 1e-6), ``q`` times ``key dim ** -0.5``, a key head repeated for the
    value heads that read it; ``v`` [B, T, value heads, value dim]."""
    B, T, _ = mixed.shape
    hk, dk, hv = cfg.lin_key_heads, cfg.lin_key_dim, cfg.lin_value_heads
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)

    def unit(y):
        y = y.reshape(B, T, hk, dk)
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                 + 1e-6)

    q, k = (jnp.repeat(y, hv // hk, axis=2).astype(cfg.dtype)
            for y in (unit(q) * dk ** -0.5, unit(k)))
    return q, k, v.reshape(B, T, hv, -1).astype(cfg.dtype)


# the XLA path of a long prompt's delta-rule layers goes this many positions
# at a time (where it divides them): the convolution's float32 rows, the heads
# and what the chunked form prepares for all of a stretch's chunks at once are
# a segment's, 1.6 GB at the published widths where 32,768 positions' are
# 5 GB. The kernel's path knows no segment: its state stays in VMEM
DELTA_SEGMENT = 8192


def attend_delta(cfg: LlamaConfig, last, qkv, g, beta, conv_w):
    """:func:`delta_block`'s ``attend`` over the call's own positions from an
    empty state (the full forward and prefill): the causal convolution, the
    heads' l2-norm and the recurrence in chunks. ``last`` (a number, traced
    or not; None: the last position): the last REAL position. The engine
    right-pads a prompt to whole pages, and a causal mask keeps nothing out
    of a recurrence: the positions after ``last`` are identity updates
    (``beta = 0``, ``g = 0``), so the state is the state after ``last``, and
    the tail is taken at ``last`` (rows before the sequence's first are
    zeros, as the convolution pads).

    Two paths, ONE arithmetic (:func:`delta_prefill_path` says which and
    why; counted as kind ``delta``, path ``kernel`` or ``chunks``, where
    :func:`attend_tiles`' kinds are). On a TPU backend one Pallas call,
    forward only (``ops/gdn_prefill.py``, imported here and nowhere else;
    its transpose is the XLA path's): the rows are read as the in-projection
    wrote them, a head's state and the convolution's last rows stay in VMEM,
    and the prompt is ONE piece whatever its pages. On every other backend,
    for what the kernel does not take and as its oracle,
    :func:`_delta_chunks` in XLA (``ops/ssm.py causal_conv``, ``ops/gdn.py
    gated_delta_chunked``, ``DELTA_SEGMENT`` positions at a time). Device
    scopes ``gdn.conv`` / ``gdn.scan``; the kernel runs under ``gdn.scan``,
    and what it is handed has the positions behind ``last`` made identity
    updates HERE (two ``where``s over ``[B, T, value heads]``), which the
    XLA form does for itself a segment.

    The tail is XLA's gather of ``lin_conv - 1`` rows of ``qkv`` at
    ``last`` on both paths (:func:`_delta_tail`)."""
    (B, T, _), K = qkv.shape, conv_w.shape[0]
    path, reason = delta_prefill_path(cfg, qkv, g, beta)
    _note_prefill_attend("delta", qkv, g, 0, path, reason, "chunks")
    if path == "kernel":
        if last is not None:
            live = (jnp.arange(T) <= last)[None, :, None]
            g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
        with jax.named_scope("gdn.scan"):
            o, state = _delta_kernel(qkv, g, beta, conv_w, cfg)
        tail = _delta_tail(last, qkv, K)
    else:
        o, state, tail = _delta_chunks(cfg, last, qkv, g, beta, conv_w)
    return o, state[:, None], tail[:, None]


# --- the delta rule's prefill: its rule and its two paths (attend_delta) --- #


def delta_prefill_path(cfg: LlamaConfig, qkv, g, beta) -> Tuple[str, str]:
    """``(path, reason)`` :func:`attend_delta` takes for these operands in
    this process: ``"kernel"`` on a TPU backend for what
    ``ops/gdn_prefill.py`` takes (``qkv`` in ``cfg.dtype``, ``g`` and
    ``beta`` float32, key and value widths whole lanes, a key head's value
    heads side by side in whole column blocks, positions a multiple of one
    of its row tiles), ``"chunks"`` with what stands in the way otherwise.
    Read from the backend and the shapes alone."""
    platform = jax.default_backend()
    if platform != "tpu":
        return "chunks", f"backend is {platform!r}, not tpu"
    from ray_tpu.ops.gdn_prefill import pick_rows

    hk, dk = cfg.lin_key_heads, cfg.lin_key_dim
    hv, dv = cfg.lin_value_heads, cfg.lin_value_dim
    types = [a.dtype.name for a in (qkv, g, beta)]
    if types != [jnp.dtype(cfg.dtype).name, "float32", "float32"]:
        return "chunks", (f"[q | k | v], g, beta in {types}: not "
                          f"{jnp.dtype(cfg.dtype).name} and float32 twice")
    if dk % 128 or dv % 128:
        return "chunks", (f"key width {dk} or value width {dv} is no "
                          "multiple of 128 lanes")
    if hv % hk or hv // hk > 2 or (2 * hk * dk) % (hv // hk * dv):
        return "chunks", (f"{hv} value heads of {dv} on {hk} key heads of "
                          f"{dk}: a key head's value heads (at most 2, a "
                          "chunk of each side by side in 128 lanes) are no "
                          "whole column block")
    if pick_rows(qkv.shape[1]) is None:
        return "chunks", (f"{qkv.shape[1]} positions are no multiple of a "
                          "row tile of the kernel's")
    return "kernel", "tpu backend"


def _delta_chunks(cfg: LlamaConfig, last, qkv, g, beta, conv_w):
    """:func:`attend_delta` in XLA: the causal convolution (``ops/ssm.py
    causal_conv``, no bias), then the recurrence in chunks (``ops/gdn.py
    gated_delta_chunked``), ``DELTA_SEGMENT`` positions at a time where that
    divides them and in one piece where not: a ``lax.scan`` that carries
    what a decode call would find, the state and the convolution's tail.
    Returns ``(o [B, T, value heads, value dim] float32, state, tail)``."""
    from ray_tpu.ops.gdn import gated_delta_chunked
    from ray_tpu.ops.ssm import causal_conv

    f32 = jnp.float32
    (B, T, _), K = qkv.shape, conv_w.shape[0]
    seg = DELTA_SEGMENT if T % DELTA_SEGMENT == 0 else T

    def segment(carry, xs):
        state, before = carry
        qkv_s, g_s, beta_s, start = xs
        with jax.named_scope("gdn.conv"):
            rows = jnp.concatenate([before, qkv_s], axis=1).astype(f32)
            mixed = jax.nn.silu(causal_conv(rows, conv_w, 0.0)[:, K - 1:])
            q, k, v = _delta_heads(cfg, mixed)
        with jax.named_scope("gdn.scan"):
            o, state = gated_delta_chunked(
                q, k, v, g_s, beta_s, cfg.lin_chunk, state,
                None if last is None else last - start)
        return (state, qkv_s[:, seg - (K - 1):]), o

    def segments(a):  # [B, T, ...] -> [T / seg, B, seg, ...]
        return jnp.moveaxis(a.reshape(B, -1, seg, *a.shape[2:]), 1, 0)

    hv = cfg.lin_value_heads
    start = (jnp.zeros((B, hv, cfg.lin_key_dim, cfg.lin_value_dim), f32),
             jnp.zeros((B, K - 1, qkv.shape[-1]), qkv.dtype))
    (state, _), o = jax.lax.scan(
        segment, start, (segments(qkv), segments(g), segments(beta),
                         jnp.arange(0, T, seg, dtype=jnp.int32)))
    tail = _delta_tail(last, qkv, K)
    return jnp.moveaxis(o, 0, 1).reshape(B, T, hv, -1), state, tail


def _delta_tail(last, qkv, K: int):
    """The convolution's tail at ``last`` (None: the last position): the
    ``K - 1`` rows of ``qkv`` [B, T, width] that end there, zeros before the
    sequence's first."""
    from ray_tpu.ops.ssm import conv_tail

    with jax.named_scope("gdn.conv"):
        return conv_tail(qkv, K, last)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _delta_kernel(qkv, g, beta, conv_w, cfg):
    """``g`` and ``beta`` with the positions behind the last real one
    already 0: from an empty state and tail, as :func:`_delta_chunks`."""
    from ray_tpu.ops.gdn_prefill import gdn_prefill

    B, K = qkv.shape[0], conv_w.shape[0]
    # interpreted where a test has steered a CPU process onto this path
    return gdn_prefill(
        qkv, conv_w, g, beta,
        jnp.zeros((B, cfg.lin_value_heads, cfg.lin_key_dim,
                   cfg.lin_value_dim), jnp.float32),
        jnp.zeros((B, K - 1, qkv.shape[-1]), qkv.dtype),
        key_heads=cfg.lin_key_heads, key_dim=cfg.lin_key_dim,
        interpret=jax.default_backend() != "tpu")


def _delta_kernel_fwd(qkv, g, beta, conv_w, cfg):
    return _delta_kernel(qkv, g, beta, conv_w, cfg), (qkv, g, beta, conv_w)


def _delta_kernel_bwd(cfg, operands, cotangent):
    # forward only: the kernel keeps nothing for a backward pass, so its
    # transpose is the XLA path's, run again from the operands
    return jax.vjp(lambda *a: _delta_chunks(cfg, None, *a)[:2],
                   *operands)[1](cotangent)


_delta_kernel.defvjp(_delta_kernel_fwd, _delta_kernel_bwd)


def _attend_state(cfg: LlamaConfig, call, l, mine, qkv, g, beta, conv_w):
    """A decode call's token through its layer's kept state and tail
    (``mine``: the views ``[1, *row]`` of the page that holds position
    ``pos - 1``, the batch's one sequence): the convolution's one new row,
    one step of the recurrence (``ops/gdn.py gated_delta_step``), and what
    the page that holds ``pos`` keeps. A sequence's first position starts
    from zeros whatever its page held."""
    from ray_tpu.ops.gdn import gated_delta_step

    f32 = jnp.float32
    state, tail = (jnp.where(call.pos > 0, a, 0) for a in mine)
    with jax.named_scope("gdn.conv"):
        rows = jnp.concatenate([tail, qkv.astype(f32)], axis=1)
        mixed = jax.nn.silu(jnp.sum(rows * conv_w, axis=1, keepdims=True))
        q, k, v = _delta_heads(cfg, mixed)
    with jax.named_scope("gdn.step"):
        o, state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], state)
    return o[:, None], state[:, None], rows[:, None, 1:]


def gated_block(cfg: LlamaConfig, x, layers, i, positions, attend,
                stat_axes=()):
    """THE gated-attention block (kind ``"A"``), for the full forward and
    for the serving programs (``N`` zero-centred)::

        a   = N(x)
        [q | gate] = a Wq  a head;   k, v = a Wk, a Wv
        q, k = N over each head's width (one gain for q, one for k), the
               first partial_rotary_factor of it rotated (half-split pairs)
        o   = softmax(q k^T / sqrt(head_dim), causal) v        GQA
        h   = x + (o * sigmoid(gate)) Wo
        out = h + MoE(N(h))                       (:func:`_routed_half`)

    ``attend(q, k, v)`` as :func:`decoder_block` takes it, under the device
    scope ``attn.gated``. Returns ``(x, stats, (k, v))``: ``k`` normed and
    rotated, what a cache keeps."""
    cd, hd, eps = cfg.dtype, cfg.head_dim, cfg.norm_eps
    B, T, _ = x.shape
    p = {w: layers[w][i] for w in ("wq", "wk", "wv", "wo", "q_norm",
                                   "k_norm")}
    a = rms_norm(x, 1.0 + layers["attn_norm"][i], eps).astype(cd)
    q, gate = jnp.split((a @ p["wq"].astype(cd)).reshape(B, T, -1, 2 * hd),
                        2, axis=-1)
    q = rms_norm(q, 1.0 + p["q_norm"], eps)
    k = rms_norm((a @ p["wk"].astype(cd)).reshape(B, T, -1, hd),
                 1.0 + p["k_norm"], eps)
    v = (a @ p["wv"].astype(cd)).reshape(B, T, -1, hd)
    rot = round(hd * cfg.partial_rotary_factor)
    q, k = (jnp.concatenate([mrope_rotate(y[..., :rot], positions,
                                          cfg.rope_theta), y[..., rot:]],
                            axis=-1) for y in (q, k))
    with jax.named_scope("attn.gated"):
        o = attend(q, k, v)
    o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
         ).astype(cd).reshape(B, T, -1)
    out, stats = _routed_half(
        cfg, x + (o @ p["wo"].astype(cd)).astype(x.dtype), layers, i,
        stat_axes)
    return out, stats, (k, v)


def parallel_block(cfg: LlamaConfig, kind: str, x, layers, i, positions,
                   attend, stat_axes=()):
    """THE parallel block of the kinds ``"P"`` and ``"R"``, for the full
    forward and for the serving programs (``LN``: :func:`layer_norm`, the
    mean subtracted, no bias)::

        a   = LN(x)                       ONE norm a layer
        A   = Attention(a) Wo             "R": q, k rotated in pairs (2i,
                                          2i + 1), over the last cfg.window
                                          positions; "P": not rotated, over
                                          every earlier one
        R   = sum_e w_e SwiGLU_e(a)       w = s / sum of the chosen s, s =
                                          sigmoid(a Wr) in float32; the held
                                          experts' part of it
        S   = 1/n sum_j SwiGLU_j(a)       the n shared experts, as ONE
                                          expert n times as wide
        out = x + A + R + S               attention and MLP read the SAME a

    ``layers``: the ``parallel`` stack ``[L, ...]`` both kinds share, ``i``:
    which layer (a number, or traced in a scan); every matrix is cut out
    ``[i]`` where it is used but the routed experts, which go down whole with
    ``layer=i`` (:func:`shortcut_layer` says why). The stream keeps the type
    it comes in (float32 in the serving programs), the router reads ``a`` in
    that type and every product its rounding to ``cfg.dtype``.
    ``attend(q, k, v)`` as :func:`decoder_block` takes it. Device scopes
    ``par.norm``, ``par.qkv``, ``attn.window`` / ``attn.full``, ``par.out``,
    ``moe.route`` / ``.dispatch`` / ``.experts`` / ``.combine`` and
    ``moe.shared``. Returns ``(x, stats, (k, v))``: ``k`` rotated for
    ``"R"`` and as projected for ``"P"``."""
    from ray_tpu.ops.moe import routed_mlp

    cd, hd = cfg.dtype, cfg.head_dim
    B, T, _ = x.shape
    with jax.named_scope("par.norm"):
        a = layer_norm(x, layers["norm"][i], cfg.norm_eps)
        h = a.astype(cd)
    with jax.named_scope("moe.shared"):
        g = jax.nn.silu((h @ layers["shared_gate"][i].astype(cd)
                         ).astype(jnp.float32))
        u = h @ layers["shared_up"][i].astype(cd)
        s = ((g * u).astype(cd) @ layers["shared_down"][i].astype(cd)
             ).astype(jnp.float32) / cfg.shared_experts
    # ONE half at a time: both read ``h`` and nothing else ties them, and a
    # scheduler that runs them side by side holds the shared experts' gate
    # and up ([T, n * width] each) beside q, the attention's output and their
    # transposes: 5.0 GB of temporaries at 16,384 positions against 3.0 in
    # turn (3.2 without the second barrier below; compiled for a described
    # v5e, PR 54)
    out, h = jax.lax.optimization_barrier((x + s.astype(x.dtype), h))
    with jax.named_scope("par.qkv"):
        q, k, v = _qkv(cfg, {w: layers[w][i] for w in ("wq", "wk", "wv")}, h,
                       cfg.n_heads, cfg.n_kv_heads, positions,
                       rope=kind == "R")
    with jax.named_scope("attn.window" if kind == "R" else "attn.full"):
        o = attend(q, k, v)
    with jax.named_scope("par.out"):
        out = out + (o.reshape(B, T, cfg.n_heads * hd)
                     @ layers["wo"][i].astype(cd)).astype(x.dtype)
    out, h = jax.lax.optimization_barrier((out, h))
    wide = cfg.router_experts or cfg.num_experts
    # the float32 sum goes onto the stream as it is
    y, stats = routed_mlp(
        h, layers["router"][i], layers["w_gate"], layers["w_up"],
        layers["w_down"], top_k=cfg.experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob, stat_axes=stat_axes,
        scoring=cfg.router_scoring,
        held=((cfg.first_expert, cfg.num_experts)
              if wide != cfg.num_experts else None),
        layer=i, router_input=a)
    return out + y.astype(x.dtype), stats, (k, v)


def attend_parallel_tiles(cfg: LlamaConfig, kind: str, q, k, v):
    """:func:`parallel_block`'s ``attend`` over the call's own positions (the
    full forward and prefill): :func:`attend_tiles` with the band for
    ``"R"``, counted under kinds of their own."""
    return attend_tiles(q, k, v, cfg.dtype,
                        window=cfg.window if kind == "R" else 0,
                        kind="parallel_window" if kind == "R"
                        else "parallel_full")


def hybrid_block(cfg: LlamaConfig, kind: str, x, layers, i, positions,
                 attend, stat_axes=()):
    """THE state-space hybrid's whole block of the kinds ``"H"`` and
    ``"N"``, for the full forward and for the serving programs (``r``:
    ``cfg.residual_multiplier``; ``N``: RMSNorm, float32 statistics)::

        a   = N(x; attn_norm)
        "H" [z | xBC | dt] = a W_in                  ops/ssm.py project_in
            y, state, tail = attend(xBC, dt, the layer's conv_w, conv_b,
                                    dt_bias, A_log, D)
            m = (N_d_inner(y * silu(z)) * gate_norm) W_out        gate_out
        "N" q, k, v = a Wq, a Wk, a Wv               no bias, NO rotation
            m = attend(q, k, v) Wo      softmax(attention_multiplier q k^T)
        h   = x + r m
        out = h + r SwiGLU(N(h; mlp_norm))           the dense MLP

    ``attend`` is all that knows where the call stands in its sequence. For
    ``"H"``: the causal convolution, ``silu``, the split and the scan over
    the call's own positions from an empty state (:func:`attend_ssm`:
    ``ops/ssm.py scan_positions``) or one token on from a kept state and
    tail (:func:`_attend_ssm_state`: ``ops/ssm.py step``); it returns ``y``
    [B, T, H, P] float32 and what a cache keeps A SEQUENCE: the state after
    the last real position ``[B, 1, H, P, N]`` float32 and the convolution's
    tail, the last ``ssm_conv - 1`` rows of ``xBC`` ``[B, 1, ssm_conv - 1,
    width]``. For ``"N"``: ``attend(q, k, v)`` as :func:`decoder_block`
    takes it, but ``q`` comes in float32 and UNSCALED: the attend scales it
    for the width ITS product divides by and rounds it once
    (:func:`_hybrid_query`). ``layers``: the kind's stack ``[L, ...]``,
    ``i``: which layer (a number, or traced in a scan). The stream keeps the
    type it comes in (float32 in the serving programs). Device scopes
    ``ssm.in_proj``, ``ssm.conv``, ``ssm.scan`` / ``ssm.step``,
    ``ssm.gate_norm``, ``ssm.out_proj`` (ops/ssm.py's, the trainer's names);
    ``hyb.qkv``, ``attn.full``, ``hyb.out``; ``hyb.mlp``. Returns ``(x, {},
    (state, tail))`` or ``(x, {}, (k, v))``."""
    from ray_tpu.ops import ssm

    cd, eps, r = cfg.dtype, cfg.norm_eps, cfg.residual_multiplier
    B, T, _ = x.shape
    a = rms_norm(x, layers["attn_norm"][i], eps).astype(cd)
    if kind == "H":
        z, xbc, dt = ssm.project_in(a, layers["w_in"][i], cfg.ssm_heads,
                                    cfg.ssm_heads * cfg.ssm_head_dim)
        y, *kept = attend(xbc, dt, {w: layers[w][i] for w in (
            "conv_w", "conv_b", "dt_bias", "A_log", "D")})
        m = ssm.gate_out(y, z, layers["gate_norm"][i], layers["w_out"][i],
                         groups=cfg.ssm_groups, eps=eps)
    else:
        hd = cfg.head_dim
        with jax.named_scope("hyb.qkv"):
            q = jnp.dot(a, layers["wq"][i].astype(cd),
                        preferred_element_type=jnp.float32
                        ).reshape(B, T, -1, hd)
            k, v = ((a @ layers[w][i].astype(cd)).reshape(B, T, -1, hd)
                    for w in ("wk", "wv"))
        with jax.named_scope("attn.full"):
            o = attend(q, k, v)
        with jax.named_scope("hyb.out"):
            m = o.reshape(B, T, -1) @ layers["wo"][i].astype(cd)
        kept = (k, v)
    h = x + r * m.astype(x.dtype)
    with jax.named_scope("hyb.mlp"):
        u = rms_norm(h, layers["mlp_norm"][i], eps).astype(cd)
        y = _dense_mlp(cfg, {w: layers[w][i] for w in (
            "w_gate", "w_up", "w_down")}, u)
    return h + r * y.astype(x.dtype), {}, tuple(kept)


def ssm_prefill_path(cfg: LlamaConfig, xbc, dt) -> Tuple[str, str]:
    """``(path, reason)`` :func:`attend_ssm` takes for these operands in
    this process (:func:`delta_prefill_path`'s sibling): ``"kernel"`` on a
    TPU backend for what ``ops/ssm_prefill.py`` takes (``xbc`` in
    ``cfg.dtype`` and ``dt`` float32; ONE group; the state's width and
    ``d_inner + 2 N`` whole 128-lane tiles; heads 64 or a multiple of 128
    wide, eight of them a step of its loop; positions a multiple of its row
    tile, one chunk of 128 or 256), ``"chunks"`` with what stands in the way
    otherwise. Read from the backend and the shapes alone."""
    platform = jax.default_backend()
    if platform != "tpu":
        return "chunks", f"backend is {platform!r}, not tpu"
    from ray_tpu.ops.ssm_prefill import pick_rows

    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    types = [a.dtype.name for a in (xbc, dt)]
    if types != [jnp.dtype(cfg.dtype).name, "float32"]:
        return "chunks", (f"xBC, dt in {types}: not "
                          f"{jnp.dtype(cfg.dtype).name} and float32")
    if G != 1:
        return "chunks", (f"{G} groups: the kernel reads ONE B and C a row "
                          "tile for all heads")
    if N % 128 or (H * P + 2 * G * N) % 128:
        return "chunks", (f"state width {N} or xBC's {H * P + 2 * G * N} "
                          "columns are no whole 128-lane tiles")
    if not (P == 64 or P % 128 == 0) or H % 8:
        return "chunks", (f"{H} heads of {P}: not 64 or a multiple of 128 "
                          "wide (two heads, or one, fill a 128-lane block), "
                          "or no multiple of 8 heads")
    if pick_rows(xbc.shape[1], cfg.ssm_chunk) is None:
        return "chunks", (f"{xbc.shape[1]} positions in chunks of "
                          f"{cfg.ssm_chunk} are no multiple of a row tile "
                          "of the kernel's (one chunk of 128 or 256)")
    return "kernel", "tpu backend"


def attend_ssm(cfg: LlamaConfig, last, xbc, dt, p):
    """:func:`hybrid_block`'s ``"H"`` ``attend`` over the call's own
    positions from an empty state (the full forward and prefill), the
    positions behind ``last`` identity updates, the tail taken at ``last``.

    Two paths, ONE arithmetic (:func:`ssm_prefill_path` says which and why;
    counted as kind ``ssm``, path ``kernel`` or ``chunks``, where
    :func:`attend_tiles`' kinds are). On a TPU backend one Pallas call,
    forward only (``ops/ssm_prefill.py``, imported here and nowhere else;
    its transpose is the XLA path's): the rows are read as the in-projection
    wrote them, the state and the convolution's last rows stay in VMEM, and
    the prompt is ONE piece whatever its pages. On every other backend, for
    what the kernel does not take and as its oracle, ``ops/ssm.py
    scan_positions`` in XLA, ``SEGMENT`` positions at a time where a prompt
    is longer. Device scopes ``ssm.conv`` / ``ssm.scan``; the kernel runs
    under ``ssm.scan``. The tail is XLA's gather of ``ssm_conv - 1`` rows of
    ``xbc`` at ``last`` on both paths (``ops/ssm.py conv_tail``)."""
    from ray_tpu.ops import ssm

    path, reason = ssm_prefill_path(cfg, xbc, dt)
    _note_prefill_attend("ssm", xbc, dt, 0, path, reason, "chunks")
    dims = dict(heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
                chunk=cfg.ssm_chunk)
    if path == "kernel":
        from ray_tpu.ops.ssm_prefill import ssm_prefill

        (B, _, width), K = xbc.shape, p["conv_w"].shape[0]
        with jax.named_scope("ssm.scan"):
            # interpreted where a test has steered a CPU process onto this
            # path
            y, state = ssm_prefill(
                xbc, dt, p,
                jnp.zeros((B, cfg.ssm_heads, cfg.ssm_head_dim,
                           cfg.ssm_state), jnp.float32),
                jnp.zeros((B, K - 1, width), xbc.dtype), last,
                interpret=jax.default_backend() != "tpu", **dims)
        with jax.named_scope("ssm.conv"):
            tail = ssm.conv_tail(xbc, K, last)
    else:
        y, state, tail = ssm.scan_positions(
            xbc, dt, p, last=last, segment=ssm.SEGMENT,
            groups=cfg.ssm_groups, state=cfg.ssm_state, **dims)
    return y, state[:, None], tail[:, None]


def _attend_ssm_state(cfg: LlamaConfig, call, l, mine, xbc, dt, p):
    """A decode call's token through its layer's kept state and tail
    (``mine``: the views ``[1, *row]`` of the page that holds position ``pos
    - 1``, the batch's one sequence): ``ops/ssm.py step``, and what the page
    that holds ``pos`` keeps. A sequence's first position starts from zeros
    whatever its page held."""
    from ray_tpu.ops import ssm

    state, tail = (jnp.where(call.pos > 0, a, 0) for a in mine)
    y, state, tail = ssm.step(xbc, dt, p, state, tail, groups=cfg.ssm_groups)
    return y, state[:, None], tail[:, None]


def _hybrid_query(cfg: LlamaConfig, q, width: int):
    """An ``"N"`` layer's float32 query for a product that divides its
    scores by ``sqrt(width)``: times what makes the scale
    ``cfg.attention_multiplier`` (0: ``1 / sqrt(head_dim)``), rounded to
    ``cfg.dtype`` ONCE. At the published 1/64 on heads 64 wide that is 0.125
    in front of a product that divides by 8: exact."""
    scale = cfg.attention_multiplier or 1.0 / math.sqrt(cfg.head_dim)
    return (q * (scale * math.sqrt(width))).astype(cfg.dtype)


def attend_hybrid_tiles(cfg: LlamaConfig, q, k, v):
    """:func:`hybrid_block`'s ``"N"`` ``attend`` over the call's own
    positions (the full forward and prefill): :func:`attend_tiles`, counted
    under a kind of its own. A head narrower than the 128 lanes prefill's
    kernel takes is filled up with zeros on every backend (they add nothing
    to a score and their value columns are cut off again), so that a TPU
    backend runs the kernel: the scale then counts the filled width."""
    D = q.shape[-1]
    fill = [(0, 0)] * 3 + [(0, -D % 128)]
    q = _hybrid_query(cfg, q, D + fill[-1][1])
    o = attend_tiles(*(jnp.pad(a, fill) for a in (q, k, v)), cfg.dtype,
                     kind="hybrid")
    return o[..., :D]


# --- the decoder-hybrid-decoder's blocks (arXiv:2507.06607) ---------------- #


class Remembering(NamedTuple):
    """The serving stream of the kinds that read what ANOTHER layer made
    (:data:`MEMORY_KINDS`): ``stream`` ``[B, T, dim]`` and, beside it, the
    newest ``memory`` (an ``"m"`` layer's scan output ``Y``, ``[B, T,
    ssm_expand x dim]`` float32, before its gate, the skip ``D u`` included:
    what a ``"g"`` layer gates AT ITS OWN POSITION) and the newest ``keys``
    and ``values`` (an ``"f"`` layer's, ``[B, T, n_kv_heads, head_dim]``
    each: what a ``"c"`` layer attends). A pytree as
    :class:`Selecting` is: the walker's scans carry it, the programs make it
    behind the embedding (:func:`remembering_stream`) and drop it in front of
    the head (:func:`stream_of`). BEHIND THE CUT of a prefill
    (:func:`cut_stream`) ``stream`` and ``memory`` hold ONE position, the
    last real one, and ``keys`` / ``values`` every position of the call."""
    stream: Any
    memory: Any
    keys: Any
    values: Any


def remembering_stream(cfg: LlamaConfig, x):
    """The stream ``x`` as the serving programs carry it through a stack of
    :data:`MEMORY_KINDS`: :class:`Remembering`, nothing remembered (the
    layers that write stand in front of those that read: ``LlamaConfig``
    holds the pattern to that). Any other stack: ``x``."""
    if not set(cfg.kinds) & set(MEMORY_KINDS):
        return x
    B, T, _ = x.shape
    rows = jnp.zeros((B, T, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    return Remembering(x, jnp.zeros((B, T, cfg.s6_inner), jnp.float32),
                       rows, rows)


def cut_stream(x, last):
    """A prefill's stream cut to position ``last``, the one the head reads:
    every leaf ``[B, T, ...]`` becomes ``[B, 1, ...]`` but what a layer
    behind the cut reads of ALL positions (:class:`Remembering`'s keys and
    values)."""
    def one(a):
        return jax.lax.dynamic_slice_in_dim(a, last, 1, axis=1)

    if isinstance(x, Remembering):
        return x._replace(stream=one(x.stream), memory=one(x.memory))
    return jax.tree.map(one, x)


def memory_block(cfg: LlamaConfig, kind: str, x, layers, i, positions,
                 attend, stat_axes=()):
    """THE decoder-hybrid-decoder's whole block of the kinds ``"m"``,
    ``"w"``, ``"f"``, ``"g"``, ``"c"``, for the serving programs. ``x``:
    :class:`Remembering` (``LN``: the mean-subtracting LayerNorm with gain
    AND bias, float32 statistics)::

        a   = LN(x; attn_norm)
        "m" [u | z] = a W_in                            ops/s6.py project_in
            Y, state, tail = attend(u, the layer's leaves)   Y IS THE MEMORY
            mix = (Y * silu(z)) W_out
        "g" mix = (silu(a Wg_in) * memory) Wg_out   memory AT THIS POSITION
        "w" / "f" / "c": q = a Wq + bq           n_heads / 2 PAIRS (q1, q2)
            "w" / "f": [k1 | k2 | v] = a Wkv + bkv     n_kv_heads / 2 pairs;
                a pair's values ONE head 2 head_dim wide; NO rotation; an
                "f" layer's ARE the keys and values a "c" layer reads
            o1, o2 = attend(q, keys, values):  o_j = softmax_j(q_j k_j^T /
                sqrt(head_dim)) v over the visible keys ("w": t - window < s
                <= t; "f", "c": s <= t); query pair p reads key pair p //
                (n_heads / n_kv_heads)
            lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6
                exp(-0.3 depth): a per-layer VALUE (``cfg.lambda_init``
                indexed by ``i``, which a scan traces)
            mix = (RMSNorm_{2 head_dim}(o1 - lam o2) * sub_norm * (1 - lam0))
                  Wo + bo
        h   = x + mix
        out = h + SwiGLU(LN(h; mlp_norm))                   the dense MLP

    ``attend`` is all that knows where the call stands in its sequence
    (:func:`attend_s6` / :func:`_attend_s6_state`; :func:`attend_diff_tiles`,
    :func:`attend_cross` / :func:`_attend_diff_cached`). ``layers``: the
    kind's stack ``[L, ...]`` (``"w"`` and ``"f"`` share one), ``i``: which
    layer. Device scopes ``s6.*`` (ops/s6.py), ``gmu.gate``, ``attn.qkv``,
    ``attn.diff_window`` / ``attn.diff_full`` / ``attn.cross``,
    ``attn.diff_norm``, ``attn.out``, ``mem.mlp``. Returns ``(Remembering,
    {}, rows)``: ``(state, tail)``, ``(keys, values)`` or ``()``: a ``"g"``
    or ``"c"`` layer keeps NOTHING."""
    from ray_tpu.ops import s6

    f32, cd, eps = jnp.float32, cfg.dtype, cfg.norm_eps
    x, memory, keys, values = x
    B, T, _ = x.shape
    p = {w: a[i] for w, a in layers.items()}
    a = (layer_norm(x, p["attn_norm"], eps) + p["attn_norm_b"]).astype(cd)
    kept = ()
    if kind == "m":
        u, z = s6.project_in(a, p["w_in"])
        memory, *kept = attend(u, p)
        mix = s6.gate_out(memory, z, p["w_out"])
    elif kind == "g":
        with jax.named_scope("gmu.gate"):
            gate = jax.nn.silu(jnp.dot(a, p["wg_in"].astype(cd),
                                       preferred_element_type=f32))
            mix = (gate * memory).astype(cd) @ p["wg_out"].astype(cd)
    else:
        hd = cfg.head_dim
        with jax.named_scope("attn.qkv"):
            q = (jnp.dot(a, p["wq"].astype(cd), preferred_element_type=f32)
                 + p["bq"]).reshape(B, T, -1, hd)
            if kind != "c":
                kv = (jnp.dot(a, p["wkv"].astype(cd),
                              preferred_element_type=f32)
                      + p["bkv"]).astype(cd)
                kept = [half.reshape(B, T, -1, hd)
                        for half in jnp.split(kv, 2, -1)]
            own = kept or (keys.astype(cd), values.astype(cd))
            if kind == "f":  # handed on in the type the stream carries them
                # in (float32 that holds the compute type's values, as the
                # stores do)
                keys, values = (new.astype(old.dtype) for new, old in zip(
                    kept, (keys, values)))
        with jax.named_scope({"w": "attn.diff_window", "f": "attn.diff_full",
                              "c": "attn.cross"}[kind]):
            o1, o2 = attend(q, *own)
        with jax.named_scope("attn.diff_norm"):
            lam0 = jnp.asarray(cfg.lambda_init("c" if kind == "c" else "wf"),
                               f32)[i]
            lq1, lk1, lq2, lk2 = p["lam"]
            lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
                   + lam0)
            o = o1.astype(f32) - lam * o2.astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
            o = o * (p["sub_norm"] * (1.0 - lam0))
        with jax.named_scope("attn.out"):
            mix = jnp.dot(o.astype(cd).reshape(B, T, -1), p["wo"].astype(cd),
                          preferred_element_type=f32) + p["bo"]
    h = x + mix.astype(x.dtype)
    with jax.named_scope("mem.mlp"):
        m = (layer_norm(h, p["mlp_norm"], eps) + p["mlp_norm_b"]).astype(cd)
        y = _dense_mlp(cfg, p, m)
    return (Remembering(h + y.astype(x.dtype), memory, keys, values), {},
            tuple(kept))


def s6_prefill_path(cfg: LlamaConfig, u) -> Tuple[str, str]:
    """``(path, reason)`` :func:`attend_s6` takes for these operands in this
    process (:func:`ssm_prefill_path`'s sibling): ``"kernel"`` on a TPU
    backend for what ``ops/s6_prefill.py`` takes (channels in whole blocks of
    1,024, positions a multiple of its row tile), ``"scan"`` with what
    stands in the way otherwise. Read from the backend and the shapes
    alone."""
    platform = jax.default_backend()
    if platform != "tpu":
        return "scan", f"backend is {platform!r}, not tpu"
    from ray_tpu.ops.s6_prefill import CHANNELS, pick_rows

    if u.shape[-1] % CHANNELS:
        return "scan", (f"{u.shape[-1]} channels are no whole blocks of "
                        f"{CHANNELS}")
    if pick_rows(u.shape[1]) is None:
        return "scan", (f"{u.shape[1]} positions are no multiple of a row "
                        "tile of the kernel's")
    return "kernel", "tpu backend"


def attend_s6(cfg: LlamaConfig, last, u, p):
    """:func:`memory_block`'s ``"m"`` ``attend`` over the call's own
    positions from an empty state (prefill), the positions behind ``last``
    identity updates, the tail taken at ``last``: the convolution and
    ``silu`` (``s6.conv``), the projections to ``r W_dt``, ``B`` and ``C``
    (``s6.x_proj``), then the scan (``s6.scan``).

    Two paths, ONE arithmetic (:func:`s6_prefill_path` says which and why;
    counted as kind ``s6``, path ``kernel`` or ``scan``, where
    :func:`attend_tiles`' kinds are). On a TPU backend one Pallas call,
    forward only (``ops/s6_prefill.py``, imported here and nowhere else):
    ``softplus``, the decays' exponentials, the update, the read-out and the
    skip in one pass over the prompt, the ``[channels, states]`` state in
    registers, no ``[T, channels, states]`` array anywhere. On every other
    backend, for what the kernel does not take and as its oracle, ``ops/
    s6.py scan``: a ``lax.scan`` a position. Returns ``(Y [B, T, D]
    float32, state [B, 1, D, N], tail [B, 1, K - 1, D])``."""
    from ray_tpu.ops import s6
    from ray_tpu.ops.ssm import conv_tail

    B, T, D = u.shape
    with jax.named_scope("s6.conv"):
        tail = conv_tail(u, p["conv_w"].shape[0], last)
    mixed = s6.convolve(u, p)
    r, b_in, c_in = s6.select(mixed, p, cfg.dtype)
    path, reason = s6_prefill_path(cfg, u)
    _note_prefill_attend("s6", u, b_in, 0, path, reason, "scan")
    with jax.named_scope("s6.scan"):
        if path == "kernel":
            from ray_tpu.ops.s6_prefill import s6_prefill

            # interpreted where a test has steered a CPU process onto this
            # path
            y, state = s6_prefill(
                mixed, r, b_in, c_in, p,
                jnp.zeros((B, D, cfg.ssm_state), jnp.float32),
                T - 1 if last is None else last,
                interpret=jax.default_backend() != "tpu")
        else:
            y, state = s6.scan(mixed, r, b_in, c_in, p, None, last)
    return y, state[:, None], tail[:, None]


def _attend_s6_state(cfg: LlamaConfig, call, l, mine, u, p):
    """A decode call's token through its ``"m"`` layer's kept state and
    tail (``mine``: the views ``[1, *row]`` of the page that holds position
    ``pos - 1``): ``ops/s6.py step``, and what the page that holds ``pos``
    keeps. A sequence's first position starts from zeros whatever its page
    held."""
    from ray_tpu.ops import s6

    state, tail = (jnp.where(call.pos > 0, a, 0) for a in mine)
    y, state, tail = s6.step(u, p, state, tail, cfg.dtype)
    return y, state[:, None], tail[:, None]


def _attends_nothing(*a):
    """The ``attend`` of a kind whose block calls none (``"g"``)."""
    raise AssertionError("a gated memory unit attends nothing")


def _diff_halves(cfg: LlamaConfig, q, k, v):
    """Differential attention's operands as their two maps take them:
    ``((q1, k1), (q2, k2)), v``: the pairs' first and second heads (the
    leaves' columns are ``[first heads | second heads]``) and a key pair's
    values as ONE head ``2 head_dim`` wide. ``q`` ``[.., n_heads, head_dim]``,
    ``k`` / ``v`` ``[.., n_kv_heads, head_dim]``."""
    return (zip(jnp.split(q, 2, axis=-2), jnp.split(k, 2, axis=-2)),
            v.reshape(*v.shape[:-2], -1, 2 * cfg.head_dim))


def attend_diff_tiles(cfg: LlamaConfig, kind: str, q, k, v):
    """:func:`memory_block`'s ``"w"`` / ``"f"`` ``attend`` over the call's
    own positions (prefill; a ``"c"`` layer's too where every position
    brings a query): differential attention's TWO softmax maps as two calls
    of :func:`attend_tiles`, ``(q1, k1)`` and ``(q2, k2)``, each against the
    pair's ONE value ``2 head_dim`` wide, counted under ``diff_window`` /
    ``diff_full`` / ``diff_cross``. The subtraction, the norm and the scale
    are the block's (XLA). ``q`` comes in float32: it is scaled for the width
    the product divides by and rounded ONCE (:func:`attend_hybrid_tiles`'
    fill: a 64-wide score is filled up to the 128 lanes prefill's kernel
    takes, which makes score and value one width). Returns ``(o1, o2)``,
    ``[B, T, n_heads / 2, 2 head_dim]`` each."""
    D = q.shape[-1]
    fill = [(0, 0)] * 3 + [(0, -D % 128)]
    scale = math.sqrt((D + fill[-1][1]) / D)
    name = {"w": "diff_window", "f": "diff_full", "c": "diff_cross"}[kind]
    maps, v = _diff_halves(cfg, q, k, v)
    return [attend_tiles(jnp.pad((qi * scale).astype(cfg.dtype), fill),
                         jnp.pad(ki, fill), v, cfg.dtype,
                         window=cfg.window if kind == "w" else 0, kind=name)
            for qi, ki in maps]


def attend_cross(cfg: LlamaConfig, last, q, k, v):
    """:func:`memory_block`'s ``"c"`` ``attend`` in a prefill. BEHIND THE
    CUT (``q`` holds ONE position, ``last``; ``k`` / ``v`` every position's,
    made by the ``"f"`` layer in this same call): two softmax maps of one
    query over the keys ``s <= last``, float32, in XLA. Where every position
    brings its query (a stack run without the cut): :func:`attend_diff_tiles`.
    Read from the shapes."""
    if q.shape[1] == k.shape[1]:
        return attend_diff_tiles(cfg, "c", q, k, v)
    f32 = jnp.float32
    B, T, hd = k.shape[0], k.shape[1], cfg.head_dim
    visible = jnp.arange(T) <= last
    maps, v = _diff_halves(cfg, q.astype(cfg.dtype), k, v)
    out = []
    for qi, ki in maps:
        qg = qi[:, 0].reshape(B, ki.shape[2], -1, hd).astype(f32)
        s = jnp.einsum("bgrd,btgd->bgrt", qg, ki.astype(f32)) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(visible, s, -1e30), axis=-1)
        out.append(jnp.einsum("bgrt,btge->bgre", w, v.astype(f32)).reshape(
            B, 1, -1, 2 * hd).astype(cfg.dtype))
    return out


def _attend_diff_cached(cfg: LlamaConfig, tag: str, k_cache, v_cache, length,
                        q, kk, vv, lowest=None):
    """A decode call's token through differential attention:
    :func:`_attend_grouped` TWICE over the same views (``k_cache`` /
    ``v_cache`` ``[Tpad, n_kv_heads, head_dim]``: slots for ``"w"``, pages
    for ``"f"`` and ``"c"``), the first heads' keys and the second heads',
    each against the pairs' values ``2 head_dim`` wide. Returns ``(o1,
    o2)``."""
    maps, v_cache = _diff_halves(cfg, q.astype(cfg.dtype), k_cache, v_cache)
    own, vv = _diff_halves(cfg, q, kk, vv)  # the token's own key and value
    return [_attend_grouped(cfg, tag, ki, v_cache, length, qi, kki, vv,
                            lowest=lowest)
            for (qi, ki), (_, kki) in zip(maps, own)]


def pattern_layer(cfg: LlamaConfig, kind: str, attend, x, p, stat_axes=()):
    """One layer of a patterned stack (``cfg.layer_pattern``). A kind the
    decode engine serves (a row of :data:`SERVED`: ``"S"``, ``"F"`` /
    ``"W"``, ``"I"``) is that row's block over that row's attention of the
    call's own positions, whatever ``attend`` is: the trainer's flash kernel
    has no window and selects nothing (prefill's forward-only kernels on a
    TPU backend, XLA tiles elsewhere). A latent block (``"L"`` / ``"G"``,
    :func:`latent_block`) is a whole block over the CALLER's ``attend``: a
    train step's flash kernel, so that its backward is the kernels' own and
    not the tile loop's transpose that prefill's forward-only kernel would
    leave it. Every other kind is a half of the
    block: ``x + f(RMSNorm(x))`` with ``f`` the Mamba-2 mixer (``"M"``,
    :func:`ray_tpu.ops.ssm.mamba2_mixer`), the routed
    feed-forward (``"E"``, :func:`_mlp_half`) or attention (``"*"``,
    :func:`_attn_half` over ``attend``). ``p``: this layer's weights, of its
    kind. Returns ``(x, stats)``, ``stats`` ``{}`` but for ``"E"`` and the
    served kinds."""
    if kind in LATENT_KINDS:  # a whole block, over the CALLER's attend
        return latent_block(cfg, kind, attend, x, p, stat_axes)
    if kind in DSA_KINDS:
        raise NotImplementedError(
            f"the full forward takes no {kind!r} layer yet: only the "
            f"serving programs carry a selection from the layer that makes "
            f"it to the layers that read it (prefill_with_cache)")
    if kind in MEMORY_KINDS:
        raise NotImplementedError(
            f"the full forward takes no {kind!r} layer yet: only the "
            f"serving programs carry a memory and a layer's keys and values "
            f"to the layers that read them (prefill_with_cache)")
    if kind in SERVED:  # this layer's weights as a stack of one
        return SERVED[kind].block(
            cfg, x, jax.tree.map(lambda a: a[None], p), 0,
            positions_of(*x.shape[:2]),
            partial(SERVED[kind].prefill, cfg, None), stat_axes)[:2]
    h = rms_norm(x, p["norm"], cfg.norm_eps).astype(cfg.dtype)
    stats = {}
    if kind == "M":
        from ray_tpu.ops.ssm import mamba2_mixer

        y = mamba2_mixer(h, p, heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
                         groups=cfg.ssm_groups, state=cfg.ssm_state,
                         chunk=cfg.ssm_chunk, eps=cfg.norm_eps)
    elif kind == "E":
        y, stats = _mlp_half(cfg, p, h, stat_axes)
    else:
        y, _, _ = _attn_half(cfg, p, h, positions_of(*x.shape[:2]), attend)
    return x + y.astype(x.dtype), stats


def pattern_stack(cfg: LlamaConfig, x, layers, attend, stat_axes=(),
                  policy=None, kinds=None):
    """The residual stream through a patterned stack in the pattern's
    order: layer ``i`` of its kind reads row ``i`` of that kind's stacked
    weights ``layers[kind name]`` (kinds that share a stack count
    together); every layer is rematerialised where
    ``cfg.remat``, but for what ``policy`` keeps (:func:`keep_policy`; only
    an attention layer and a latent block have names). ``kinds``: a stack
    that is not the config's own (a prediction module's blocks). Returns
    ``(x, stats)``, the routed layers' stats stacked ``[n_E]`` a leaf
    (``{}`` with no routed layer)."""
    met = dict.fromkeys(LAYER_KINDS.values(), 0)
    stats = []
    for kind in cfg.kinds if kinds is None else kinds:
        fn = partial(pattern_layer, cfg, kind, attend, stat_axes=stat_axes)
        if cfg.remat:
            fn = jax.checkpoint(fn, policy=policy)
        row = met[LAYER_KINDS[kind]]
        met[LAYER_KINDS[kind]] += 1
        x, st = fn(x, jax.tree.map(lambda a: a[row],
                                   layers[LAYER_KINDS[kind]]))
        if st:
            stats.append(st)
    if not stats:
        return x, {}
    return x, jax.tree.map(lambda *leaves: jnp.stack(leaves), *stats)


def flash_causal(q, k, v):
    """``attend`` of the steps that run on local shards: the flash kernel."""
    from ray_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True)


def _layer(cfg: LlamaConfig, mesh, x, layer_params, positions):
    """One decoder layer under GSPMD (or on one device, ``mesh`` None):
    the block over :func:`_attention`. Returns ``(x, stats)``."""
    x, stats, _ = decoder_block(
        cfg, x, layer_params, positions,
        lambda q, k, v: _attention(cfg, q, k, v, mesh))
    if mesh is not None and mesh.size > 1:
        # pin the residual stream's layout at every block boundary:
        # without the constraint GSPMD is free to pick a different
        # sharding for the scan carry than the embed output, paying a
        # resharding collective on entry/exit of every layer
        from ray_tpu.parallel.sharding import constraint

        x = constraint(x, ("batch", "seq", None), mesh)
    return x, stats


def embed_tokens(cfg, params, tokens, mesh=None, table_sharded=None):
    """Token embedding lookup, partition-friendly.

    Replicated table: plain gather. Vocab/embed-sharded table: one-hot
    matmul contraction (MaxText ``use_iota_embed`` / t5x ``one_hot``
    precedent) — GSPMD partitions dots natively (psum over the vocab shard
    axis), whereas a gather from a sharded table triggers the
    spmd_partitioner's "involuntary full rematerialization" fallback
    (replicate + repartition). Costs one extra lm_head-sized matmul on the
    MXU; the one-hot operand is sharded over batch/seq/vocab so it never
    materializes unsharded.

    ``table_sharded``: pass explicitly when the caller shards the table by
    its own specs (pipeline path); default infers from DEFAULT_RULES.
    """
    emb = params["embedding"].astype(cfg.dtype)
    if table_sharded is None and mesh is not None and mesh.size > 1:
        from ray_tpu.parallel.sharding import _mesh_axes_for

        def live(logical):
            ax = _mesh_axes_for(logical, DEFAULT_RULES, mesh)
            axs = ax if isinstance(ax, tuple) else (ax,) if ax else ()
            return any(mesh.shape[a] > 1 for a in axs)

        table_sharded = live("vocab") or live("embed")
    if mesh is None or mesh.size == 1 or not table_sharded:
        x = emb[tokens]
    else:
        from ray_tpu.parallel.sharding import constraint

        hot = jax.nn.one_hot(tokens, emb.shape[0], dtype=cfg.dtype)
        hot = constraint(hot, ("batch", "seq", "vocab"), mesh)
        x = jnp.einsum("btv,vd->btd", hot, emb,
                       preferred_element_type=jnp.float32).astype(cfg.dtype)
    if cfg.embedding_multiplier != 1.0:  # an 'H' / 'N' stack's alone
        x = x * cfg.embedding_multiplier
    if mesh is not None:
        from ray_tpu.parallel.sharding import constraint

        x = constraint(x, ("batch", "seq", None), mesh)
    return x


def _backbone(cfg: LlamaConfig, params, tokens, mesh=None):
    """tokens [B, T] int32 -> ``(final-normed hidden states [B, T, dim],
    the layers' stats stacked [L])``."""
    x = embed_tokens(cfg, params, tokens, mesh)
    if cfg.layer_pattern:
        x, stats = pattern_stack(
            cfg, widen_stream(cfg, x), params["layers"],
            lambda q, k, v: _attention(cfg, q, k, v, mesh))
        return _final_norm(cfg, collapse_stream(cfg, x),
                           params["final_norm"]), stats
    positions = positions_of(*tokens.shape)

    layer_fn = partial(_layer, cfg, mesh)
    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn, static_argnums=())

    def scan_body(carry, layer_params):
        return layer_fn(carry, layer_params, positions)

    x, stats = jax.lax.scan(scan_body, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.norm_eps), stats


def _head(cfg: LlamaConfig, params):
    return (params["embedding"].T if cfg.tie_embeddings
            else params["lm_head"])


def _logits(cfg: LlamaConfig, x, head):
    """Final-normed hidden states through ``head`` [dim, vocab] (whole or a
    vocabulary shard): the product in cfg.dtype, the logits float32."""
    logits = checkpoint_name(x.astype(cfg.dtype) @ head.astype(cfg.dtype),
                             "head").astype(jnp.float32)
    return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


def _final_gain(cfg: LlamaConfig, final_norm):
    return 1.0 + final_norm if cfg.zero_centered else final_norm


def _final_norm(cfg: LlamaConfig, x, final_norm, bias=None):
    """The model's last norm, of the kind ``cfg.norm_kind`` names, plus its
    ``bias`` where the family's LayerNorm has one (``final_norm_b``)."""
    norm = layer_norm if cfg.norm_kind == "layer" else rms_norm
    x = norm(x, _final_gain(cfg, final_norm), cfg.norm_eps)
    return x if bias is None else x + bias.astype(x.dtype)


def head_logits(cfg: LlamaConfig, x, final_norm, head, bias=None):
    """The model's end: final norm -> head -> float32 logits."""
    return _logits(cfg, _final_norm(cfg, x, final_norm, bias), head)


def forward(cfg: LlamaConfig, params, tokens, mesh=None):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32."""
    x, _ = _backbone(cfg, params, tokens, mesh)
    return _logits(cfg, x, _head(cfg, params))


def _plain_chunk_nll(cfg: LlamaConfig, head):
    """Per-chunk next-token NLL against a full-width head [d, vocab]:
    fp32 log-softmax over the whole vocab."""

    def chunk_nll(x_c, t_c):
        logp = jax.nn.log_softmax(_logits(cfg, x_c, head), axis=-1)
        return -jnp.take_along_axis(logp, t_c[..., None], axis=-1)[..., 0]

    return chunk_nll


def chunked_nll_mean(cfg: LlamaConfig, x, targets, chunk_nll, policy=None,
                     live=None):
    """Mean NLL with the lm_head matmul + softmax CHUNKED over the
    sequence under ``jax.checkpoint``: fp32 logits exist only per-chunk
    ([B, C, vocab] instead of [B, T, vocab] — the round-1 OOM at batch
    32) and are made again in the backward pass. With no ``policy`` a
    chunk's backward recomputes its head product too (one extra head
    matmul a chunk; frees GBs); under one that keeps ``head``
    (:func:`keep_policy`; ``make_spmd_train_step`` gives it where the
    device has the room) the product is kept in cfg.dtype, [B, T, vocab]
    at half the float32 logits' size, and only its conversion and the
    softmax are made again. ``chunk_nll(x_c, t_c) -> [B, C]`` supplies
    the head — full-width (:func:`_plain_chunk_nll`) or vocab-parallel
    (:func:`vp_chunk_nll`). ``live``: only the first ``live`` positions of
    a row count; the others add nothing and are left out of the mean (a
    prediction module's last position has no target). None: all count."""
    B, T, d = x.shape
    C = cfg.loss_chunk
    count = B * T
    if live is not None:  # a position's target with whether it counts
        head_nll, count = chunk_nll, B * live
        targets = jnp.stack([targets, jnp.broadcast_to(
            jnp.arange(T) < live, (B, T)).astype(targets.dtype)], axis=-1)

        def chunk_nll(x_c, t_c):
            return head_nll(x_c, t_c[..., 0]) * t_c[..., 1]

    if not C or T <= C:
        if live is None:
            return chunk_nll(x, targets).mean()
        return chunk_nll(x, targets).sum() / count

    n, rem = divmod(T, C)
    xs = jnp.swapaxes(x[:, :n * C].reshape(B, n, C, d), 0, 1)     # [n,B,C,d]
    ts = jnp.swapaxes(targets[:, :n * C].reshape(
        B, n, C, *targets.shape[2:]), 0, 1)                       # [n,B,C]

    def body(total, chunk):
        x_c, t_c = chunk
        return total + chunk_nll(x_c, t_c).sum(), None

    total, _ = jax.lax.scan(jax.checkpoint(body, policy=policy),
                            jnp.zeros((), jnp.float32), (xs, ts))
    if rem:
        total = total + chunk_nll(x[:, n * C:], targets[:, n * C:]).sum()
    return total / count


def add_mtp_loss(cfg: LlamaConfig, mtp, x, tokens, nll, stats, *, embed,
                 attend, chunk_nll, stat_axes=(), policy=None):
    """The multi-token-prediction module's loss beside the main one, for
    :func:`loss_parts` and ``make_spmd_train_step`` alike. ``x`` [B, T, dim]:
    the main model's FINAL-NORMED stream over ``tokens[:, :-1]`` (``tokens``
    [B, T + 1] = t_0 .. t_T); ``nll`` / ``stats``: the main cross-entropy and
    the stack's router stats. The caller lends what the module SHARES with
    the model: ``embed(ids)`` (the embedding table), ``chunk_nll`` (the
    head), and its ``attend``::

        h'_i = [N_e(Emb(t_{i+1})) ; N_h(x_i)] W_eh      scope ``mtp.merge``
        y    = the module's "L" block(s) over all T positions  ``mtp.block``
        L_mtp = mean_{i < T - 1} -log p(t_{i+2} | N(y_i) head)  ``mtp.head``

    Position ``T - 1`` has no target and is left out of the mean
    (``chunked_nll_mean``'s ``live``). Returns ``(nll + mtp_loss_weight *
    L_mtp, stats with the module's block's behind the stack's, {"main_loss":
    nll, "mtp_loss": L_mtp})``; without a module what it was given and
    ``{}``."""
    if not cfg.mtp_layers:
        return nll, stats, {}
    cd, eps = cfg.dtype, cfg.norm_eps
    B, T, _ = x.shape
    # t_{i+2}; the last position's is any id: it does not count
    targets = jnp.concatenate(
        [tokens[:, 2:], jnp.zeros((B, 1), tokens.dtype)], axis=1)

    def merge(x, ids, enorm, hnorm, eh_proj):
        both = jnp.concatenate(
            [rms_norm(embed(ids), enorm, eps).astype(cd),
             rms_norm(x, hnorm, eps).astype(cd)], axis=-1)
        return (both @ eh_proj.astype(cd)).astype(x.dtype)

    with jax.named_scope("mtp.merge"):
        y = (jax.checkpoint(merge) if cfg.remat else merge)(
            x, tokens[:, 1:], mtp["enorm"], mtp["hnorm"], mtp["eh_proj"])
    with jax.named_scope("mtp.block"):
        y, block_stats = pattern_stack(
            cfg, y, mtp["layers"], attend, stat_axes, policy,
            kinds="L" * cfg.mtp_layers)
    with jax.named_scope("mtp.head"):
        mtp_nll = chunked_nll_mean(
            cfg, rms_norm(y, mtp["final_norm"], eps), targets, chunk_nll,
            policy, live=T - 1)
    stats = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), stats,
                         block_stats)
    return (nll + cfg.mtp_loss_weight * mtp_nll, stats,
            {"main_loss": nll, "mtp_loss": mtp_nll})


def loss_parts(cfg: LlamaConfig, params, tokens, mesh=None):
    """``(total, report)``: what is trained on, and for a routed model the
    router's scalars apart (:func:`add_router_losses`; the cross-entropy
    is ``total`` less the weighted two); with a prediction module
    (``cfg.mtp_layers``) the cross-entropy is the main one plus the
    module's weighted, and the report has both apart (:func:`add_mtp_loss`,
    the ONE place that builds it, for ``make_spmd_train_step`` too).
    tokens [B, T+1]."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, stats = _backbone(cfg, params, inputs, mesh)
    chunk_nll = _plain_chunk_nll(cfg, _head(cfg, params))
    nll = chunked_nll_mean(cfg, x, targets, chunk_nll)
    nll, stats, report = add_mtp_loss(
        cfg, params.get("mtp"), x, tokens, nll, stats,
        embed=lambda t: embed_tokens(cfg, params, t, mesh),
        attend=lambda q, k, v: _attention(cfg, q, k, v, mesh),
        chunk_nll=chunk_nll)
    total, router = add_router_losses(cfg, nll, stats)
    return total, {**router, **report}


def loss_fn(cfg: LlamaConfig, params, tokens, mesh=None):
    """Next-token cross-entropy, fp32 log-softmax, plus a routed model's
    weighted router losses. tokens [B, T+1]. See :func:`chunked_nll_mean`
    for the chunked-head memory story."""
    return loss_parts(cfg, params, tokens, mesh)[0]


# --------------------------------------------------------------------------- #
# Generative decode (paged KV cache — serve/kv_cache.py owns the pages)
# --------------------------------------------------------------------------- #


def _gqa_repeat(cfg: LlamaConfig, k, v):
    rep = cfg.n_heads // cfg.n_kv_heads
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _page_slab(pages, page):
    """The ``[S, 1, page_size, *row]`` slab of one physical page (``page``
    traced) of a store laid out ``[S, n_pages, page_size, *row]``."""
    S, _, ps, *row = pages.shape
    return jax.lax.dynamic_slice(pages, (0, page, 0) + (0,) * len(row),
                                 (S, 1, ps, *row))


def _read_pages(pages, page_ids):
    """The ``n`` physical pages ``page_ids``, in that order, as one
    ``[S, n * page_size, *row]`` view. One dynamic slice per page (``n``
    is static) and not ``pages[:, page_ids]``: XLA moves the layers'
    conversion to the compute dtype in front of a gather and then converts
    the WHOLE store on every call (6.8 of a 29.5 ms decode call at 128
    pages; my chip run, PR 25)."""
    S, _, ps, *row = pages.shape
    slabs = [_page_slab(pages, page_ids[i])
             for i in range(page_ids.shape[0])]
    return jnp.concatenate(slabs, axis=1).reshape(S, -1, *row)


def _write_pages(pages, rows, page_ids):
    """Write ``rows`` [S, n * page_size, *row] into the ``n`` physical
    pages ``page_ids`` of ``pages``. One dynamic-update-slice per page
    (``n`` is static): with the store donated each is in place."""
    S, _, ps, *row = pages.shape
    rows = rows.astype(pages.dtype).reshape(S, -1, ps, *row)
    for i in range(rows.shape[1]):
        pages = jax.lax.dynamic_update_slice(
            pages, rows[:, i:i + 1], (0, page_ids[i], 0) + (0,) * len(row))
    return pages


def _attend_selected_cached(cfg: LlamaConfig, k_pages, v_pages, page_ids,
                            pos, layer, ki_cache, q, kk, vv, qi, ki, w):
    """One new token at position ``pos`` (:func:`index_block`'s ``attend``
    arguments) against layer ``layer`` of the page stores: index scores
    over ALL the sequence's index keys ``ki_cache`` ``[Tpad, Di]`` (the
    gathered pages' view; rows from ``pos`` on are masked, the token's own
    key stands in at ``pos``), the ``index_topk`` best
    (:func:`select_top`), then attention over those ROWS of the key and
    value stores, gathered one by one: ``index_topk`` rows a store and not
    the sequence's whole pages. The token's own key and value are not in
    the stores yet: they come as ``kk`` / ``vv`` and count only if ``pos``
    is among the chosen. Float32 scores.

    The gathered rows are used as the float32 they are kept in (they are
    the compute type's values: a program of this engine wrote them), in
    products at ``HIGHEST`` precision, so that NO conversion stands behind
    the gather, the one a default-precision product makes of its float32
    operands included. With one, XLA moves the conversion in front of the
    gather and out of the layers' loop and converts both WHOLE stores on
    every call (12 of a 14 ms decode call at 80 pages; my chip run, PR 40;
    :func:`_read_pages` met the same in PR 25), and an
    ``optimization_barrier`` between the two did not hold it back. The
    products are 32 query heads by 2,049 rows: six passes cost nothing."""
    cd, f32 = cfg.dtype, jnp.float32
    ps = k_pages.shape[2]
    G, rep = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    where, real, own = _pick_rows(cfg, pos, ki_cache, qi, ki, w)
    with jax.named_scope("dsa.attend"):
        page, row = page_ids[where // ps], where % ps
        K_all = jnp.concatenate([k_pages[layer, page, row],
                                 kk[0].astype(f32)])
        V_all = jnp.concatenate([v_pages[layer, page, row],
                                 vv[0].astype(f32)])
        seen = jnp.append(real, own)
        exact = jax.lax.Precision.HIGHEST
        s = jnp.einsum("grd,kgd->grk",
                       q[0, 0].astype(f32).reshape(G, rep, -1), K_all,
                       precision=exact) / math.sqrt(cfg.head_dim)
        probs = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        o = jnp.einsum("grk,kgd->grd", probs, V_all, precision=exact)
    return o.reshape(1, 1, G * rep, -1).astype(cd)


def _pick_rows(cfg: LlamaConfig, pos, ki_cache, qi, ki, w):
    """A decode call's selection at position ``pos``: index scores over the
    sequence's index keys ``ki_cache`` ``[Tpad, Di]`` (rows from ``pos`` on
    are masked, the token's own key ``ki`` stands in at ``pos``; scope
    ``dsa.index``), the ``index_topk`` best (:func:`select_top`; scope
    ``dsa.select``). Returns ``(where [index_topk] int32, real
    [index_topk] bool, own)``: the chosen EARLIER positions in order, which
    of the places hold one, and whether ``pos`` itself is chosen."""
    cd, i32 = cfg.dtype, jnp.int32
    Tpad, K = ki_cache.shape[0], cfg.index_topk
    at = jnp.arange(Tpad, dtype=i32)
    with jax.named_scope("dsa.index"):
        keys = jax.lax.dynamic_update_slice(
            ki_cache.astype(cd), ki[0], (pos, 0))
        score = index_scores(qi, keys[None], w)[0, 0]
    with jax.named_scope("dsa.select"):
        chosen = select_top(score, at <= pos, K)
        own = chosen[pos]
        earlier = chosen & (at != pos)
        # the chosen positions in order: place j holds the position under
        # which j chosen ones lie
        under = jnp.cumsum(earlier, dtype=i32)
        place = jnp.arange(K, dtype=i32)
        where = jnp.sum(under[None, :] <= place[:, None], axis=1, dtype=i32)
        real = place < under[-1]
        where = jnp.where(real, where, 0)
    return where, real, own


# which FORM a decode call's attention took, counted where a program is
# traced: "grouped" (_attend_grouped: a KV head's query heads against its keys
# as ONE product, the keys read once in the type the store keeps them in) or
# "repeated" (_attend_cached: every key and value repeated for each query head
# of its group and widened to float32). A kind's row of SERVED says which;
# decode_attend_forms() keeps the shapes and the reason beside the count
_g_engine_decode_attend = Gauge(
    "ray_tpu_serve_engine_decode_attend",
    "Decode attentions traced into the decode engine's programs, by form: "
    "grouped (a KV head's query heads in one product, no key repeated) or "
    "repeated (keys and values repeated per query head)",
    tag_keys=("form",))

# (form, q shape, view shape) -> {.., "calls"}
_decode_attend_taken: Dict[tuple, dict] = {}


def decode_attend_forms() -> list:
    """Every distinct decode attention (form, shapes) traced in this
    process, with its reason and how often: how a run proves that its
    decode programs repeat no key (beside :func:`prefill_attend_paths`)."""
    with _paths_lock:
        return [dict(rec) for rec in _decode_attend_taken.values()]


def _note_decode_attend(form, q, view, reason) -> None:
    key = (form, q.shape, view.shape)
    with _paths_lock:
        rec = _decode_attend_taken.setdefault(key, {
            "form": form, "q_shape": list(q.shape),
            "view_shape": list(view.shape), "view_dtype": view.dtype.name,
            "reason": reason, "calls": 0})
        rec["calls"] += 1
        counts = {way: sum(r["calls"] for r in _decode_attend_taken.values()
                           if r["form"] == way)
                  for way in ("grouped", "repeated")}
    for way, n in counts.items():
        _g_engine_decode_attend.set(float(n), tags={"form": way})


def _attend_cached(cfg: LlamaConfig, k_cache, v_cache, length, q, kk, vv,
                   lowest=None):
    """One new token (``q``, ``kk``, ``vv``: the block's ``attend``
    arguments) against ONE layer's gathered, page-padded keys and values
    ``[Tpad, n_kv, head_dim]``: positions >= ``length`` are pad garbage and
    masked, and so are, for a window layer, those below ``lowest`` (both
    count the view's rows); the token attends to the history and itself.
    Float32 scores."""
    cd = cfg.dtype
    Tpad = k_cache.shape[0]
    _note_decode_attend("repeated", q, k_cache, "the kind's row of SERVED "
                        "attends through _attend_cached")
    K = jnp.concatenate([k_cache.astype(cd)[None], kk], axis=1)
    V = jnp.concatenate([v_cache.astype(cd)[None], vv], axis=1)
    K, V = _gqa_repeat(cfg, K, V)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   K.astype(jnp.float32)) * scale
    idx = jnp.arange(Tpad + 1)
    valid = (idx < length) | (idx == Tpad)  # history + the token itself
    if lowest is not None:
        valid &= idx >= lowest
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      V.astype(jnp.float32)).astype(cd)


def _attend_grouped(cfg: LlamaConfig, tag: str, k_cache, v_cache, length, q,
                    kk, vv, lowest=None):
    """:func:`_attend_cached`'s attention, masks and arguments, WITHOUT its
    copies: the ``n_heads / n_kv_heads`` query heads of a KV head score
    against that head's keys as ONE product (``[n_kv, rep, D] x [Tpad, n_kv,
    D]``), the views are read once in the type the store keeps them in
    (float32 that holds the compute type's values: a program of this engine
    wrote them), nothing is repeated per query head and nothing is
    concatenated: the token's own key and value (``kk`` / ``vv``, not in the
    store yet) enter the softmax as one more term. Query head ``h`` reads KV
    head ``h // rep``. At 16 query heads a KV head and 16,385 positions the
    repeated form writes and reads two ``[16385, 128, 128]`` float32 arrays
    (2.1 GB) a layer a token. Float32 scores."""
    f32 = jnp.float32
    Tpad, G, D = k_cache.shape  # the views', whose values may be wider
    _note_decode_attend(
        "grouped", q, k_cache,
        f"{tag}: {q.shape[2] // G} query heads a KV head in one product, "
        f"the views read as kept ({k_cache.dtype.name})")
    qg, scale = q[0, 0].reshape(G, -1, D).astype(f32), 1.0 / math.sqrt(D)
    s = jnp.einsum("grd,tgd->grt", qg, k_cache.astype(f32)) * scale
    own = jnp.einsum("grd,gd->gr", qg,
                     kk[0, 0].astype(f32))[..., None] * scale
    idx = jnp.arange(Tpad)
    valid = idx < length
    if lowest is not None:
        valid &= idx >= lowest
    s = jnp.where(valid, s, -1e30)
    top = jnp.maximum(s.max(axis=-1, keepdims=True), own)
    p, p_own = jnp.exp(s - top), jnp.exp(own - top)
    o = jnp.einsum("grt,tgd->grd", p, v_cache.astype(f32)) \
        + p_own * vv[0, 0].astype(f32)[:, None, :]
    o = o / (p.sum(axis=-1, keepdims=True) + p_own)
    return o.reshape(1, 1, -1, v_cache.shape[-1]).astype(cfg.dtype)


def _attend_latent_cached(cfg: LlamaConfig, cache, length, q, latent, wkv_b,
                          seen=None):
    """One new token (``_latent_half``'s ``attend`` arguments) against ONE
    sublayer's gathered, page-padded latent rows ``[Tpad, latent_row]``,
    masked as :func:`_attend_cached` masks, or by ``seen`` ``[Tpad + 1]``
    where the rows are a SELECTION of the sequence's (the last: the token's
    own). No key or value is expanded:
    ``wkv_b``'s key half is absorbed into the query (``q_abs[h] = q_nope[h]
    Wk[h]^T``, as wide as a latent row's ``c``), the scores are taken
    against the rows themselves, and its value half is applied to the
    ``heads`` weighted sums of rows. Float32 scores."""
    cd, f32 = cfg.dtype, jnp.float32
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    Tpad = cache.shape[0]
    w = wkv_b.reshape(r, cfg.n_heads, -1)  # a head's [Wk | Wv]
    rows = jnp.concatenate([cache.astype(cd), latent[0]], axis=0).astype(f32)
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q[..., :dn], w[..., :dn],
                       preferred_element_type=f32)
    q_row = jnp.concatenate([q_abs, q[..., dn:].astype(f32)], axis=-1)
    s = jnp.einsum("bqhc,kc->bhqk", q_row, rows) / math.sqrt(q.shape[-1])
    idx = jnp.arange(Tpad + 1)
    valid = seen
    if seen is None:
        valid = (idx < length) | (idx == Tpad)  # history + the token itself
    probs = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    o = jnp.einsum("bhqk,kr->bqhr", probs, rows[:, :r])
    return jnp.einsum("bqhr,rhd->bqhd", o.astype(cd), w[..., dn:],
                      preferred_element_type=f32).astype(cd)


def _attend_dsa_cached(cfg: LlamaConfig, call, l, mine, q, latent, wkv_b,
                       index, chosen):
    """:func:`dsa_block`'s ``attend`` for a decode call's one token against
    ``mine``, the layer's OWN views: its latent rows and, a FULL layer's,
    its index keys. While the table holds no more than ``index_topk``
    positions every visible one is attended and no selection is made
    (:func:`_attend_picked`'s rule). Past that a full layer (``index``
    given) scores the index keys' view and picks (:func:`_pick_rows`), a
    SHARED one takes the row numbers that came with the stream, and either
    attends those ``index_topk`` rows of its own latent view (scope
    ``dsa.gather``) and the token's own in :func:`_attend_latent_cached`'s
    absorbed form: no key or value is expanded. The rows come from the
    VIEW (the sequence's pages, 37.7 MB a layer at 16,384 positions) and not
    from the store: a store 576 wide lies positions-minor on the chip, and
    a row gather from it is first a copy of the whole store (3.65 of a
    decode call's 8.0 ms; my chip run, PR 63). Returns ``(o, chosen)``."""
    if mine[0].shape[0] <= cfg.index_topk:
        return _attend_latent_cached(cfg, mine[0], call.pos, q, latent,
                                     wkv_b), chosen
    if index is not None:
        where, real, own = _pick_rows(cfg, call.pos, mine[1], *index)
        chosen = (where, jnp.append(real, own))
    where, seen = chosen
    with jax.named_scope("dsa.attend" if index is not None
                         else "dsa.attend_shared"):
        with jax.named_scope("dsa.gather"):
            rows = mine[0][where]
        o = _attend_latent_cached(cfg, rows, None, q, latent, wkv_b,
                                  seen=seen)
    return o, chosen


# --- the served kinds: ONE row a layer letter ------------------------------ #


class Served(NamedTuple):
    """One layer kind the decode engine serves: all that :func:`page_rows`,
    the walker (:func:`_serve_layers`), the two programs and
    :class:`LlamaDecodeEngine` know of it. Serving a new kind is a block
    function, its attends and ONE row of :data:`SERVED`.

    ``family``: :func:`page_rows`' name of the stores; the kinds of one
    family are served TOGETHER and no others with them. ``stack``: the name
    of its weights under ``params["layers"]`` (None: the dense tree; the
    table says how either reaches the block). ``block(cfg, x, stack, which,
    positions, attend)`` -> ``(x, stats, rows)``: the layer, ``which`` its
    number in the stack (the dense block: its own weights), ``rows`` what it
    keeps, an array a store (or the one array). ``f32``: the serving
    stream is float32 and not ``cfg.dtype``; ``x`` is ``[B, T, dim]``, or
    a tuple of ``hc_mult`` of them where the config has several rows a token
    (the programs widen it behind the embedding and sum it in front of the
    head: only a block that takes :func:`hyper_connected` reads such a
    stream).
    ``rows(cfg)``: ``[(tag,
    sublayers, row shape, table), ...]``, what a layer keeps, a store each,
    found by the rule ``table`` names (:data:`TABLES`): a row a POSITION by
    the pool's page ids (``"page"``) or by the engine's map of a page to its
    SLOT (``"slot"``), counted by ``ray_tpu_serve_engine_page_bytes{kind}``
    under ``tag``; or ONE row a SEQUENCE (``"state"``: a recurrent layer's
    state), counted by ``ray_tpu_serve_engine_state_bytes{part}``.
    ``prefill(cfg, last, *the block's attend arguments)``: the layer's
    mixing over the call's own positions, of which ``last`` is the last
    real one (None: all are; attention's causal mask needs no telling, a
    recurrence does). ``decode(cfg, call, l, mine, *the block's attend
    arguments)``: one new token of the kind's ``l``-th layer against
    ``mine``, that layer's page-padded views, one a row of ``rows``
    (``[Tpad, *row]``; ``[sublayers, Tpad, *row]`` for several); ``call``
    has ``pos`` (the write position), ``page_ids``, ``base`` (the position
    of a SLOT view's first row) and ``stores`` (``{kind: its stores,
    whole}``). ``attended(cfg, program, n)`` -> ``(visible, attended)``
    keys of a prefill of ``n`` tokens or the decode of the ``n``-th, for a
    kind that attends fewer than it sees
    (``ray_tpu_serve_engine_selected_share{program}``). ``alone``: a stack
    of SOME of the family's kinds is served too (a test holds such a stack's
    logits to a reference).

    A ROW MAY KEEP NOTHING (``rows(cfg)`` is ``[]``): such a layer has no
    store, no view and no rows to write, and mixes positions only through
    what the layers in front of it kept or handed on beside the stream, so a
    PREFILL runs it on the last real position alone (:func:`stream_cut`).
    And it MAY READ ANOTHER KIND'S VIEW: ``reads`` names a kind of the same
    family, and ``mine`` of its ``decode`` is then THAT kind's views, all its
    layers' (``[n, Tpad, *row]`` a store), in place of its own."""
    family: str
    stack: Optional[str]
    block: Callable
    f32: bool
    rows: Callable
    prefill: Callable
    decode: Callable
    attended: Optional[Callable] = None
    alone: bool = False
    reads: Optional[str] = None


class Table(NamedTuple):
    """One rule by which a store's slabs are found and filled. ``ids``: whose
    numbers find a slab, the pool's page ids (``"page"``) or the engine's
    slots (``"slot"``, fewer than pages). ``rows``: the rows a slab holds,
    None for a page's positions (``page_size``: a row a POSITION, written
    where the position lies) or 1: ONE row, what a SEQUENCE keeps whatever
    its length (a recurrent state), which each call reads and REWRITES."""
    ids: str
    rows: Optional[int]


# The rules ``Served.rows`` may name. ``"state"``: the slab of page ``p``
# holds the state after the NEWEST position written in ``p``. Prefill
# writes the slab of the page that holds its last real position; a decode
# call at ``pos`` reads the slab of the page that holds ``pos - 1`` and
# writes that of the page that holds ``pos``; ``copy_page`` copies it with
# the page. So the state is found from the page table alone (a call carries
# no sequence), it follows a sequence across a page boundary, a page shared
# read-only (a prefix entry's) is never written again after its prefill,
# whether the prompt fills its last page or not, and every full page keeps
# the state at its end. A slab never written is zeros, a valid state.
TABLES: Dict[str, Table] = {"page": Table("page", None),
                            "slot": Table("slot", None),
                            "state": Table("page", 1)}


def _kv_rows(cfg: LlamaConfig, tag: str, table: str = "page"):
    """Per-head keys and values, a store each over the kind's layers."""
    return [(tag, 1, (cfg.n_kv_heads, cfg.head_dim), table)] * 2


def _attend_pages(cfg: LlamaConfig, call, l, mine, *a):
    """A decode call's token against its layer's keys' and values' pages,
    every visible position."""
    return _attend_cached(cfg, *mine, call.pos, *a)


def _attend_slots(cfg: LlamaConfig, call, l, mine, *a):
    """A window layer's views are the SLOTS of the pages its window reaches
    (``call.base``: the position of their first row), masked by true
    position: ``pos - window < j <= pos``."""
    return _attend_cached(cfg, *mine, call.pos - call.base, *a,
                          lowest=call.pos - cfg.window + 1 - call.base)


def _attend_picked(cfg: LlamaConfig, call, l, mine, q, kk, vv, qi, ki, w):
    """While an indexed block's table holds no more than ``index_topk``
    positions every visible one is attended, from the keys' and values'
    views; past that the index keys' view ALONE is read, and
    ``index_topk`` ROWS of the other two stores are gathered where the
    layer has picked them (:func:`_attend_selected_cached`). The views no
    ``attend`` reads are never made: lowering drops what is dead (the
    pinned texts of ``tests/test_olmoe.py SERVED_PROGRAMS`` hold that)."""
    if mine[2].shape[0] <= cfg.index_topk:
        return _attend_cached(cfg, *mine[:2], call.pos, q, kk, vv)
    return _attend_selected_cached(
        cfg, *call.stores["I"][:2], call.page_ids, call.pos, l, mine[2],
        q, kk, vv, qi, ki, w)


def _index_attended(cfg: LlamaConfig, program: str, n: int):
    """A row that sees no more than ``index_topk`` keys attends them all."""
    few = min(n, cfg.index_topk)
    if program == "decode":
        return n, few
    return n * (n + 1) // 2, few * (few + 1) // 2 + (n - few) * few


# The table, a comment a row (a row may keep NOTHING and may read another
# kind's view: Served's last paragraph). HOW A LAYER'S WEIGHTS REACH ITS BLOCK: a
# patterned kind's stay stacked under ``params["layers"][stack]`` and every
# matrix is cut out by the layer's number where it is used, the experts never
# (:func:`shortcut_layer` has the readings: a whole layer cut out first is a
# copy of 2.5 GB a layer, the experts' three of 403 MB a call). The dense
# block's are the layer scan's ``xs``, and a decode call's new row leaves that
# scan's body without its position where the others drop the batch behind
# the scan: no measurement prefers either, each is what its programs' pinned
# texts hold (``tests/test_olmoe.py``; the dense ones were taken before there
# was a pattern).
SERVED: Dict[str, Served] = {
    # the dense block (a dense model is the pattern "b" * n_layers): per-head
    # keys and values; prefill is plain attention over its few short pages
    "b": Served(
        "kv", None,
        lambda cfg, x, stack, p, positions, attend: decoder_block(
            cfg, x, p, positions, attend),
        False, partial(_kv_rows, tag="kv"),
        # float32 scores: decode numerics never depend on prefill matching a
        # fused kernel, only on the cached bytes
        lambda cfg, last, q, k, v: plain_attention(
            q, *_gqa_repeat(cfg, k, v), causal=True), _attend_pages),
    # the shortcut-connected double layer (latent attention, routed and
    # identity experts): ONE store of latent rows, [kv_lora_rank +
    # qk_rope_head_dim] an attention sublayer, from which prefill expands keys
    # and values and decode never does (_attend_latent_cached)
    "S": Served(
        "latent", "scmoe", shortcut_layer, False,
        lambda cfg: [("latent", 2, (cfg.latent_row,), "page")],
        lambda cfg, last, j, *a: attend_latent_expanded(cfg, *a),
        lambda cfg, call, l, mine, j, *a: _attend_latent_cached(
            cfg, mine[0][j], call.pos, *a)),
    # the whole routed block with full and with window attention, ONE stack
    # in layer order: the full layers' keys and values by page id, the window
    # layers' by SLOT (a window layer needs a position for cfg.window
    # positions and no longer: the engine's WINDOW SLOTS). The stream is
    # float32: a top-k choice is a hard one (window_block)
    "F": Served(
        "window", "block", lambda cfg, *a: window_block(cfg, "F", *a), True,
        partial(_kv_rows, tag="full"),
        lambda cfg, last, *a: attend_window_tiles(cfg, "F", *a),
        _attend_pages),
    "W": Served(
        "window", "block", lambda cfg, *a: window_block(cfg, "W", *a), True,
        partial(_kv_rows, tag="window", table="slot"),
        lambda cfg, last, *a: attend_window_tiles(cfg, "W", *a),
        _attend_slots),
    # the indexed block (per-head QK-norm, a held range of experts): keys and
    # values as the block's and a THIRD store, the indexer's ONE key head a
    # layer, all three by page id; prefill attends under the selection
    "I": Served(
        "index", "index", index_block, True,
        lambda cfg: _kv_rows(cfg, "kv")
        + [("index", 1, (cfg.index_head_dim,), "page")],
        lambda cfg, last, *a: attend_selected(cfg, *a), _attend_picked,
        _index_attended),
    # the delta-rule family, a stack a kind. "D" (the gated delta rule)
    # keeps NO row a position: its state and its convolution's tail, a
    # SEQUENCE, by the table rule "state". "A" (gated full attention) keeps
    # normed, rotated keys and values by page id as the dense block does
    "D": Served(
        "delta", "delta", delta_block, True,
        lambda cfg: [
            ("state", 1, (cfg.lin_value_heads, cfg.lin_key_dim,
                          cfg.lin_value_dim), "state"),
            ("conv", 1, (cfg.lin_conv - 1,
                         2 * cfg.lin_key_heads * cfg.lin_key_dim
                         + cfg.lin_value_heads * cfg.lin_value_dim),
             "state")],
        attend_delta, _attend_state),
    "A": Served(
        "delta", "gated", gated_block, True, partial(_kv_rows, tag="gated"),
        lambda cfg, last, *a: attend_tiles(*a, cfg.dtype, kind="gated"),
        _attend_pages),
    # the latent blocks outside the double layer (serve_latent_block), a
    # stack a kind, the routed one first (LATENT_KINDS' order:
    # the engine reads its experts' groups off the first store's kind):
    # latent rows as "S" keeps them, ONE a layer, a store a kind, both under
    # the tag latent_block; prefill expands keys and values, decode never
    # does. The stream is
    # float32 (a top-k choice is a hard one, and with hc_mult rows a token it
    # is mixed, not only added to, at every sublayer)
    **{c: Served(
        "latent_block", LAYER_KINDS[c],
        lambda cfg, *a, c=c: serve_latent_block(cfg, c, *a), True,
        lambda cfg: [("latent_block", 1, (cfg.latent_row,), "page")],
        lambda cfg, last, *a: attend_latent_expanded(cfg, *a),
        lambda cfg, call, l, mine, *a: _attend_latent_cached(
            cfg, mine[0], call.pos, *a)) for c in LATENT_KINDS},
    # the parallel blocks (parallel_block), ONE stack in
    # layer order: keys and values as "F" / "W" keep them, the full layers'
    # by page id and the window layers' by SLOT, under tags of their own.
    # Decode scores a KV head's query heads against its keys as ONE product
    # and repeats no key (_attend_grouped). The stream is float32 (a top-k
    # choice is a hard one). A stack of window layers alone is served too
    # (a cell's rehearsal cuts the pattern inside its first period)
    "P": Served(
        "parallel", "parallel",
        lambda cfg, *a: parallel_block(cfg, "P", *a), True,
        partial(_kv_rows, tag="parallel_full"),
        lambda cfg, last, *a: attend_parallel_tiles(cfg, "P", *a),
        lambda cfg, call, l, mine, *a: _attend_grouped(
            cfg, "parallel_full", *mine, call.pos, *a), alone=True),
    "R": Served(
        "parallel", "parallel",
        lambda cfg, *a: parallel_block(cfg, "R", *a), True,
        partial(_kv_rows, tag="parallel_window", table="slot"),
        lambda cfg, last, *a: attend_parallel_tiles(cfg, "R", *a),
        lambda cfg, call, l, mine, *a: _attend_grouped(
            cfg, "parallel_window", *mine, call.pos - call.base, *a,
            lowest=call.pos - cfg.window + 1 - call.base), alone=True),
    # the state-space hybrid's whole blocks (hybrid_block), a stack a kind.
    # "H" (Mamba-2) keeps NO row a position: its state and its convolution's
    # tail, a SEQUENCE, by the table rule "state" (as "D"). "N" (unrotated
    # GQA) keeps keys and values by page id; decode scores a KV head's query
    # heads against its keys as ONE product (_attend_grouped). The stream is
    # float32 though no router makes a hard choice here: eighty sublayers
    # each add residual_multiplier of a vector to a stream
    # embedding_multiplier embeddings wide, and in bfloat16 every add rounds
    # the WHOLE stream (a hundredth of it by the end), which is the size of
    # the faults a comparison of logits has to see. A stack of state-space
    # layers alone is served too (a cell's rehearsal cuts the pattern inside
    # its first period)
    "H": Served(
        "hybrid", "hybrid_mamba",
        lambda cfg, *a: hybrid_block(cfg, "H", *a), True,
        lambda cfg: [
            ("ssm_state", 1, (cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), "state"),
            ("ssm_conv", 1, (cfg.ssm_conv - 1,
                             cfg.ssm_heads * cfg.ssm_head_dim
                             + 2 * cfg.ssm_groups * cfg.ssm_state),
             "state")],
        attend_ssm, _attend_ssm_state, alone=True),
    "N": Served(
        "hybrid", "hybrid_attn",
        lambda cfg, *a: hybrid_block(cfg, "N", *a), True,
        partial(_kv_rows, tag="hybrid"),
        lambda cfg, last, *a: attend_hybrid_tiles(cfg, *a),
        lambda cfg, call, l, mine, q, *a: _attend_grouped(
            cfg, "hybrid", *mine, call.pos,
            _hybrid_query(cfg, q, cfg.head_dim), *a)),
    # latent attention under a selection that layers SHARE (dsa_block), a
    # stack a kind, the routed ones first (as the latent blocks'). Every
    # layer keeps a latent row, a store a kind under the tag dsa_latent; a
    # FULL layer ("Y", "X") keeps its indexer's ONE key head too (dsa_index),
    # a SHARED one ("Z") has no such store. The stream is Selecting: the
    # selection travels beside it from a full layer to the layers behind it
    # (float32, as the latent blocks'). Prefill expands keys and values under
    # the mask; decode gathers index_topk rows of the layer's OWN view and
    # never expands. Any part of the family whose first layer is full is
    # served alone
    **{c: Served(
        "dsa", LAYER_KINDS[c],
        lambda cfg, *a, c=c: dsa_block(cfg, c, *a), True,
        lambda cfg, c=c: [("dsa_latent", 1, (cfg.latent_row,), "page")]
        + [("dsa_index", 1, (cfg.index_head_dim,), "page")]
        * (c in DSA_FULL),
        lambda cfg, last, *a: attend_latent_selected(cfg, *a),
        _attend_dsa_cached, _index_attended, alone=True)
       for c in DSA_KINDS},
    # the decoder-hybrid-decoder's whole blocks (memory_block), a stack a
    # kind but "w" / "f", which share one in layer order. "m" (Mamba-1)
    # keeps NO row a position: its state [channels, states] and its
    # convolution's tail, a SEQUENCE, by the table rule "state" (as "H",
    # "D"). "w" keeps keys and values by SLOT (as "W", "R"), "f" by page id:
    # ONE layer's for the whole model. "g" and "c" keep NOTHING: "g" gates the
    # memory that came beside the stream, "c" attends the keys and values of
    # the "f" layer in front of it, a prefill's from the stream and a decode
    # call's from that layer's views (``reads``). The stream is Remembering,
    # float32 (thirty-two blocks each add to it, and a LayerNorm's mean is a
    # difference of large numbers). Decode: _attend_grouped twice, a map
    # each. Any part of the family that LlamaConfig takes is served alone (a
    # cell's rehearsal is the first two layers)
    "m": Served(
        "memory", "memory_mamba",
        lambda cfg, *a: memory_block(cfg, "m", *a), True,
        lambda cfg: [
            ("s6_state", 1, (cfg.s6_inner, cfg.ssm_state), "state"),
            ("s6_conv", 1, (cfg.ssm_conv - 1, cfg.s6_inner), "state")],
        attend_s6, _attend_s6_state, alone=True),
    "w": Served(
        "memory", "memory_attn",
        lambda cfg, *a: memory_block(cfg, "w", *a), True,
        partial(_kv_rows, tag="memory_window", table="slot"),
        lambda cfg, last, *a: attend_diff_tiles(cfg, "w", *a),
        lambda cfg, call, l, mine, *a: _attend_diff_cached(
            cfg, "memory_window", *mine, call.pos - call.base, *a,
            lowest=call.pos - cfg.window + 1 - call.base), alone=True),
    "f": Served(
        "memory", "memory_attn",
        lambda cfg, *a: memory_block(cfg, "f", *a), True,
        partial(_kv_rows, tag="memory_full"),
        lambda cfg, last, *a: attend_diff_tiles(cfg, "f", *a),
        lambda cfg, call, l, mine, *a: _attend_diff_cached(
            cfg, "memory_full", *mine, call.pos, *a), alone=True),
    "g": Served(
        "memory", "memory_gate",
        lambda cfg, *a: memory_block(cfg, "g", *a), True,
        lambda cfg: [], _attends_nothing, _attends_nothing, alone=True),
    "c": Served(
        "memory", "memory_cross",
        lambda cfg, *a: memory_block(cfg, "c", *a), True,
        lambda cfg: [], attend_cross,
        # the newest "f" layer's views
        lambda cfg, call, l, mine, *a: _attend_diff_cached(
            cfg, "memory_cross", *(view[-1] for view in mine), call.pos,
            *a), alone=True, reads="f"),
}


class Store(NamedTuple):
    """One store of a served stack, ``[layers, n_pages or n_slots, page_size
    or 1, *row]`` as its ``table``'s rule says (:data:`TABLES`): ``kind``'s
    (``layers``: its layers times ``sub``, the rows a layer keeps there),
    counted under ``tag``."""
    kind: str
    tag: str
    sub: int
    layers: int
    row: tuple
    table: str

    def shape(self, n_pages: int, n_slots: int, page_size: int) -> tuple:
        rule = TABLES[self.table]
        return (self.layers, n_slots if rule.ids == "slot" else n_pages,
                rule.rows or page_size, *self.row)


def served_kinds(cfg: LlamaConfig) -> str:
    """The stack's layers as letters of :data:`SERVED`."""
    return cfg.kinds or "b" * cfg.n_layers


def served_stores(cfg: LlamaConfig) -> list:
    """The engine's page stores, in THE order every program takes and
    returns them: the table's kinds that the stack has, in the table's
    order, each kind's ``rows`` in theirs."""
    kinds = served_kinds(cfg)
    return [Store(c, tag, sub, kinds.count(c) * sub, row, table)
            for c, kind in SERVED.items() if c in kinds
            for tag, sub, row, table in kind.rows(cfg)]


def _by_kind(layout, values):
    """``values``, one a store of ``layout``: ``{kind: [its own]}``."""
    return {c: [v for v, s in zip(values, layout) if s.kind == c]
            for c in dict.fromkeys(s.kind for s in layout)}


def page_rows(cfg: LlamaConfig):
    """What the engine's page stores keep: ``(family, [(S, row), ...])``, a
    store ``[S, n_pages, page_size, *row]`` each (a store by slot counts
    SLOTS there; the engine says how many): :func:`served_stores`, folded."""
    stores = served_stores(cfg)
    return ("+".join(dict.fromkeys(SERVED[s.kind].family for s in stores)),
            [(s.layers, s.row) for s in stores])


# --- which builder answers for which kind: ONE table, ONE refusal ---------- #
#
# Every kind is the block's or the pattern's, so every path COMPUTES it; a
# builder that no reference holds to the result says what it lacks instead
# of running. A row is one thing a builder may lack: whether ``cfg`` has it,
# the words that name it, and per builder the reason it gives, a text or a
# function of ``cfg`` that gives one (None: that stack it runs). A builder
# that is not in a row's ``why`` RUNS what the row names. A new kind adds a
# row (or its builders to a row) here and touches no builder. Rows are
# walked in this order; those that can meet in one configuration (a stream
# of several rows with the latent kinds, any row with the last) stand in the
# order the builders named them.

BUILDERS = ("make_spmd_train_step", "make_train_step",
            "make_pipeline_train_step", "the MPMD pipeline",
            "LlamaDecodeEngine")


class Refused(NamedTuple):
    present: Callable  # cfg -> the configuration has it
    words: Callable    # cfg -> "no ... (the fields that say so)"
    why: Dict[str, Any]


def _has(kinds: str) -> Callable:
    return lambda cfg: bool(set(cfg.kinds) & set(kinds))


def _module_words(kinds: str) -> Callable:
    return lambda cfg: (f"{kinds}no prediction module (layer_pattern="
                        f"{cfg.layer_pattern!r}, mtp_layers={cfg.mtp_layers})")


def _engine_lacks(cfg: LlamaConfig) -> Optional[str]:
    """What the decode engine says of a stack it does not serve (a dense
    stack's own refusals, or the pattern's); None where the pattern is ONE
    family of :data:`SERVED`, or a part of one that is served alone."""
    by_family: Dict[str, str] = {}
    for c, kind in SERVED.items():
        by_family[kind.family] = by_family.get(kind.family, "") + c
    families = by_family.values()
    kinds = set(served_kinds(cfg))
    part = any(kinds <= set(family) and all(SERVED[c].alone for c in kinds)
               for family in families)
    if cfg.layer_pattern and (part or kinds in map(set, families)):
        return None
    return (f"it serves the kinds of ONE family of its table (SERVED: "
            f"{' | '.join(families)}), all of them (both full "
            f"and window layers), and this stack has "
            f"{' '.join(sorted(kinds))}, of which the table lacks "
            f"{' '.join(sorted(kinds - set(SERVED))) or 'none'}; for the "
            f"'M' / 'E' / '*' "
            f"halves, a part or a mix of families, whole-projection "
            f"QK-norm or an unpatterned routed block no test compares "
            f"its logits with the reference")


REFUSED: Dict[str, Refused] = {
    # a path whose attention cannot band
    "window": Refused(
        _has(BLOCK_KINDS),
        lambda cfg: (f"no 'F' / 'W' layer (layer_pattern="
                     f"{cfg.layer_pattern!r}, window={cfg.window})"),
        {"make_spmd_train_step":
         "its layers attend through the flash kernel, which masks the "
         "causal triangle and has no window (forward and backward), and "
         "under fsdp / tensor a patterned stack has no per-kind gather; "
         "models.llama.loss_fn runs these kinds through attend_tiles",
         "make_pipeline_train_step":
         "its stages run the dense block over the flash kernel, which has "
         "no window, and pass no router's losses on"}),
    # a path no reference holds the kinds' gradients on
    "delta": Refused(
        _has(DELTA_KINDS),
        lambda cfg: (f"no 'D' / 'A' layer (layer_pattern="
                     f"{cfg.layer_pattern!r})"),
        {"make_spmd_train_step":
         "no train step is held to a reference for the gated delta rule's "
         "backward (autodiff through ops/gdn.py's chunked form) or the gated "
         "attention's; models.llama.loss_fn runs the forward of both"}),
    "parallel": Refused(
        _has(PARALLEL_KINDS),
        lambda cfg: (f"no 'P' / 'R' layer (layer_pattern="
                     f"{cfg.layer_pattern!r})"),
        {"make_spmd_train_step":
         "no train step is held to a reference for the parallel block's "
         "backward, and its flash kernel has no window; "
         "models.llama.loss_fn runs the forward through attend_tiles",
         "make_train_step":
         "no train step is held to a reference for the parallel block's "
         "backward; models.llama.loss_fn runs its forward",
         "make_pipeline_train_step":
         "its stages run the dense block alone"}),
    "hybrid": Refused(
        _has(HYBRID_KINDS),
        lambda cfg: (f"no 'H' / 'N' layer (layer_pattern="
                     f"{cfg.layer_pattern!r}; embedding_multiplier, "
                     f"attention_multiplier, residual_multiplier)"),
        {"make_spmd_train_step":
         "no train step is held to a reference for the hybrid blocks' "
         "backward or their three multipliers' (the Mamba-2 mixer's own is "
         "held as the 'M' half); models.llama.loss_fn runs the forward",
         "make_train_step":
         "no train step is held to a reference for the hybrid blocks' "
         "backward or their three multipliers'; models.llama.loss_fn runs "
         "the forward",
         "make_pipeline_train_step":
         "its stages run the dense block alone"}),
    # a path on which no selection travels from layer to layer
    "selected": Refused(
        _has(DSA_KINDS),
        lambda cfg: (f"no 'Y' / 'Z' / 'X' layer (layer_pattern="
                     f"{cfg.layer_pattern!r}, first_layer={cfg.first_layer}, "
                     f"index_topk={cfg.index_topk})"),
        {"make_spmd_train_step":
         "no train step carries a selection from the layer that makes it to "
         "the layers that read it, masks its flash kernel by one, or is held "
         "to a reference for an indexer's backward; LlamaDecodeEngine "
         "serves these kinds",
         "make_train_step":
         "no train step carries a selection from the layer that makes it to "
         "the layers that read it or is held to a reference for an "
         "indexer's backward; LlamaDecodeEngine serves these kinds",
         "make_pipeline_train_step":
         "its stages pass the residual stream alone: a selection made on "
         "one stage has no way to the shared layers of the next",
         "the MPMD pipeline":
         "its stages pass the residual stream alone: a selection made on "
         "one stage has no way to the shared layers of the next"}),
    # a path on which no memory and no layer's keys and values travel from
    # the layer that makes them to the layers that read them
    "memory": Refused(
        _has(MEMORY_KINDS),
        lambda cfg: (f"no 'm' / 'w' / 'f' / 'g' / 'c' layer (layer_pattern="
                     f"{cfg.layer_pattern!r}, ssm_expand={cfg.ssm_expand}, "
                     f"window={cfg.window})"),
        {"make_spmd_train_step":
         "no train step carries a state-space layer's scan output or an "
         "attention layer's keys and values to the layers that read them, "
         "has a backward for the Mamba-1 scan (ops/s6_prefill.py is forward "
         "only) or is held to a reference for differential attention's; "
         "LlamaDecodeEngine serves these kinds",
         "make_train_step":
         "no train step carries a state-space layer's scan output or an "
         "attention layer's keys and values to the layers that read them or "
         "is held to a reference for the Mamba-1 scan's backward; "
         "LlamaDecodeEngine serves these kinds",
         "make_pipeline_train_step":
         "its stages pass the residual stream alone: a memory or keys and "
         "values made on one stage have no way to the layers of the next",
         "the MPMD pipeline":
         "its stages pass the residual stream alone: a memory or keys and "
         "values made on one stage have no way to the layers of the next"}),
    # a stream of several rows a token (``hc_mult > 1``), and a latent block
    # whose score is not as wide as its value
    "wide": Refused(
        lambda cfg: cfg.hc_mult > 1 or (
            _has(LATENT_KINDS)(cfg) and cfg.qk_nope_head_dim
            + cfg.qk_rope_head_dim != cfg.v_head_dim),
        lambda cfg: (
            f"no hyper-connections (hc_mult={cfg.hc_mult}) and "
            f"no latent block whose score width (qk_nope_head_dim + "
            f"qk_rope_head_dim = "
            f"{cfg.qk_nope_head_dim + cfg.qk_rope_head_dim}) is not its "
            f"v_head_dim={cfg.v_head_dim}"),
        {"make_spmd_train_step":
         "no train step is held to a reference for the mixes' backward or "
         "keeps a stream of several rows' recomputation in its account, and "
         "its flash kernel attends q, k and v of one width; "
         "models.llama.loss_fn runs the forward",
         "make_train_step":
         "no train step is held to a reference for the mixes' backward, and "
         "its flash kernel attends q, k and v of one width",
         "make_pipeline_train_step":
         "its stages pass ONE row a token from stage to stage, and no train "
         "step is held to a reference for the mixes' backward"}),
    # a path that runs neither the ``"L"`` / ``"G"`` kinds nor the
    # prediction module
    "latent": Refused(
        lambda cfg: _has(LATENT_KINDS)(cfg) or bool(cfg.mtp_layers),
        _module_words("no 'L' / 'G' layer and "),
        {"make_pipeline_train_step":
         "its stages run the dense block alone, and the prediction module's "
         "second loss needs the last stage's stream AND the first stage's "
         "embedding"}),
    # a path that runs the blocks and refuses the module alone
    "module": Refused(
        lambda cfg: bool(cfg.mtp_layers), _module_words(""),
        {"LlamaDecodeEngine":
         "a prediction module's self-drafted decode steps need a "
         "scheduler that takes more than one token a call"}),
    # routed experts, QK-norm and every pattern, for a dense-only path
    "dense": Refused(
        lambda cfg: bool(cfg.num_experts or cfg.qk_norm or cfg.layer_pattern),
        lambda cfg: (f"no config with num_experts={cfg.num_experts}, "
                     f"qk_norm={cfg.qk_norm}, layer_pattern="
                     f"{cfg.layer_pattern!r}"),
        {"make_pipeline_train_step":
         "its stages pass the residual stream alone, so a router's losses "
         "have no way out, and its layer specs name the dense leaves only",
         "the MPMD pipeline":
         "its stages pass the residual stream alone, so a router's "
         "losses have no way out, and no test runs QK-norm through it",
         "LlamaDecodeEngine": _engine_lacks}),
}


def held_to(cfg: LlamaConfig, who: str) -> None:
    """Raise for the first row of :data:`REFUSED` that ``cfg`` has and the
    builder ``who`` (one of :data:`BUILDERS`) gives a reason against."""
    assert who in BUILDERS, who
    for row in REFUSED.values():
        why = row.why.get(who)
        if why is None or not row.present(cfg):
            continue
        why = why(cfg) if callable(why) else why
        if why:
            raise NotImplementedError(
                f"{who} takes {row.words(cfg)} yet: {why}")


def _period(kinds: str) -> Tuple[str, int]:
    """``(unit, times)``: the shortest ``unit`` whose repetition ``kinds``
    is a prefix of, and how many whole units ``kinds`` holds."""
    for n in range(1, len(kinds) + 1):
        if all(kind == kinds[i % n] for i, kind in enumerate(kinds)):
            return kinds[:n], len(kinds) // n
    return "", 0


def _segments(kinds: str, least: int = 2) -> list:
    """How the walker (:func:`_serve_layers`) takes a stack, ``[(unit, times,
    scanned), ...]`` in layer order: the pattern's whole periods where there
    are ``least`` of them or the period is ONE layer (a stack of one layer is
    a scan of one: the dense block's weights reach it as a scan's ``xs``),
    then, of what is left (a last period cut short, or the whole of a stack
    with fewer periods), from each layer on the SHORTEST unit that stands
    there ``least`` times or more in a row as ``(unit, n, True)`` (a run of
    one kind: ``GLLLL`` -> ``G``, ``L`` x 4; a period inside the stack: ``mw``
    x 8, ``mf``, ``gc`` x 7), and the layers between such stretches together
    as ``(their letters, 1, False)``. ``least`` is 2; a test passes more than
    a stack has to get every layer in line."""
    unit, times = _period(kinds)
    if times < least and len(unit) > 1:
        times = 0
    out = [(unit, times, True)] if times else []
    at = times * len(unit)
    while at < len(kinds):
        for n in range(1, (len(kinds) - at) // max(least, 2) + 1):
            unit, reps = kinds[at:at + n], 1
            while kinds[at + reps * n:at + (reps + 1) * n] == unit:
                reps += 1
            if reps >= least:
                out.append((unit, reps, True))
                at += reps * n
                break
        else:
            if out and not out[-1][2]:
                out[-1] = (out[-1][0] + kinds[at], 1, False)
            else:
                out.append((kinds[at], 1, False))
            at += 1
    return out


def stream_cut(cfg: LlamaConfig) -> int:
    """The layers a PREFILL runs over all positions: behind the last layer
    that KEEPS a row the stream is cut to the last real position
    (:func:`cut_stream`). A layer that keeps nothing (``SERVED[c].rows`` is
    ``[]``) has no earlier position of its own to attend in a decode call,
    so it mixes positions only through what the layers in front of it kept
    or handed on, and the first token needs it at ONE position (the
    decoder-hybrid-decoder's "linear-time prefill": 18 of 32 layers over the
    prompt, 14 over one position). Every other family's every layer keeps
    rows: the cut is the stack's depth, in front of the head, where it
    always was (``ray_tpu_serve_engine_prefill_layers{positions}``)."""
    kinds = served_kinds(cfg)
    return 1 + max(i for i, c in enumerate(kinds) if SERVED[c].rows(cfg))


def _serve_segments(cfg: LlamaConfig) -> list:
    """:func:`_segments` of the layers in front of :func:`stream_cut` and of
    those behind it: no segment spans the cut."""
    kinds, cut = served_kinds(cfg), stream_cut(cfg)
    return _segments(kinds[:cut]) + (_segments(kinds[cut:])
                                     if cut < len(kinds) else [])


def traced_layers(cfg: LlamaConfig) -> int:
    """The layer bodies ONE serving program of ``cfg`` traces
    (``ray_tpu_serve_engine_traced_layers``): a segment's ``unit`` once,
    however often it is scanned."""
    return sum(len(unit) for unit, *_ in _serve_segments(cfg))


def _serve_layers(cfg: LlamaConfig, x, layers, positions, attends, cache,
                  keep, cut=None):
    """THE served layers, whatever their kinds, a segment at a time
    (:func:`_segments`): the pattern's whole periods scanned (one compiled
    body of a period's layers; the scan carries the period's number and not
    a patterned kind's weights), and wherever layers stand IN LINE (a stack
    of fewer than two periods, what a last period cut short leaves) a run of
    two or more layers of ONE kind scanned too, one body a run.

    The ``l``-th layer of kind ``c`` runs ``SERVED[c].block`` and attends
    through ``attends[c](l, mine, *the block's arguments)``. ``x`` is the
    stream as the programs carry it: ``[B, T, dim]``, or ``hc_mult`` rows
    of that apart (:func:`widen_stream`), or with a selection beside it
    (:class:`Selecting`; whatever pytree it is, a scan
    carries it), float32 where a kind's row says so and ``cfg.dtype``
    otherwise; it leaves as it came, and the caller collapses it. ``cache``
    and ``keep`` have an entry a store (:func:`served_stores`): the store's
    view ``[layers, Tpad, *row]``, of which ``mine`` are the layer's own,
    or ``cache`` None (a prefill: nothing cached, ``mine`` None); and how
    many of the call's LAST positions a layer's rows leave. A kind that
    keeps nothing has no entry, no ``mine`` (``[]``) and no rows; a kind
    whose row ``reads`` another gets THAT kind's views, whole, as ``mine``.
    ``cut(x, positions)`` -> ``(x, positions)`` (a prefill's; None: none):
    applied behind layer :func:`stream_cut` where layers stand behind it, so
    that they run on the last real position alone. Returns ``(x,
    rows, shares)``: ``rows`` a store, ``[layers, B, n, *row]`` for a
    prefill and ``[layers, 1, *row]`` for a decode call's one new position;
    ``shares``: the routed assignments' shares, each averaged over the
    layers that report it, and a mixed stream's ``hc_sinkhorn_error``, its
    layers' largest."""
    kinds, layout = served_kinds(cfg), served_stores(cfg)
    # a kind a store of the layout, in its order, then those that keep none
    table = {c: SERVED[c] for c in SERVED if c in kinds}

    def by_kind(values):  # {kind: its stores' own}, [] where it keeps none
        return {**dict.fromkeys(table, []), **_by_kind(layout, values)}

    subs, keep = by_kind([s.sub for s in layout]), by_kind(keep)
    if any(kind.f32 for kind in table.values()):  # a selection stays whole
        x = jax.tree.map(lambda a: a.astype(jnp.float32) if jnp.issubdtype(
            a.dtype, jnp.floating) else a, x)
    if cache is not None:  # a layer's rows together: [n_c, sub, Tpad, *row]
        cache = {c: [a if sub == 1 else a.reshape(-1, sub, *a.shape[1:])
                     for a, sub in zip(views, subs[c])]
                 for c, views in by_kind(cache).items()}
    # a layer's number in its STACK: its number among the layers of the
    # kinds that share the stack (one stack a family, or one a kind)
    stacks = {c: table[c].stack for c in table}

    def layer(x, c, which, l, mine):
        kind = table[c]
        if kind.reads and cache is not None:  # another kind's views, whole
            mine = cache[kind.reads]
        x, stats, rows = kind.block(
            cfg, x, layers if kind.stack is None else layers[kind.stack],
            which, positions, partial(attends[c], l, mine))
        rows = rows if isinstance(rows, tuple) else (rows,)
        if cache is not None and kind.stack is None:  # the table's comment
            rows = tuple(a[:, 0] for a in rows)
        else:  # the positions' axis stands behind [sublayers,] B
            rows = tuple(a[:, :, -n:] if sub > 1 else a[:, -n:]
                         for a, sub, n in zip(rows, subs[c], keep[c]))
        # the block's statistics that OVER_LAYERS lists
        return x, (rows, {k: stats[k] for k in OVER_LAYERS if k in stats})

    def segment(x, unit, times, scanned, first, start):
        """``times`` of the layers ``unit``, scanned or (``times`` 1) in
        line; the first of them in stack ``k`` is layer ``first[k]`` there,
        the first of kind ``c`` is ``start[c]`` among ``c``'s. Returns
        ``(x, {c: (rows, shares)})``, a leading dimension over ``c``'s
        layers."""
        # a scanned kind IS its layers: the scan's own stacking is theirs,
        # with no stack of one in the body and no reshape around it
        one = scanned and len(unit) == 1
        per = {c: unit.count(c) for c in table if c in unit}
        per_stack = {k: sum(n for c, n in per.items() if stacks[c] == k)
                     for k in stacks.values()}

        def past(number, n):  # no operation where the layers are the first:
            return number + n if n else number  # the texts from before runs

        def body(x, xs):
            number, own, cached = xs
            if one:
                x, new = layer(
                    x, unit, past(number, first[stacks[unit]])
                    if own is None else own, past(number, start[unit]),
                    None if cached is None else cached[unit])
                return x, {unit: new}
            out, met = {c: [] for c in per}, dict(first)
            # the layers of the repetitions before this one
            which = {k: number * n for k, n in per_stack.items()}
            l = {c: number * n for c, n in per.items()}
            for c in unit:
                n, k = len(out[c]), stacks[c]
                mine = None if cached is None else [a[n] for a in cached[c]]
                x, new = layer(x, c, which[k] + met[k],
                               l[c] + (start[c] + n), mine)
                met[k] += 1
                out[c].append(new)
            return x, {c: jax.tree.map(lambda *a: jnp.stack(a), *out[c])
                       for c in per}

        # these layers' cached views; a scanned period's, a period's together
        lead = (times,) if scanned and not one else ()
        cached = None if cache is None else {
            c: [a[start[c]:start[c] + times * n].reshape(
                *lead, -1, *a.shape[1:]) for a in cache[c]]
            for c, n in per.items()}
        if not scanned:
            return body(x, (0, None, cached))
        x, new = jax.lax.scan(body, x, (
            jnp.arange(times, dtype=jnp.int32),
            layers if one and table[unit].stack is None else None, cached))
        return x, {c: new[c] if one else jax.tree.map(
            lambda a: a.reshape(-1, *a.shape[2:]), new[c]) for c in per}

    out = {c: [] for c in table}
    first, start = dict.fromkeys(stacks.values(), 0), dict.fromkeys(table, 0)
    behind, done = stream_cut(cfg), 0
    for unit, times, scanned in _serve_segments(cfg):
        if cut is not None and done == behind:
            x, positions = cut(x, positions)
        # device scope: what a prefill runs on the ONE position
        with jax.named_scope("one_position") if (
                cut is not None and done >= behind) else nullcontext():
            x, new = segment(x, unit, times, scanned, first, start)
        done += times * len(unit)
        for c in new:
            out[c].append(new[c])
            start[c] += times * unit.count(c)
            first[stacks[c]] += times * unit.count(c)
    out = {c: jax.tree.map(lambda *a: jnp.concatenate(a), *out[c])
           for c in table}
    shares: Dict[str, list] = {}
    for _, share in out.values():
        for k, a in share.items():
            shares.setdefault(k, []).append(a.reshape(-1))
    # each over its layers as OVER_LAYERS says (a share: their mean)
    shares = {k: OVER_LAYERS[k](jnp.concatenate(a))
              for k, a in shares.items()}
    rows = [a if sub == 1 else a.reshape(-1, *a.shape[2:])
            for c, (mine, _) in out.items() for a, sub in zip(mine, subs[c])]
    if cache is not None:  # the one new row: the batch's 1 for its position
        rows = [a if SERVED[s.kind].stack is None else a[:, 0]
                for a, s in zip(rows, layout)]
    return x, rows, shares


def prefill_with_cache(cfg: LlamaConfig, params, *args, page_size=None):
    """Prefill one sequence into its pages, inside the program: embed, the
    layers (:func:`_serve_layers`), each store written through ITS table,
    the head. With ``cfg.hc_mult > 1`` the stream between embedding and
    head is ``hc_mult`` rows a token, each ``[1, T, dim]`` float32
    (:func:`widen_stream`, apart: copies of the embedding), and the rows of
    the ONE position the head reads are summed (:func:`collapse_stream`).

    ``page_size``: a page's positions where no store says them (a stack
    whose every store keeps a row a SEQUENCE); the engine binds it.
    ``args``: ``*stores, tokens, page_ids, last`` and, for a stack with a
    store by slot (window layers), ``slot_ids`` [min(n, k)] int32 behind
    them: the slots of the LAST pages, the only ones whose window rows are
    written (``k``: ``cfg.window_pages``). ``stores``: the engine's page
    stores (:func:`served_stores`; donated by the engine, updated in place);
    ``tokens`` [1, n * page_size] int32, right-padded (causal masking keeps
    pad garbage out of real positions, and a kind that keeps a state is told
    ``last``); ``page_ids`` [n] int32; ``last`` int32 scalar, the last real
    position. A store whose table is ``"state"`` gets its ONE row, the
    state after ``last``, in the page that holds ``last``. Returns
    ``(*stores, logits
    [vocab] fp32, shares)``: what every layer keeps (rotated keys and
    values, latent rows, index keys) of ALL ``n * page_size`` positions is
    written, the pad positions of the last page included (they hold the pad
    token's keys: finite, and masked by every decode until the sequence
    itself overwrites them), and the head is applied to position ``last``
    alone.

    WHERE THE STREAM IS CUT to position ``last``: behind the last layer that
    keeps a row (:func:`stream_cut`). In every family but one every layer
    keeps rows (its later positions attend its earlier ones through them), so
    all layers run over all positions and the cut stands in front of the
    head, one row into the final norm. The decoder-hybrid-decoder's layers
    behind its ONE full attention layer keep nothing (``"g"`` reads the
    memory at its own position, ``"c"`` the full layer's keys and values):
    the first token needs them at ``last`` alone, the walker cuts the stream
    there (:func:`cut_stream`) and they run on ONE position, ``positions =
    last``, the cross layers attending the rows the full layer made in this
    same call. ``shares``: a routed model's assignment shares (``{}`` for a
    dense one) and a mixed stream's ``hc_sinkhorn_error``."""
    layout, slot_ids = served_stores(cfg), None
    if any(s.table == "slot" for s in layout):
        *args, slot_ids = args
    *stores, tokens, page_ids, last = args
    ps = _page_size(stores, layout, page_size)
    ids = {"page": page_ids, "slot": slot_ids}
    x = remembering_stream(cfg, selecting_stream(cfg, widen_stream(
        cfg, embed_tokens(cfg, params, tokens, None), True)))
    positions = positions_of(*tokens.shape)
    keep = {"page": tokens.shape[1], "slot": 0 if slot_ids is None
            else slot_ids.shape[0] * ps}
    if any(s.table == "state" for s in layout):
        # ONE row a sequence: into the page of the last real position
        ids["state"], keep["state"] = page_ids[last // ps][None], 1
    # the layers behind the last one that keeps a row run on ONE position
    early = stream_cut(cfg) < len(served_kinds(cfg))
    x, rows, shares = _serve_layers(
        cfg, x, params["layers"], positions,
        {c: lambda l, mine, *a, c=c: SERVED[c].prefill(cfg, last, *a)
         for c in set(served_kinds(cfg))},
        None, [keep[s.table] for s in layout],
        (lambda x, positions: (cut_stream(x, last), jnp.full(
            (tokens.shape[0], 1), last, jnp.int32))) if early else None)
    stores = [_write_pages(pages, new[:, 0], ids[s.table])
              for pages, new, s in zip(stores, rows, layout)]
    # final_norm and the head are per position: one row, not T
    x = collapse_stream(cfg, stream_of(x) if early else jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, last, 1, axis=1),
        stream_of(x)))
    logits = head_logits(cfg, x, params["final_norm"], _head(cfg, params),
                         params.get("final_norm_b"))
    return (*stores, logits[0, 0], shares)


def decode_step_with_cache(cfg: LlamaConfig, params, *args, page_size=None):
    """One decode step of one sequence against the page stores: each
    store gathered through ITS table where its kind's row says so
    (``SERVED[c].decode``), embed, the layers (:func:`_serve_layers`), the
    head, the new position's rows written. The stream is widened and
    collapsed as :func:`prefill_with_cache` does it.

    ``args``: ``*stores, token, pos, page_ids`` and, for a stack with a
    store by slot (window layers), ``slot_ids, first`` behind them.
    ``token`` [1] int32; ``pos`` int32 scalar (the write position = tokens
    so far); ``page_ids`` [n] int32, the sequence's page table in order;
    ``slot_ids`` [min(n, k)] int32: the slots of the table's pages ``first
    ..`` (``first`` int32 scalar), which hold every position ``pos``'s
    window reaches and ``pos`` itself: a store by slot gathers those alone
    and writes the new position's rows into its page's slot.
    The table's pages are gathered on the device into the ``[S, n *
    page_size, *row]`` view of each store (positions >= ``pos`` are
    masked), and what the new position keeps is written at
    ``(page_ids[pos // page_size], pos % page_size)``. A store whose table
    is ``"state"`` is READ AND REWRITTEN: its view is the ONE row of the page
    that holds ``pos - 1`` (:data:`TABLES`), and the layer's new state goes
    into the page that holds ``pos``. Returns ``(*stores,
    logits [vocab] fp32)``. ``pos`` and the page ids are traced, so one
    compilation covers every step at a given page count."""
    layout, slot_ids = served_stores(cfg), None
    if any(s.table == "slot" for s in layout):
        *args, slot_ids, first = args
    *stores, token, pos, page_ids = args
    ids = {"page": page_ids, "slot": slot_ids}
    ps = _page_size(stores, layout, page_size)
    if any(s.table == "state" for s in layout):
        # the state BEFORE this position: the page of the one before it
        ids["state"] = page_ids[jnp.maximum(pos - 1, 0) // ps][None]
    cached = [_read_pages(pages, ids[s.table])
              for pages, s in zip(stores, layout)]
    x = remembering_stream(cfg, selecting_stream(cfg, widen_stream(
        cfg, embed_tokens(cfg, params, token[None, :], None), True), True))
    positions = jnp.full((1, 1), pos, dtype=jnp.int32)
    call = SimpleNamespace(
        pos=pos, page_ids=page_ids, stores=_by_kind(layout, stores),
        base=None if slot_ids is None else first * ps)
    x, rows, _ = _serve_layers(
        cfg, x, params["layers"], positions,
        {c: partial(SERVED[c].decode, cfg, call)
         for c in set(served_kinds(cfg))},
        cached, [1] * len(layout))
    logits = head_logits(cfg, collapse_stream(cfg, stream_of(x)),
                         params["final_norm"], _head(cfg, params),
                         params.get("final_norm_b"))
    page = page_ids[pos // ps]
    at = {"page": (0, page, pos % ps), "state": (0, page, 0)}
    if slot_ids is not None:
        at["slot"] = (0, slot_ids[pos // ps - first], pos % ps)
    stores = [jax.lax.dynamic_update_slice(
        pages, new[:, :, None].astype(pages.dtype),
        at[s.table] + (0,) * (pages.ndim - 3))
        for pages, new, s in zip(stores, rows, layout)]
    return (*stores, logits[0, 0])


def _page_size(stores, layout, given=None) -> int:
    """A page's positions, read off a store that keeps a row a position;
    ``given`` (the programs' ``page_size``, which the engine binds) where
    every store keeps a row a SEQUENCE and none says it."""
    return next((pages.shape[2] for pages, s in zip(stores, layout)
                 if TABLES[s.table].rows is None), given)


def copy_page_in_stores(stores, src, dst):
    """Slab ``src`` duplicated into ``dst`` (both traced: page ids, or
    slots) in every one of ``stores``, the stores that go by ONE kind of id
    (a page's positions and, with them, a sequence's state that lies in the
    page)."""
    return tuple(jax.lax.dynamic_update_slice(
        pages, _page_slab(pages, src), (0, dst) + (0,) * (pages.ndim - 2))
        for pages in stores)


# the leaves the serving programs multiply (each stands under an
# ``.astype(cfg.dtype)`` in embed_tokens / the head, in the block's _qkv /
# wo / dense _mlp_half, in the double layer's _latent_half and dense
# feed-forwards, and the experts' in routed_mlp). By name, not by rank: the
# stacked norms are two-dimensional too, and rms_norm and the router use
# theirs in float32.
_MATMUL_TOP = ("embedding", "lm_head")
_MATMUL_LAYER = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                 "wq_a", "wq_b", "wkv_a", "wkv_b",
                 "ffn_gate", "ffn_up", "ffn_down",
                 # the indexer's query and key projections; the head
                 # weights' (ww) is used in float32, as a router is
                 "wqi", "wki",
                 # the delta rule's projections and the gated shared expert
                 # (its gate's vector w_sg, A_log, dt_bias and the
                 # convolution are used in float32)
                 "w_qkvz", "w_ba", "w_out", "ws_gate", "ws_up", "ws_down",
                 # the Mamba-2 mixer's in-projection (w_out is the delta
                 # rule's name too; its convolution and bias, dt_bias, A_log,
                 # D and the gated norm are used in float32)
                 "w_in",
                 # the latent block's ungated shared expert (a sublayer's
                 # hc_phi is used in float32, as a router is)
                 "shared_gate", "shared_up", "shared_down",
                 # the Mamba-1 mixer's two small products, differential
                 # attention's ONE key-and-value projection and the gated
                 # memory unit's two (the biases, the lambda vectors, A_log,
                 # dt_bias, D and the convolution are used in float32)
                 "w_x", "w_dt", "wkv", "wg_in", "wg_out")


def serving_params(cfg: LlamaConfig, params) -> Dict[str, Any]:
    """``params`` with every leaf the serving programs multiply held in
    ``cfg.dtype``: the round-to-nearest conversion the programs apply in
    front of each product, made once, in one jitted call over the leaves
    that need it. Every other leaf (the norms, a router and its bias; a
    weight already in ``cfg.dtype``) is the array it was, and a tree with
    nothing to convert is returned as it is. Traced inside the program that
    builds the weights, the float32 ones are that program's temporaries.
    A patterned stack's leaves lie a level down, under their kind."""
    cd = jnp.dtype(cfg.dtype)

    def matmuls(tree, names):
        return {k: tree[k] for k in names
                if k in tree and tree[k].dtype != cd}

    top = matmuls(params, _MATMUL_TOP)
    kinds = (params["layers"] if cfg.layer_pattern
             else {"": params["layers"]})
    layers = {kind: matmuls(tree, _MATMUL_LAYER)
              for kind, tree in kinds.items()}
    if not (top or any(layers.values())):
        return params
    top, layers = jax.jit(lambda tree: jax.tree.map(
        lambda w: w.astype(cd), tree))((top, layers))
    layers = {kind: {**kinds[kind], **layers[kind]} for kind in kinds}
    return {**params, **top,
            "layers": layers if cfg.layer_pattern else layers[""]}


class LlamaDecodeEngine:
    """Paged-KV decode engine over the functional llama model — the
    engine protocol :class:`ray_tpu.serve.decode.DecodeScheduler` drives
    (prefill/decode/copy_page + pool/prefix_cache/page_size).

    A KIND IS A ROW OF :data:`SERVED`: what a layer letter keeps a position,
    by which table, through which block, attended how in prefill and in
    decode is said there and nowhere here. The engine serves a stack whose
    kinds are ONE family's, all of them (dense blocks; all ``"S"``; ``"F"``
    with ``"W"``; all ``"I"``; ``"D"`` with ``"A"``; ``"L"`` with ``"G"``,
    whose stream may be ``hc_mult`` rows a token:
    ``ray_tpu_serve_engine_stream_bytes``; ``"P"`` with ``"R"``; ``"H"``
    with ``"N"``; ``"Y"`` / ``"Z"`` / ``"X"``, whose stream carries a
    selection from the layers that make one to those that reuse it:
    ``ray_tpu_serve_engine_selecting_layers{role}``; ``"m"`` / ``"w"`` /
    ``"f"`` / ``"g"`` / ``"c"``, whose stream carries a memory and one
    layer's keys and values to layers that keep nothing, and whose prefill
    runs those on one position:
    ``ray_tpu_serve_engine_prefill_layers{positions}``). A kind without a row
    (the ``"M"`` / ``"E"`` / ``"*"`` halves), a part or a mix of families,
    whole-projection
    QK-norm, the UNPATTERNED routed block and a prediction module
    (``mtp_layers``) are refused: no test holds their logits to a reference
    here.

    ``params`` is the tree the programs run on, :func:`serving_params`': the
    matmul weights in ``cfg.dtype``, converted ONCE here and not inside
    every call, the norms, a router and its bias in float32. A float32 tree
    passed in (a trainer's) is converted and not kept. At bfloat16 that is
    two bytes a parameter on the device (3.8 GB at 1.89B parameters), which
    ``ray_tpu_serve_engine_weight_bytes{dtype}`` reports.

    THE STORES. Physical pages live ON THE DEVICE, in float32 arrays,
    ``stores`` (:func:`served_stores`: one a row of every kind's ``rows``),
    each laid out as its table's rule says (:data:`TABLES`):
    ``[layers * sublayers, n_pages, page_size, *row]`` by pool page id for
    a row whose table is ``"page"``, ``[.., n_slots, ..]`` by SLOT (below)
    for ``"slot"``, ``[.., n_pages, 1, *row]`` for ``"state"``: ONE row a
    page, the state a recurrent layer keeps A SEQUENCE (after the newest
    position written in that page), which a decode call reads and rewrites
    where every other row is appended, and which ``copy_page`` copies with
    its page, so that a shared page's state is never written. They are
    read and written only inside three jitted
    programs that take them donated and return them: prefill writes the
    layers' rows into the pages (slots) it is given, decode gathers the
    sequence's page table into a page-padded view (positions beyond the
    true length are masked, so a compilation per page count serves every
    sequence and step) and writes the new position, copy_page duplicates
    one page, the stores of one table a call. A call moves token ids and
    page ids in and one ``[vocab]`` row of float32 logits out.

    WINDOW SLOTS. A window layer needs a position's keys and values for
    ``cfg.window`` positions and no longer, so its stores have ``n_slots``
    page slabs, fewer than the pool has pages, and the engine owns the map
    pool page -> slot (``n_slots`` is non-zero exactly when a store goes by
    slot). With ``k = cfg.window_pages(page_size)`` (the pages a
    window can touch) and ``P = ceil(max_seq_len / page_size)`` (the longest
    sequence's pages)::

        n_slots = min(n_pages, ceil(n_pages / P) * (k + 2))

    each of the longest sequences the pool can hold keeps ``k`` pages of
    window, one copied tail page and one page opened by decode. That sizes
    the stores for LONG sequences; short ones need a slot for every page
    (16 of five pages fill 80 pages and would need 80 slots), so slots and
    not pages may bound how many run: ``DecodeScheduler`` admits a sequence
    only while ``window_slots_needed`` of every running one and its own fit
    in ``n_slots``, and a decode call that opens a page always finds its
    slot. A page gets
    its slot when a window layer first touches it: prefill of ``n`` pages
    writes window rows for the LAST ``min(n, k)`` pages alone (earlier
    pages of a long prompt never get a slot), decode gathers the slots of
    the ``min(n, k)`` pages that end at the position's own (a page no
    prefill wrote gets its slot then), ``copy_page`` gives the copy a slot
    where the source has one. A slot is freed when the pool frees its page
    (``PagePool.release_hooks``). Short of slots the engine evicts idle
    prefixes as the pool does under page pressure; a call that still finds
    none raises ``WindowSlotsOOM`` (a ``CacheOOM``) with nothing assigned,
    and ``DecodeScheduler`` keeps the prefill queued. A whole-prompt prefix
    hit stays valid: the pages its window layers need are the last ``k``,
    which hold their slots as long as the entry holds its pages. Slots are
    NOT freed behind a long decode as it advances.
    ``ray_tpu_serve_engine_window_slots{state}`` reports total and used.

    One caller at a time (the scheduler's lock covers a whole iteration):
    a call hands the stores to its program and takes the returned ones."""

    def __init__(self, cfg: Optional[LlamaConfig] = None, params=None, *,
                 n_pages: int = 64, page_size: int = 8, seed: int = 0):
        import numpy as np

        from ray_tpu.serve.kv_cache import PagePool, PrefixCache
        from ray_tpu.util.device_telemetry import backend_devices

        _t_build = _fr.now()
        backend_devices()  # a replica's first touch: jax.backend_init
        self.cfg = cfg or LlamaConfig.debug()
        held_to(self.cfg, "LlamaDecodeEngine")
        kinds = set(served_kinds(self.cfg))
        _t = _fr.now()
        if params is None:
            # one jitted program, not a dozen eager ones: at 664.6M
            # parameters the eager form spends 67 s on a v5e, nearly all of
            # it compiling per-leaf RNG programs (measured, PR 21). The
            # conversion is part of it: the float32 tree is never resident
            params = jax.jit(lambda key: serving_params(
                self.cfg, init_params(self.cfg, key)))(
                    jax.random.PRNGKey(seed))
        else:
            params = serving_params(self.cfg, params)
        if _t:  # recorder on: the span closes when the tree is on the device
            jax.block_until_ready(params)
        _sp_engine_weights.end(_t)
        self.params = params
        # both tags always: a float32 engine reads bfloat16 = 0, and not
        # what an earlier engine of this process left there
        by_dtype = {"bfloat16": 0, "float32": 0}
        for leaf in jax.tree.leaves(self.params):
            by_dtype[leaf.dtype.name] = (by_dtype.get(leaf.dtype.name, 0)
                                         + leaf.nbytes)
        for name, nbytes in by_dtype.items():
            _g_engine_weight_bytes.set(float(nbytes), tags={"dtype": name})
        _g_engine_traced_layers.set(float(traced_layers(self.cfg)))
        stack = served_kinds(self.cfg)
        cut = stream_cut(self.cfg)
        for positions, n in (("all", cut), ("one", len(stack) - cut)):
            _g_engine_prefill_layers.set(float(n),
                                         tags={"positions": positions})
        for role, letters in (("select", "I" + DSA_FULL), ("reuse", "Z")):
            _g_engine_selecting_layers.set(
                float(sum(stack.count(c) for c in letters)),
                tags={"role": role})
        self.page_size = int(page_size)
        self.pool = PagePool(n_pages, page_size)
        self.prefix_cache = PrefixCache(self.pool)
        self._np = np
        layout = served_stores(self.cfg)
        # the stores' places by whose ids find their slabs: a copy's
        self._by_ids = {ids: [i for i, s in enumerate(layout)
                              if TABLES[s.table].ids == ids]
                        for ids in ("page", "slot")}
        # a store by slot has n_slots slabs (the class docstring's rule);
        # the slots' map is one caller's at a time, as the stores are
        self.window_pages = self.n_slots = 0
        if self._by_ids["slot"]:
            self.window_pages = self.cfg.window_pages(page_size)
            longest = -(-self.cfg.max_seq_len // page_size)
            self.n_slots = min(n_pages, -(-n_pages // longest)
                               * (self.window_pages + 2))
        self._slot_of: Dict[int, int] = {}
        self._free_slots = list(range(self.n_slots - 1, -1, -1))
        if self.n_slots:
            self.pool.release_hooks.append(self._free_slots_of)
        stores = []
        for kind, its in _by_kind(layout, layout).items():
            _t = _fr.now()
            stores += [jnp.zeros(s.shape(n_pages, self.n_slots, page_size),
                                 jnp.float32) for s in its]
            if _t:  # as the weights' span
                jax.block_until_ready(stores)
            _sp_engine_stores.end(_t, kind)
        self.stores = tuple(stores)
        # every tag of the table always, as above: a row a position under
        # page_bytes, a row a sequence under state_bytes
        held = {(tag, TABLES[table].rows is None): 0
                for kind in SERVED.values()
                for tag, _, _, table in kind.rows(self.cfg)}
        for s in layout:
            held[s.tag, TABLES[s.table].rows is None] += (
                4 * s.layers * math.prod(s.row))
        for (tag, a_position), nbytes in held.items():
            if a_position:
                _g_engine_page_bytes.set(float(nbytes), tags={"kind": tag})
            else:
                _g_engine_state_bytes.set(float(nbytes), tags={"part": tag})
        self._note_slots()
        _g_engine_stream_bytes.set(float(
            self.cfg.hc_mult * self.cfg.dim * (4 if any(
                SERVED[c].f32 for c in kinds) else jnp.dtype(
                    self.cfg.dtype).itemsize)))
        groups = {"program": 0, "layer": 0}
        if self.cfg.num_experts:  # the routed kinds it serves
            from ray_tpu.ops.moe import expert_groups

            _watch_routed_calls()
            w_up = self.params["layers"][SERVED[layout[0].kind].stack]["w_up"]
            groups = {"program": expert_groups(w_up, self.cfg.dtype),
                      "layer": w_up.shape[1]}
        for part, n in groups.items():
            _g_engine_expert_groups.set(float(n), tags={"part": part})
        # the kinds that attend fewer keys than they see say how many
        self._attended = [SERVED[c].attended for c in sorted(kinds)
                          if SERVED[c].attended]
        donated = tuple(range(1, 1 + len(layout)))  # the stores, every call
        self._prefill_fn = observe_compiled(
            jax.jit(partial(prefill_with_cache, self.cfg,
                            page_size=self.page_size),
                    donate_argnums=donated),
            "llama.prefill")
        self._decode_fn = observe_compiled(
            jax.jit(partial(decode_step_with_cache, self.cfg,
                            page_size=self.page_size),
                    donate_argnums=donated),
            "llama.decode")
        self._copy_fn = observe_compiled(
            jax.jit(copy_page_in_stores, donate_argnums=0),
            "llama.copy_page")
        self.prefill_calls = 0
        self.decode_calls = 0
        self._buckets: Dict[str, set] = {"prefill": set(), "decode": set()}
        # compiled here, a shape of its own a table: a server warms prefill
        # and decode by running them, but may never copy a page before its
        # first prefix hit
        for ids in self._by_ids:
            self._copy(ids, 0, 0)
        _sp_engine_build.end(_t_build)

    # ---- window slots (a stack with window layers; else n_slots is 0)

    def window_slots_needed(self, n_prompt: int, max_tokens: int) -> int:
        """The most slots one sequence can hold at once: its prompt's last
        ``k`` pages (counted again for every sequence that shares a prefix
        entry's), a copied tail page, and every page its ``max_tokens``
        open, since none is freed behind a decode. What
        ``DecodeScheduler`` counts at admission; 0 without window layers."""
        if not self.n_slots:
            return 0
        ps = self.page_size
        prompt = -(-n_prompt // ps)
        return (min(prompt, self.window_pages) + (1 if n_prompt % ps else 0)
                + -(-(n_prompt + max_tokens) // ps) - prompt)

    def _note_slots(self) -> None:
        for state, n in (("total", self.n_slots),
                         ("used", len(self._slot_of))):
            _g_engine_window_slots.set(float(n), tags={"state": state})

    def _free_slots_of(self, pages) -> None:
        """``PagePool.release``'s hook: the freed pages' slots are free."""
        for page in pages:
            slot = self._slot_of.pop(page, None)
            if slot is not None:
                self._free_slots.append(slot)
        self._note_slots()

    def _slots_for(self, pages):
        """The slots of ``pages``, in order, as the ``int32`` table a
        program takes; a page without one is assigned one now. All or
        nothing: short of slots, idle prefixes are evicted, and if that
        does not free enough ``WindowSlotsOOM`` is raised with no slot
        assigned."""
        from ray_tpu.serve.kv_cache import WindowSlotsOOM

        new = [p for p in pages if p not in self._slot_of]
        if len(new) > len(self._free_slots):
            self.prefix_cache.evict_lru(
                enough=lambda: len(self._free_slots) >= len(new))
            if len(new) > len(self._free_slots):
                raise WindowSlotsOOM(
                    f"{len(new)} pages need a window slot and "
                    f"{len(self._free_slots)} of {self.n_slots} are free")
        for page in new:
            self._slot_of[page] = self._free_slots.pop()
        if new:
            self._note_slots()
        return self._np.asarray([self._slot_of[p] for p in pages],
                                self._np.int32)

    def _note_bucket(self, kind: str, tpad: int) -> None:
        buckets = self._buckets[kind]
        if tpad not in buckets:
            buckets.add(tpad)
            _g_decode_buckets.set(float(len(buckets)),
                                  tags={"kind": kind})

    # Every input below is a numpy array of a fixed dtype, and nothing on a
    # call's path is an eager jnp operation: a Python int (a weak type) or
    # an eager slice would be a compilation of its own the first time it
    # is met, inside a server's measured window.

    def prefill(self, tokens, pages):
        np = self._np
        self.prefill_calls += 1
        T = len(tokens)
        n_pages = len(pages)
        tpad = n_pages * self.page_size
        if not 0 < T <= tpad:
            raise ValueError(f"{T} tokens do not fit {n_pages} pages of "
                             f"{self.page_size}")
        self._note_bucket("prefill", tpad)
        _t = _fr.now()
        toks = np.zeros((1, tpad), np.int32)
        toks[0, :T] = tokens
        window = ()
        if self.n_slots:  # the last pages' slots: the only window rows kept
            _t_slots = _fr.now()
            window = (self._slots_for(pages[-self.window_pages:]),)
            _sp_window_slots.end(_t_slots, n_pages)
        # the read below would wait for the results anyway: waiting here
        # puts the device's time in its own span
        *stores, logits, shares = jax.block_until_ready(self._prefill_fn(
            self.params, *self.stores, toks,
            np.asarray(pages, np.int32), np.asarray(T - 1, np.int32),
            *window))
        _sp_prefill_program.end(_t, n_pages)
        _t = _fr.now()
        # the stores were donated: only a call that returned hands back
        # live ones, and only those replace the engine's
        self.stores = tuple(stores)
        _sp_prefill_kv.end(_t, n_pages)
        _t = _fr.now()
        # a routed model's shares come with the logits: one read
        last, shares = jax.device_get((logits, shares))
        _sp_prefill_logits.end(_t, n_pages)
        self._note_selected("prefill", T)
        _note_stream(shares)
        if shares or self.cfg.num_experts:  # every expert here: held is 1.0
            # where the assignments fell and, of a held range, the places a
            # row was made for
            _note_assignments(shares, tpad * self.cfg.experts_per_token,
                              self.cfg)
        return last

    def decode(self, pos, token, pages):
        np = self._np
        self.decode_calls += 1
        n_pages = len(pages)
        tpad = n_pages * self.page_size
        if not 0 <= pos < tpad:
            raise ValueError(f"position {pos} lies outside {n_pages} pages "
                             f"of {self.page_size}")
        self._note_bucket("decode", tpad)
        _t_call = _t = _fr.now()
        window = ()
        if self.n_slots:  # the pages the position's window can reach
            _t_slots = _fr.now()
            reach = min(n_pages, self.window_pages)
            first = max(0, pos // self.page_size - reach + 1)
            window = (self._slots_for(pages[first:first + reach]),
                      np.asarray(first, np.int32))
            _sp_window_slots.end(_t_slots, n_pages)
        # the program cannot start before its inputs are on the device,
        # and the read waits for its results: the two waits add none
        inputs = jax.block_until_ready(jax.device_put(
            (np.asarray([token], np.int32), np.asarray(pos, np.int32),
             np.asarray(pages, np.int32), *window)))
        _sp_decode_upload.end(_t, n_pages)
        _t = _fr.now()
        *stores, logits = jax.block_until_ready(self._decode_fn(
            self.params, *self.stores, *inputs))
        _sp_decode_program.end(_t, n_pages)
        _t = _fr.now()
        self.stores = tuple(stores)  # as in prefill
        out = np.asarray(logits, np.float32)
        _sp_decode_readback.end(_t, n_pages)
        _sp_decode.end(_t_call, n_pages)
        self._note_selected("decode", pos + 1)
        return out

    def _note_selected(self, program: str, n: int) -> None:
        """The share of the visible keys the call's queries attended, from
        the call's own length ``n``, as the kinds that attend fewer say."""
        for attended in self._attended:
            visible, kept = attended(self.cfg, program, n)
            _g_engine_selected_share.set(kept / visible,
                                         tags={"program": program})

    def _copy(self, ids: str, src: int, dst: int) -> None:
        """Slab ``src`` of the stores that go by ``ids`` copied into
        ``dst``."""
        np, which = self._np, self._by_ids[ids]
        if not which:
            return
        stores = list(self.stores)
        copied = self._copy_fn(tuple(stores[i] for i in which),
                               np.asarray(src, np.int32),
                               np.asarray(dst, np.int32))
        for i, pages in zip(which, copied):
            stores[i] = pages
        self.stores = tuple(stores)

    def copy_page(self, src: int, dst: int) -> None:
        # the stores by page id in one call (a page's positions and the
        # state that lies with it); those by slot in one, where the source
        # has a slot (the copy's is assigned first: short of slots nothing
        # is copied)
        moves = {"page": (src, dst)}
        if src in self._slot_of:
            moves["slot"] = self._slots_for([src, dst])
        for ids, (a, b) in moves.items():
            self._copy(ids, a, b)


# --------------------------------------------------------------------------- #
# Train step (GSPMD)
# --------------------------------------------------------------------------- #


def make_train_step(cfg: LlamaConfig, mesh, optimizer=None, rules=None):
    """Build (init_state, train_step) jitted over the mesh.

    State = {params, opt_state, step}; shardings derive from logical axes.
    XLA inserts all collectives (grad psum over data/fsdp, all-gathers for
    fsdp params, tensor-parallel reduce-scatters) from the shardings alone.
    """
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    # init draws params THROUGH the shardings: the same seed yields the
    # same params on every mesh layout because jax.random is
    # sharding-invariant (test_parallelism_consistency)
    rules = rules or DEFAULT_RULES
    held_to(cfg, "make_train_step")
    optimizer = optimizer or optax.adamw(3e-4, b1=0.9, b2=0.95,
                                         weight_decay=0.1)
    axes = param_logical_axes(cfg)
    param_shardings = jax.tree.map(
        lambda ax: logical_sharding(ax, mesh, rules), axes,
        is_leaf=lambda x: isinstance(x, tuple))
    repl = NamedSharding(mesh, P())
    batch_axes = tuple(a for a in ("slice", "data", "fsdp")
                       if a in mesh.axis_names)
    # tokens shard over batch only; the seq axis shards *activations* (a
    # sharding constraint inside forward) — raw token length is T+1, not
    # necessarily divisible by the seq axis
    data_sharding = NamedSharding(mesh, P(batch_axes if batch_axes else None))

    from ray_tpu.parallel.sharding import opt_state_shardings

    def init_state(key):
        params = init_params(cfg, key)
        opt_state = optimizer.init(params)
        return {"params": params, "opt_state": opt_state,
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
        "llama.gspmd_init")

    def step_fn(state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, mesh))(state["params"])
        updates, new_opt = optimizer.update(grads, state["opt_state"],
                                            state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return ({"params": new_params, "opt_state": new_opt,
                 "step": state["step"] + 1}, loss)

    train_step = observe_compiled(
        jax.jit(
            step_fn,
            in_shardings=(state_shardings, data_sharding),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0,),
        ),
        "llama.gspmd_train_step")
    return init_jit, train_step, data_sharding, state_shardings


# --------------------------------------------------------------------------- #
# Tensor-parallel collectives (manual/Megatron style)
# --------------------------------------------------------------------------- #


def tp_psum_pair(axis):
    """Megatron 'f'/'g' collective pair for EXACT grads when
    ``value_and_grad`` runs INSIDE a shard_map body with replication
    checking off: check-off autodiff transposes a raw ``psum`` back to a
    ``psum``, which re-sums the already-replicated cotangent axis-size
    times (factor-T grad inflation on every upstream leaf). The pair
    writes the correct per-device backward explicitly — ``f`` (identity
    fwd / psum bwd) enters a column-parallel region, ``g`` (psum fwd /
    identity bwd) leaves a row-parallel one. The pipeline step
    differentiates OUTSIDE shard_map and keeps the raw psum."""

    @jax.custom_vjp
    def f(x):
        return x

    f.defvjp(lambda x: (x, None),
             lambda _, ct: (jax.lax.psum(ct, axis),))

    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, axis)

    g.defvjp(lambda x: (jax.lax.psum(x, axis), None),
             lambda _, ct: (ct,))

    return f, g


def vp_embed(cfg: LlamaConfig, emb_local, tokens, axis, gp):
    """Vocab-parallel embedding lookup on a local shard [V/t, dim]:
    masked local take + psum over ``axis`` assembles each token's row
    from whichever device owns its id. ``gp`` is the psum-fwd /
    identity-bwd half of :func:`tp_psum_pair`, so the backward
    scatter-adds straight into the local rows."""
    vloc = emb_local.shape[0]
    off = jax.lax.axis_index(axis) * vloc
    local = tokens - off
    ok = (local >= 0) & (local < vloc)
    rows = emb_local.astype(cfg.dtype)[jnp.clip(local, 0, vloc - 1)]
    return gp(jnp.where(ok[..., None], rows, 0))


def vp_chunk_nll(cfg: LlamaConfig, head_local, axis, gp):
    """Per-chunk NLL against a vocab-sharded head [d, V/t] (Megatron
    vocab-parallel cross-entropy): replicated logsumexp from
    pmax-of-local-max plus psum of the local sum-exp; the target logit
    by masked local take + psum. ``stop_gradient`` sits on the pmax
    OPERAND because pmax has no transpose rule — the shift is the usual
    gradient-free logsumexp stabilizer anyway."""
    vloc = head_local.shape[-1]

    def chunk_nll(x_c, t_c):
        logits = _logits(cfg, x_c, head_local)
        m = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(logits, -1)), axis)
        lse = jnp.log(gp(jnp.sum(jnp.exp(logits - m[..., None]), -1))) + m
        off = jax.lax.axis_index(axis) * vloc
        local = t_c - off
        ok = (local >= 0) & (local < vloc)
        tlogit = gp(jnp.where(
            ok,
            jnp.take_along_axis(logits,
                                jnp.clip(local, 0, vloc - 1)[..., None],
                                axis=-1)[..., 0],
            0.0))
        return lse - tlogit

    return chunk_nll


# --------------------------------------------------------------------------- #
# Pipeline-parallel train step (pipe [+ tensor/data] mesh axes)
# --------------------------------------------------------------------------- #


def make_pipeline_train_step(cfg: LlamaConfig, mesh, num_microbatches: int,
                             optimizer=None):
    """GPipe pipeline-parallel train step over a mesh with a ``pipe`` axis.

    Layers are split into ``mesh.shape['pipe']`` contiguous stages (params
    reshaped [L] -> [P, L/P], stage dim sharded over ``pipe``); the
    microbatch schedule is :func:`ray_tpu.parallel.pipeline.pipelined_apply`
    inside one shard_map over the full mesh. ``tensor`` (if present) shards
    heads/mlp within each stage with explicit psums; ``data``/``fsdp`` axes
    act as pure data parallelism here (shard_map's autodiff inserts the
    gradient psums). Embedding/lm_head run outside the pipelined region
    under GSPMD, replicated over ``pipe``.

    Returns (init_jit, train_step, data_sharding, state_shardings) — the
    same contract as :func:`make_train_step`.
    """
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.pipeline import (merge_microbatches,
                                           pipelined_apply,
                                           split_microbatches)

    if "pipe" not in mesh.axis_names:
        raise ValueError("mesh has no 'pipe' axis")
    held_to(cfg, "make_pipeline_train_step")
    n_stages = mesh.shape["pipe"]
    if cfg.n_layers % n_stages:
        raise ValueError(f"{cfg.n_layers} layers not divisible by "
                         f"{n_stages} pipeline stages")
    optimizer = optimizer or optax.adamw(3e-4, b1=0.9, b2=0.95,
                                         weight_decay=0.1)
    ta = "tensor" if ("tensor" in mesh.axis_names
                      and mesh.shape["tensor"] > 1) else None
    # differentiated OUTSIDE the shard_map: the raw psum (tp_psum_pair)
    row_out = (lambda y: jax.lax.psum(y, ta)) if ta else None
    batch_axes = tuple(a for a in ("slice", "data", "fsdp")
                       if a in mesh.axis_names)
    bspec = batch_axes if batch_axes else None

    layer_specs = {
        "wq": P("pipe", None, None, ta),
        "wk": P("pipe", None, None, ta),
        "wv": P("pipe", None, None, ta),
        "wo": P("pipe", None, ta, None),
        "w_gate": P("pipe", None, None, ta),
        "w_up": P("pipe", None, None, ta),
        "w_down": P("pipe", None, ta, None),
        "attn_norm": P("pipe", None, None),
        "mlp_norm": P("pipe", None, None),
    }
    vocab_axis = ta
    param_specs = {
        "embedding": P(vocab_axis, None),
        "layers": layer_specs,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        param_specs["lm_head"] = P(None, vocab_axis)
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs,
        is_leaf=lambda x: isinstance(x, P))
    repl = NamedSharding(mesh, P())
    data_sharding = NamedSharding(mesh, P(bspec))

    def init_state(key):
        params = init_params(cfg, key)
        params["layers"] = jax.tree.map(
            lambda a: a.reshape((n_stages, cfg.n_layers // n_stages)
                                + a.shape[1:]), params["layers"])
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    from ray_tpu.parallel.sharding import opt_state_shardings

    sample = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    state_shardings = {
        "params": param_shardings,
        "opt_state": opt_state_shardings(
            optimizer, sample["params"], param_shardings, repl),
        "step": repl,
    }

    init_jit = observe_compiled(
        jax.jit(init_state, out_shardings=state_shardings),
        "llama.pipe_init")

    act_spec = {"x": P(bspec, None, None), "pos": P(bspec, None)}

    def pipe_region(stage_params, x, positions):
        local = jax.tree.map(lambda a: a[0], stage_params)

        def stage_fn(sp, act):
            def one_layer(carry, lp):
                return decoder_block(cfg, carry, lp, act["pos"], flash_causal,
                                     row_out=row_out)[0], None

            body = one_layer
            if cfg.remat:
                body = jax.checkpoint(one_layer)
            h, _ = jax.lax.scan(body, act["x"], sp)
            return {"x": h, "pos": act["pos"]}

        mb = split_microbatches({"x": x, "pos": positions},
                                num_microbatches)
        out = pipelined_apply(stage_fn, local, mb, axis_name="pipe")
        return merge_microbatches(out)["x"]

    from ray_tpu.util.jax_compat import shard_map as _sm

    pipe_fn = _sm(
        pipe_region, mesh=mesh,
        in_specs=(layer_specs, act_spec["x"], act_spec["pos"]),
        out_specs=act_spec["x"], check=False)

    def loss(params, tokens):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        # pipeline shards the table by its own specs P(ta, None): sharded
        # iff the tensor axis is live — DEFAULT_RULES inference would
        # misread a dp/fsdp batch axis as embed sharding
        x = embed_tokens(cfg, params, inputs, mesh,
                         table_sharded=ta is not None)
        x = pipe_fn(params["layers"], x, positions_of(*inputs.shape))
        logits = head_logits(cfg, x, params["final_norm"],
                             _head(cfg, params))
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return nll.mean()

    def step_fn(state, tokens):
        l, grads = jax.value_and_grad(loss)(state["params"], tokens)
        updates, new_opt = optimizer.update(grads, state["opt_state"],
                                            state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return ({"params": new_params, "opt_state": new_opt,
                 "step": state["step"] + 1}, l)

    train_step = observe_compiled(
        jax.jit(
            step_fn,
            in_shardings=(state_shardings, data_sharding),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0,),
        ),
        "llama.pipe_train_step")
    return init_jit, train_step, data_sharding, state_shardings
