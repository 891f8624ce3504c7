"""The Mamba-2 mixer (state-space duality, arXiv:2405.21060), plain
``jax.numpy`` / ``lax``: the half of a layer that ``models/llama.py
pattern_layer`` runs where the layer pattern says ``M``. (The Mamba-1 mixer,
the selective scan S6 whose decay is a channel's AND a state's, is ANOTHER
module, ``ops/s6.py`` with its kernel ``ops/s6_prefill.py``: it shares
:func:`causal_conv` and :func:`conv_tail` with this one and nothing else.)

    [z | xBC | dt] = h W_in                       # widths d_inner | d_inner + 2 G N | H
    xBC = silu(conv1d_causal_depthwise(xBC) + b)  # kernel K, over time
    x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)   # head i reads group i // (H / G)
    dt = softplus(dt + dt_bias)      A = -exp(A_log)      # one scalar a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t          # state [H, P, N]
    y_t = S_t C_t + D x_t
    y   = GroupRMSNorm_G(y * silu(z)) * w         # the norm AFTER the gate
    out = y W_out

The recurrence is run in chunks of ``chunk`` positions (:func:`ssd_scan`):
inside a chunk the decay-masked ``C B^T`` scores times ``dt x`` (two
products, as attention over the chunk), across chunks a ``lax.scan`` that
carries the ``[H, P, N]`` states. The backward pass is autodiff through it.
Products run in the compute type with float32 accumulation; ``dt``, the
decays, the carried state and the norm are float32.

ONE arithmetic, three callers. The TRAINER's mixer (:func:`mamba2_mixer`:
``train-nemotron3nano-1chip``'s step) is :func:`project_in`,
:func:`scan_positions` from an empty state over all positions, and
:func:`gate_out`: the program it was before the served forms came
(``tests/test_olmoe.py PROGRAMS`` pins its lowered text, taken on the commit
before). The decode engine's whole block (``models/llama.py hybrid_block``,
kind ``"H"``) runs the same three around what a SEQUENCE keeps by the
engine's ``"state"`` rule, the state ``[H, P, N]`` float32 and the
convolution's tail (the last ``K - 1`` rows of ``xBC`` BEFORE the
convolution): its PREFILL is :func:`scan_positions` with ``last`` (the
engine right-pads a prompt to whole pages; a causal mask keeps nothing out
of a recurrence, so the positions behind ``last`` are identity updates,
``dt = 0``: no decay, no input), in segments of :data:`SEGMENT` positions
where they divide a longer prompt (each stretch starts from the state and
the tail of the one before: :func:`ssd_scan` TAKES a start state and
RETURNS the state after the last live position), and its DECODE step is :func:`step`: the tail's
rows and the new one through the taps and the bias, one update of ``S``,
``y = S C + D x``, float32 throughout. Device scopes, the same names in
all three: ``ssm.in_proj``, ``ssm.conv``, ``ssm.scan`` (prefill and the
trainer) / ``ssm.step`` (decode), ``ssm.gate_norm``, ``ssm.out_proj``.

No kernel HERE: the trainer's scan is a train cell's step, and the
benchmark's ``flash_roofline`` (the train cells' alone) tells Pallas kernels
apart by result type, so one more ``tpu_custom_call`` in a train step would
be counted as ``flash_dq``: a Pallas scan there waits for **kernels by name**
(ROADMAP Reach B1(a)) and a backward. The SERVED prefill has its kernel: on a
TPU backend ``models/llama.py attend_ssm`` runs ``ops/ssm_prefill.py`` (the
convolution, ``silu``, the split, ``softplus`` and the scan in ONE
forward-only Pallas call, the state in VMEM, the prompt one piece;
``ssm_prefill_path`` says which path and why, and counts it), as
``ops/gdn_prefill.py`` followed the delta rule's XLA path. These functions
are that kernel's oracle, every other backend's path and the path of what it
does not take. :func:`causal_conv` stays the delta rule's XLA path's too
(``models/llama.py _delta_chunks``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


# a long prompt's served prefill goes this many positions at a time (where
# it divides them): the convolution's float32 rows, the heads and the decay
# masks of all of a stretch's chunks at once are a segment's. At 64 heads and
# chunks of 256 the float32 masks ``[chunks, heads, 256, 256]`` are 0.27 GB
# at 4,096 positions where 16,384 positions' are 1.07 GB, and XLA's programs
# for one stretch do not scale: a layer's ``ssm.scan`` + ``ssm.conv`` took
# 1.05 ms at 4,096 positions and 4.1 ms at 8,192 (37.8 and 148.5 ms over a
# prefill's 36 layers; my chip run, PR 58), so two stretches of 4,096 are
# half of one of 8,192
SEGMENT = 4096


def causal_conv(x, w, b, before=None):
    """Depthwise causal convolution over time. ``x`` [B, T, C]; ``w``
    [K, C] (``w[K - 1]`` multiplies the position itself, ``w[0]`` the one
    ``K - 1`` back); ``b`` [C]. ``K`` shifted adds. ``before`` [B, K - 1,
    C]: the rows in front of ``x``'s first (None: zeros, a sequence's
    start)."""
    K, T = w.shape[0], x.shape[1]
    xp = (jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))) if before is None
          else jnp.concatenate([before.astype(x.dtype), x], axis=1))
    return sum(xp[:, k:k + T] * w[k] for k in range(K)) + b


def conv_tail(xbc, K: int, last=None):
    """The convolution's tail at ``last`` (a number, traced or not; None:
    the last position): the ``K - 1`` rows of ``xbc`` [B, T, C] that end
    there, zeros before the sequence's first."""
    T = xbc.shape[1]
    at = (T - 1 if last is None else last) - (K - 2) + jnp.arange(K - 1)
    return jnp.where((at >= 0)[None, :, None], xbc[:, jnp.maximum(at, 0)], 0)


def ssd_scan(x, dt, a, b_in, c_in, chunk: int, state=None, last=None):
    """``y_t = C_t S_t`` of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x)
    B_t`` in chunks of ``chunk`` positions, from ``S_0 = state`` [B, H, P,
    N] float32 (None: zeros).

    ``x`` [B, T, H, P] and ``b_in`` / ``c_in`` [B, T, G, N] in the compute
    type (head ``i`` reads group ``i // (H / G)``); ``dt`` [B, T, H]
    float32, positive; ``a`` [H] float32, negative. Positions after ``last``
    (a number, traced or not; None: the last) are IDENTITY updates, ``dt =
    0`` (no decay, no input), and so are the positions a ``T`` that is no
    multiple of the chunk is padded with (a ``T`` shorter than the chunk is
    one chunk of ``T``). Returns ``(y [B, T, H, P] float32, the state after
    position ``last`` [B, H, P, N] float32)``; ``y`` after ``last`` reads
    the unchanged state and means nothing."""
    cd = x.dtype
    bsz, T, H, P = x.shape
    G, N = b_in.shape[2:]
    R = H // G
    if last is not None:
        dt = jnp.where((jnp.arange(T) <= last)[None, :, None], dt, 0.0)
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, b_in, c_in = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b_in, c_in))
    nc = (T + pad) // Q
    x = x.reshape(bsz, nc, Q, G, R, P)
    dt = dt.reshape(bsz, nc, Q, G, R)
    b_in = b_in.reshape(bsz, nc, Q, G, N)
    c_in = c_in.reshape(bsz, nc, Q, G, N)
    # log-decay from the chunk's start up to and including each position
    cs = jnp.cumsum(dt * a.reshape(G, R), axis=2)            # [b,c,Q,G,R]
    dtx = dt[..., None] * x.astype(F32)                      # [b,c,Q,G,R,P]

    # inside a chunk: position i reads j <= i through exp(cs_i - cs_j)
    scores = jnp.einsum("bcign,bcjgn->bcgij", c_in, b_in,
                        preferred_element_type=F32)
    cs_t = jnp.moveaxis(cs, 2, -1)                           # [b,c,G,R,Q]
    seg = cs_t[..., :, None] - cs_t[..., None, :]            # [b,c,G,R,i,j]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    # masked BEFORE the exponential: above the diagonal the difference is
    # positive and its exponential may overflow
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp",
                   (scores[:, :, :, None] * decay).astype(cd),
                   dtx.astype(cd), preferred_element_type=F32)

    # what each chunk adds to the state by its end, and the chunk's decay
    to_end = jnp.exp(cs[:, :, -1:] - cs)                     # [b,c,Q,G,R]
    added = jnp.einsum("bcjgn,bcjgrp->bcgrpn", b_in,
                       (to_end[..., None] * dtx).astype(cd),
                       preferred_element_type=F32)
    chunk_decay = jnp.exp(cs[:, :, -1])                      # [b,c,G,R]

    def carry_state(state, chunk_in):
        add_c, decay_c = chunk_in
        return decay_c[..., None, None] * state + add_c, state

    after, before = jax.lax.scan(
        carry_state, jnp.zeros((bsz, G, R, P, N), F32) if state is None
        else state.reshape(bsz, G, R, P, N),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                      # [b,c,G,R,P,N]
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bcign,bcgrpn->bcigrp", c_in, before.astype(cd),
        preferred_element_type=F32)
    return y.reshape(bsz, T + pad, H, P)[:, :T], after.reshape(bsz, H, P, N)


def project_in(h, w_in, heads: int, d_inner: int):
    """``[z | xBC | dt] = h W_in`` on ``h`` [B, T, dim] (normed, compute
    type): ONE stored matrix, two products: ``z`` and ``xBC`` leave in the
    compute type as every other projection does, the ``H`` columns of ``dt``
    in float32 (before its bias and softplus). Returns ``(z, xbc, dt)``."""
    with jax.named_scope("ssm.in_proj"):
        w_in = w_in.astype(h.dtype)
        z, xbc = jnp.split(h @ w_in[:, :-heads], [d_inner], -1)
        dt = jnp.dot(h, w_in[:, -heads:], preferred_element_type=F32)
    return z, xbc, dt


def _piece(xbc, dt, p, *, heads, head_dim, groups, state, chunk, start=None,
           before=None, last=None):
    """A stretch of positions through the convolution and the scan, from
    the state ``start`` and the rows ``before`` (None: a sequence's first
    stretch). Returns ``(y [B, T, H, P] float32, the state after)``."""
    cd = xbc.dtype
    bsz, T, _ = xbc.shape
    d_inner, gn = heads * head_dim, groups * state
    with jax.named_scope("ssm.conv"):
        xbc = jax.nn.silu(causal_conv(xbc.astype(F32), p["conv_w"],
                                      p["conv_b"], before)).astype(cd)
        x, b_in, c_in = jnp.split(xbc, [d_inner, d_inner + gn], -1)
        x = x.reshape(bsz, T, heads, head_dim)
    with jax.named_scope("ssm.scan"):
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y, after = ssd_scan(x, dt, -jnp.exp(p["A_log"]),
                            b_in.reshape(bsz, T, groups, state),
                            c_in.reshape(bsz, T, groups, state), chunk,
                            start, last)
        y = y + p["D"][:, None] * x.astype(F32)
    return y, after


def scan_positions(xbc, dt, p, *, last=None, segment: int = 0, **dims):
    """The call's own positions from an EMPTY state and tail: ``xbc`` [B, T,
    d_inner + 2 G N] (compute type) and ``dt`` [B, T, H] float32 as
    :func:`project_in` leaves them, through the convolution, ``silu``, the
    split, ``softplus`` and the scan, plus ``D x``; ``p``: ``conv_w``,
    ``conv_b``, ``dt_bias``, ``A_log``, ``D`` of ONE layer; ``dims``:
    ``heads``, ``head_dim``, ``groups``, ``state``, ``chunk``. ``last``: the
    last REAL position (:func:`ssd_scan`). ``segment``: that many positions
    at a time where it divides MORE than one such stretch (each stretch
    starts from what a decode call would find, the state and the
    convolution's last rows, of the one before); otherwise one piece. Returns ``(y [B, T, H, P]
    float32, the state after ``last`` [B, H, P, N] float32, the tail at
    ``last`` [B, K - 1, width] in the compute type)``."""
    (B, T, width), K = xbc.shape, p["conv_w"].shape[0]
    with jax.named_scope("ssm.conv"):
        tail = conv_tail(xbc, K, last)
    if not segment or T <= segment or T % segment:
        y, after = _piece(xbc, dt, p, last=last, **dims)
        return y, after, tail
    # in line and not a ``lax.scan``: a scan stacks its segments' outputs by
    # dynamic-update-slices (4.7 ms each at 8,192 x 4,096 float32, a dozen a
    # layer) and hands the gated norm a layout it reads at a twentieth of
    # the chip's bandwidth (198 ms of a 1,282 ms prefill at 16,384
    # positions in two scanned segments of 8,192; my chip run, PR 58)
    ys, after, before = [], None, None
    for first in range(0, T, segment):
        stretch = slice(first, first + segment)
        y, after = _piece(xbc[:, stretch], dt[:, stretch], p, start=after,
                          before=before,
                          last=None if last is None else last - first,
                          **dims)
        ys.append(y)
        before = xbc[:, first + segment - (K - 1):first + segment]
    return jnp.concatenate(ys, axis=1), after, tail


def step(xbc, dt, p, state, tail, *, groups: int):
    """ONE token on from a kept state and tail, float32 throughout (the
    recurrence as it is written, no chunk): ``xbc`` [B, 1, width] and ``dt``
    [B, 1, H] as :func:`project_in` leaves them, ``state`` [B, H, P, N] and
    ``tail`` [B, K - 1, width] float32 (the tail holds the compute type's
    values: rows of ``xbc`` BEFORE the convolution); ``p`` as
    :func:`scan_positions`'. The tail's rows and the new one go through the
    ``K`` taps and the bias, ``S = exp(dt a) S + dt x (x) B``, ``y = S C + D
    x``. Returns ``(y [B, 1, H, P] float32, state, tail)``, both as the next
    token finds them."""
    bsz, H, P, N = state.shape
    with jax.named_scope("ssm.conv"):
        rows = jnp.concatenate([tail.astype(F32), xbc.astype(F32)], axis=1)
        # rounded to the compute type where the scan's operands are
        mixed = jax.nn.silu(jnp.sum(rows * p["conv_w"], axis=1)
                            + p["conv_b"]).astype(xbc.dtype).astype(F32)
        x, b_in, c_in = jnp.split(mixed, [H * P, H * P + groups * N], -1)
        x = x.reshape(bsz, groups, H // groups, P)
        b_in, c_in = (v.reshape(bsz, groups, 1, 1, N) for v in (b_in, c_in))
    with jax.named_scope("ssm.step"):
        dt = jax.nn.softplus(dt[:, 0] + p["dt_bias"]).reshape(
            bsz, groups, H // groups)
        a = -jnp.exp(p["A_log"]).reshape(groups, H // groups)
        state = state.astype(F32).reshape(bsz, groups, H // groups, P, N)
        state = (jnp.exp(dt * a)[..., None, None] * state
                 + (dt[..., None] * x)[..., None] * b_in)
        # a sum of products and no dot: float32 as written on every backend
        y = jnp.sum(state * c_in, axis=-1) \
            + p["D"].reshape(groups, H // groups, 1) * x
    return (y.reshape(bsz, 1, H, P), state.reshape(bsz, H, P, N),
            rows[:, 1:])


def gate_out(y, z, gate_norm, w_out, *, groups: int, eps: float):
    """``GroupRMSNorm_G(y * silu(z)) * w`` (the norm AFTER the gate, over a
    group's ``d_inner / G``; float32) through ``W_out``: ``y`` [B, T, H, P]
    float32, ``z`` [B, T, d_inner] in the compute type, which the result
    [B, T, dim] has too."""
    cd = z.dtype
    bsz, T, d_inner = z.shape
    with jax.named_scope("ssm.gate_norm"):
        y = y.reshape(bsz, T, groups, d_inner // groups) * jax.nn.silu(
            z.astype(F32)).reshape(bsz, T, groups, d_inner // groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y.reshape(bsz, T, d_inner) * gate_norm
    with jax.named_scope("ssm.out_proj"):
        return y.astype(cd) @ w_out.astype(cd)


def mamba2_mixer(h, p, *, heads: int, head_dim: int, groups: int,
                 state: int, chunk: int, eps: float):
    """The mixer on ``h`` [B, T, dim] (normed, compute type) with one
    layer's weights ``p``: ``w_in`` [dim, 2 d_inner + 2 G N + H], ``conv_w``
    [K, d_inner + 2 G N], ``conv_b``, ``dt_bias`` / ``A_log`` / ``D`` [H],
    ``gate_norm`` [d_inner], ``w_out`` [d_inner, dim]. Returns [B, T, dim]
    in the compute type, before the residual add."""
    z, xbc, dt = project_in(h, p["w_in"], heads, heads * head_dim)
    y, _ = _piece(xbc, dt, p, heads=heads, head_dim=head_dim, groups=groups,
                  state=state, chunk=chunk)
    return gate_out(y, z, p["gate_norm"], p["w_out"], groups=groups, eps=eps)


def init_mamba2(key, layers: int, dim: int, *, heads: int, head_dim: int,
                groups: int, state: int, conv: int, dt_min: float,
                dt_max: float, dt_floor: float):
    """``layers`` mixers' weights stacked, float32, initialised as the
    published Mamba-2 code does (a random ``A`` gives no stable
    recurrence): ``A_log = log U[1, 16]``, ``D = 1``, ``dt`` log-uniform in
    ``[dt_min, dt_max]`` floored at ``dt_floor`` and ``dt_bias`` its
    inverse softplus; the products and the conv are random normals over
    the square root of their fan-in, the conv's bias zero."""
    d_inner, gn = heads * head_dim, groups * state
    k_in, k_out, k_conv, k_dt, k_a = jax.random.split(key, 5)

    def dense(rng, shape, fan_in):
        return jax.random.normal(rng, shape, F32) / math.sqrt(fan_in)

    dt = jnp.exp(jax.random.uniform(k_dt, (layers, heads), F32)
                 * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
    dt = jnp.maximum(dt, dt_floor)
    return {
        "norm": jnp.ones((layers, dim), F32),
        "w_in": dense(k_in, (layers, dim, 2 * d_inner + 2 * gn + heads), dim),
        "conv_w": dense(k_conv, (layers, conv, d_inner + 2 * gn), conv),
        "conv_b": jnp.zeros((layers, d_inner + 2 * gn), F32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k_a, (layers, heads), F32,
                                            1.0, 16.0)),
        "D": jnp.ones((layers, heads), F32),
        "gate_norm": jnp.ones((layers, d_inner), F32),
        "w_out": dense(k_out, (layers, d_inner, dim), d_inner),
    }
