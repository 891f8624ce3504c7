"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464), plain
``jax.numpy`` / ``lax``: the recurrence of the linear-attention layer that
``models/llama.py delta_block`` runs where the layer pattern says ``D``.

One value head keeps a state ``S`` ``[K, V]`` (a key's width by a value's).
A token brings a query ``q`` and a key ``k`` ``[K]``, a value ``v`` ``[V]``,
a log-decay ``g <= 0`` and a write strength ``beta`` in (0, 1)::

    S_t = a_t S_{t-1} + beta_t k_t (v_t - (a_t S_{t-1})^T k_t)^T   a_t = exp(g_t)
    o_t = S_t^T q_t

the state is decayed, what it already answers for ``k_t`` is taken off the
value (the delta rule) and the rest is written under ``k_t``; then it is read.
:func:`gated_delta_step` is that line for one token: what a decode call runs.

IN CHUNKS (:func:`gated_delta_chunked`; a prompt of 32,000 positions cannot be
32,000 steps). Inside a chunk of ``C`` positions with ``G_i = g_1 + .. + g_i``
(the log-decay from the chunk's start up to and including ``i``) and ``S_0``
the state before it, write ``S_i = exp(G_i) S_0 + sum_{j<=i} exp(G_i - G_j)
k_j u_j^T``. The recurrence then says of the written rows ``u``::

    u_i = beta_i (v_i - exp(G_i) S_0^T k_i)
          - sum_{j<i} beta_i exp(G_i - G_j) (k_i . k_j) u_j

that is ``(I + A) U = diag(beta) (V - diag(exp G) K S_0)`` with ``A`` the
STRICTLY lower triangle of ``beta_i exp(G_i - G_j) (k_i . k_j)``: a
unit-lower-triangular system, solved once for the two right-hand sides
``diag(beta) V`` and ``diag(beta exp G) K`` (``T = (I + A)^-1``; ``U = T
diag(beta) V - T diag(beta exp G) K S_0``). With ``U`` known a chunk is
attention: ``O = diag(exp G) Q S_0 + tril(exp(G_i - G_j) (q_i . k_j)) U`` and
``S_C = exp(G_C) S_0 + (diag(exp(G_C - G)) K)^T U``. Everything that does not
read ``S_0`` is computed for all chunks at once; a ``lax.scan`` over chunks
carries ``[H, K, V]`` and does the four products that do.

Products run in the compute type with float32 accumulation; ``g``, the
cumulative decays, the solve and the carried state are float32.

This module is the XLA form: what the full forward, every backend but a TPU
and ``jax.grad`` run, and the oracle of the serving kernel. On a TPU backend a
prefill's rows go through ``ops/gdn_prefill.py`` instead (PR 46): ONE
forward-only Pallas call with this algebra and these rounding points, the
convolution and the heads' l2-norm in front of it, the state in VMEM. The
delta rule runs in no train step, so the kernel did not have to wait for
**kernels by name** as ``ops/ssm.py``'s scan does (its docstring says why).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def gated_delta_step(q, k, v, g, beta, state):
    """One token of the recurrence, float32 throughout. ``q`` / ``k``
    [B, H, K], ``v`` [B, H, V], ``g`` / ``beta`` [B, H], ``state``
    [B, H, K, V]. Returns ``(o [B, H, V], state)``."""
    q, k, v, state = (a.astype(F32) for a in (q, k, v, state))
    state = state * jnp.exp(g.astype(F32))[..., None, None]
    known = jnp.einsum("bhkv,bhk->bhv", state, k)
    write = beta.astype(F32)[..., None] * (v - known)
    state = state + k[..., :, None] * write[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q), state


def gated_delta_chunked(q, k, v, g, beta, chunk: int, state0=None, last=None):
    """The recurrence over ``T`` positions in chunks of ``chunk`` (module
    docstring). ``q`` / ``k`` [B, T, H, K] and ``v`` [B, T, H, V] in the
    compute type (one query and key a VALUE head: a caller whose key heads
    are fewer repeats them), ``g`` / ``beta`` [B, T, H] float32, ``state0``
    [B, H, K, V] float32 (None: zeros). Positions after ``last`` (a number,
    traced or not; None: the last) are IDENTITY updates, ``beta = 0`` and
    ``g = 0``, and so are the positions a ``T`` that is no multiple of the
    chunk is filled up with: the returned state is the state after position
    ``last`` whatever follows it (a prompt's pad tokens). Returns ``(o
    [B, T, H, V] float32, state [B, H, K, V] float32)``; ``o`` after
    ``last`` reads the unchanged state and means nothing."""
    cd = q.dtype
    B, T, H, K = q.shape
    V = v.shape[-1]
    if last is not None:
        live = (jnp.arange(T) <= last)[None, :, None]
        g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    C = min(chunk, T)
    pad = -T % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    N = (T + pad) // C

    def chunks(a):  # [B, T, H, ...] -> [B, H, N, C, ...]
        return jnp.moveaxis(a.reshape(B, N, C, *a.shape[2:]), 3, 1)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g.astype(F32), axis=-1)                    # [B,H,N,C]
    beta = beta.astype(F32)
    lower = jnp.tril(jnp.ones((C, C), bool))
    # masked BEFORE the exponential: above the diagonal the difference is
    # positive and its exponential may overflow
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))                      # [B,H,N,C,C]
    kk = jnp.einsum("bhnik,bhnjk->bhnij", k, k, preferred_element_type=F32)
    system = jnp.eye(C, dtype=F32) + jnp.where(
        jnp.tril(lower, -1), beta[..., None] * decay * kk, 0.0)
    # both right-hand sides in one solve: [diag(beta) V | diag(beta exp G) K]
    rhs = jnp.concatenate(
        [beta[..., None] * v.astype(F32),
         (beta * jnp.exp(G))[..., None] * k.astype(F32)], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    u_own, k_seen = solved[..., :V].astype(cd), solved[..., V:].astype(cd)
    qk = jnp.where(lower, decay * jnp.einsum(
        "bhnik,bhnjk->bhnij", q, k, preferred_element_type=F32),
        0.0).astype(cd)
    q_in = (jnp.exp(G)[..., None] * q.astype(F32)).astype(cd)
    k_out = (jnp.exp(G[..., -1:] - G)[..., None] * k.astype(F32)).astype(cd)
    chunk_decay = jnp.exp(G[..., -1])                         # [B,H,N]

    def carry_state(state, xs):
        u_c, seen_c, qk_c, q_c, k_c, decay_c = xs
        before = state.astype(cd)
        u = u_c.astype(F32) - jnp.einsum(
            "bhck,bhkv->bhcv", seen_c, before, preferred_element_type=F32)
        o = jnp.einsum("bhck,bhkv->bhcv", q_c, before,
                       preferred_element_type=F32) \
            + jnp.einsum("bhij,bhjv->bhiv", qk_c, u.astype(cd),
                         preferred_element_type=F32)
        state = decay_c[..., None, None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", k_c, u.astype(cd), preferred_element_type=F32)
        return state, o

    if state0 is None:
        state0 = jnp.zeros((B, H, K, V), F32)
    state, o = jax.lax.scan(
        carry_state, state0.astype(F32),
        tuple(jnp.moveaxis(a, 2, 0)
              for a in (u_own, k_seen, qk, q_in, k_out, chunk_decay)))
    # [N, B, H, C, V] -> [B, T, H, V]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, T + pad, H, V)
    return o[:, :T], state
