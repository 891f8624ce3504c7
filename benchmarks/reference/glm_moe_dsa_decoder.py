"""The plain reference of GLM-5.2 (``model_type: glm_moe_dsa``): a stack of
blocks of latent (MLA) attention UNDER a learned selection that several
layers share ("DSA with IndexShare"), a dense SwiGLU in the leading blocks
and sigmoid-routed experts beside one shared expert in the others
(DeepSeek-V3's router, arXiv:2412.19437 section 2.1; DeepSeek-V3.2's
indexer), a final RMSNorm and an untied head.

One layer on ``x`` ``[T, hidden]``, ``N`` RMSNorm, position ``t`` of a token
its place::

    a        = N(x; attn_norm)
    cq       = N(a Wq_a; q_norm)                          [q_lora_rank]
    q        = cq Wq_b                a head: [nope | rope]
    [c | kr] = a Wkv_a;  c = N(c; kv_norm)                [kv_lora_rank | rope]
    q's rope slice and kr: pairs (2i, 2i + 1) turned by t * theta^(-2i / rope)
    k_h      = [c Wkb_h | kr]        v_h = c Wvb_h        every head, EXPANDED
    a FULL layer (``indexer_types[l] == "full"``):
        qI = cq WqI                  index_n_heads x index_head_dim
        kI = LayerNorm(a WkI)        ONE head; scale and bias
        the FIRST HALF of qI's and kI's width turned as above (pairs (2i,
        2i + 1), theta^(-2i / half)), by t
        w  = a Ww                    a weight a query head
        I(t, s) = sum_j w_j(t) Hi^-1/2 Di^-1/2 relu(qI_j(t) . kI(s)),  s <= t
        S(t)    = the index_topk positions s <= t of largest I(t, s) (all of
                  them while t + 1 <= index_topk; a tie to the LOWER position)
    a SHARED layer: S(t) = the S(t) of the nearest full layer below it; it
        has no indexer weights and computes no score
    h        = x + [softmax_{s in S(t)}(q_h(t) . k_h(s) / sqrt(nope + rope))
               v_h(s)]_h Wo
    m        = N(h; mlp_norm)
    out      = h + dense SwiGLU(m)                          ``mlp_layer_types``
               "dense", else
               h + sum over the chosen experts HELD HERE of w_j swiglu_j(m)
                 + shared(m)
    router:  s = sigmoid(m W_r); chosen = top_k(s + b) (b for the choice
             only); w_j = s_j / (sum of the chosen s + 1e-20) *
             routed_scaling_factor

The file is a CUT: ``first_layer`` and ``num_hidden_layers`` say which layers
of the published ``indexer_types`` / ``mlp_layer_types`` are built (the
file's ``layer_pattern`` spells the same stack in the program's letters, and
names the weights' stacks); ``n_routed_experts`` experts from ``first_expert``
on are held here of the router's ``router_experts`` outputs (one chip's share
of an expert-parallel deployment) and the others' terms are not in the sum:
nothing stands in for the absent chips. The prediction module
(``num_nextn_predict_layers``) is not here: it drafts decoded tokens and
changes no logit of the main model.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no page, a
Python loop over layers, ``jax.lax.top_k`` on a query's WHOLE row of float32
scores (it returns the lower index first among equals) scattered into a
mask, per-head keys and values expanded (never the absorbed form), a loop
over the held experts. Weights from the program's parameter tree (leaves
stacked over a kind's layers, stored ``[in, out]``); it imports nothing of
``ray_tpu``. Done for room, changing no value: a matrix is cut out of its
stacked leaf and converted to float32 where it is used (:func:`_mm`), and
queries go in blocks of ``QUERY_BLOCK`` rows (``lax.map``), each against ALL
keys. What the catalog cannot confirm is listed under ``assumed`` in
``configs/GLM-5.2.json``.

The keyword switches compute the stack a WRONG way; ``sweep/glm52_check.py``
measures that the comparison refuses each.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 128
# the program's letter of a layer -> the name of its stacked weights
STACK = {"X": "dsa_dense", "Y": "dsa_full", "Z": "dsa_shared"}


def _mm(x, w, at=()):
    """``x @ w[at]``, the matrix cut out of its stacked leaf ``w`` (as
    stored) and converted to float32 only once ``x`` has been computed."""
    w, _ = jax.lax.optimization_barrier((w, x))
    return x @ w[at].astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w.astype(F32) + b.astype(F32)


def _swiglu(m, p, l, names=("w_gate", "w_up", "w_down"), e=()):
    gate, up, down = names
    return _mm(jax.nn.silu(_mm(m, p[gate], (l, *e))) * _mm(m, p[up], (l, *e)),
               p[down], (l, *e))


def rope(x, theta):
    """``x`` [T, H, D]: pair ``(2i, 2i + 1)`` of the token at place ``t``
    turns by ``t * theta^(-2i / D)``."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]     # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _theta(cfg):
    return cfg["rope_parameters"]["rope_theta"]


def kinds_of(cfg):
    """The built layers in order: ``(stack, its row, full?, dense?)`` from
    the published lists; the file's pattern only names the stacks."""
    first, n = cfg.get("first_layer", 0), cfg["num_hidden_layers"]
    met, out = dict.fromkeys(STACK.values(), 0), []
    for l in range(first, first + n):
        stack = STACK[cfg["layer_pattern"][l]]
        out.append((stack, met[stack], cfg["indexer_types"][l] == "full",
                    cfg["mlp_layer_types"][l] == "dense"))
        met[stack] += 1
    return out


def qkv(cfg, a, p, l):
    """``(q [T, H, nope + rope], k [T, H, nope + rope], v [T, H, v], cq)`` of
    layer ``l`` on ``a`` [T, hidden] (normed): keys and values of every head
    expanded from the latent row, the rope slices turned."""
    T = a.shape[0]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    cq = _rms_norm(_mm(a, p["wq_a"], l), p["q_norm"][l], eps)
    q = _mm(cq, p["wq_b"], l).reshape(T, H, dn + dr)
    ckr = _mm(a, p["wkv_a"], l)
    c = _rms_norm(ckr[:, :r], p["kv_norm"][l], eps)
    kr = rope(ckr[:, None, r:], _theta(cfg))                   # [T, 1, dr]
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], _theta(cfg))], -1)
    kv = _mm(c, p["wkv_b"], l).reshape(T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(kr, (T, H, dr))], -1)
    return q, k, kv[..., dn:], cq


def indexer(cfg, a, cq, p, l, *, rotate=True, from_stream=False):
    """``(qI [T, Hi, Di], kI [T, Di], w [T, Hi])`` of full layer ``l``; ``w``
    carries the two constant scales. ``from_stream``: the WRONG way in which
    the query reads the normed stream (its first q_lora_rank numbers: the
    matrix has no more rows; a narrower stream is repeated) and not ``cq``."""
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    T = a.shape[0]
    r = cq.shape[1]  # the stream's numbers, as many as the matrix has rows
    src = jnp.tile(a, (1, -(-r // a.shape[1])))[:, :r] if from_stream else cq
    qi = _mm(src, p["wqi"], l).reshape(T, hi, di)
    ki = _layer_norm(_mm(a, p["wki"], l), p["ki_norm"][l], p["ki_bias"][l],
                     cfg["rms_norm_eps"])
    w = _mm(a, p["ww"], l) / math.sqrt(hi) / math.sqrt(di)

    def first_half_turned(x):  # [T, H, Di]
        return jnp.concatenate([rope(x[..., :di // 2], _theta(cfg)),
                                x[..., di // 2:]], -1)

    if rotate:
        qi = first_half_turned(qi)
    ki = first_half_turned(ki[:, None])[:, 0] if rotate else ki
    return qi, ki, w


def _blocks(x, spare):
    return jnp.pad(x, ((0, spare),) + ((0, 0),) * (x.ndim - 1)).reshape(
        -1, QUERY_BLOCK, *x.shape[1:])


def selection(cfg, qi, ki, w):
    """``S`` as a mask ``[T, T]`` bool: for every query the ``index_topk``
    visible positions of largest index score, ``jax.lax.top_k`` over the
    query's whole row (the unseen at -inf), scattered."""
    T = qi.shape[0]
    k = min(cfg["index_topk"], T)
    spare = -T % QUERY_BLOCK

    def block(rows):
        qi_b, w_b, at_b = rows
        score = jnp.sum(w_b[:, :, None] * jax.nn.relu(
            jnp.einsum("qhd,kd->qhk", qi_b, ki)), axis=1)         # [R, T]
        visible = jnp.arange(T)[None, :] <= at_b[:, None]
        _, best = jax.lax.top_k(jnp.where(visible, score, -jnp.inf), k)
        chosen = jnp.zeros(score.shape, bool).at[
            jnp.arange(score.shape[0])[:, None], best].set(True)
        return chosen & visible

    out = jax.lax.map(block, (_blocks(qi, spare), _blocks(w, spare),
                              jnp.arange(T + spare).reshape(-1, QUERY_BLOCK)))
    return out.reshape(-1, T)[:T]


def attention(cfg, q, k, v, S, p, l):
    """Softmax attention of every head under the mask ``S`` [T, T], then
    ``Wo``; before the residual add."""
    T, H, D = q.shape
    spare = -T % QUERY_BLOCK

    def block(rows):
        q_b, S_b = rows
        s = jnp.einsum("qhd,khd->hqk", q_b, k) / math.sqrt(D)
        probs = jax.nn.softmax(jnp.where(S_b[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    # a spare row sees position 0, so that its softmax is a number
    S = jnp.pad(S, ((0, spare), (0, 0))).at[T:, 0].set(True)
    out = jax.lax.map(block, (_blocks(q, spare),
                              S.reshape(-1, QUERY_BLOCK, T)))
    return _mm(out.reshape(-1, H * v.shape[-1])[:T], p["wo"], l)


def route(cfg, m, p, l):
    """``[T, router_experts]``: an expert's weight for a token, 0 where the
    token did not choose it."""
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(m @ p["router"][l].astype(F32))
    _, chosen = jax.lax.top_k(s + p["router_bias"][l].astype(F32), k)
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    hit = chosen[:, :, None] == jnp.arange(E)[None, None, :]    # [T, k, E]
    return jnp.sum(jnp.where(hit, top[:, :, None], 0.0), axis=1)


def experts(cfg, m, weight, p, l, first=None, count=None):
    """The routed sum on ``m`` [T, hidden] (normed) over the experts held
    here: expert ``first + e`` of the router is row ``e`` of the stacked
    leaves. Every held expert's SwiGLU on every token, times the token's
    weight for it."""
    first = cfg.get("first_expert", 0) if first is None else first
    count = cfg["n_routed_experts"] if count is None else count

    def one(e, y):
        return y + weight[:, first + e, None] * _swiglu(m, p, l, e=(e,))

    return jax.lax.fori_loop(0, count, one, jnp.zeros_like(m))


def shared_expert(m, p, l):
    return _swiglu(m, p, l, ("shared_gate", "shared_up", "shared_down"))


def moe(cfg, m, p, l):
    return experts(cfg, m, route(cfg, m, p, l), p, l) + shared_expert(m, p, l)


def dense(cfg, m, p, l):
    return _swiglu(m, p, l)


# the parts of a layer, for tests and the check script to hold one by one
LAYER = {"qkv": qkv, "indexer": indexer, "selection": selection,
         "attention": attention, "route": route, "experts": experts,
         "shared_expert": shared_expert, "dense": dense, "moe": moe}


def layer(cfg, x, S, p, l, full, is_dense, *, select=True, rotate_index=True,
          index_from_stream=False):
    """One layer on ``x`` [T, hidden] with the selection ``S`` that came
    with it (None in front of the first layer); ``p``: its kind's stack,
    ``l`` its row there. Returns ``(x, S)``: a full layer's own selection,
    a shared layer's the one it read. ``select=False``: every visible key."""
    eps = cfg["rms_norm_eps"]
    T = x.shape[0]
    a = _rms_norm(x, p["attn_norm"][l], eps)
    q, k, v, cq = qkv(cfg, a, p, l)
    if full:
        S = selection(cfg, *indexer(cfg, a, cq, p, l, rotate=rotate_index,
                                    from_stream=index_from_stream))
    seen = S if select else (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])
    h = x + attention(cfg, q, k, v, seen, p, l)
    m = _rms_norm(h, p["mlp_norm"][l], eps)
    return h + (dense if is_dense else moe)(cfg, m, p, l), S


def hidden_one(cfg, params, tokens, *, shared_selects=False, stale=False,
               **wrong):
    """tokens [T] -> ``(final-normed states [T, hidden], [S a layer])``.
    The wrong ways: ``shared_selects``: a shared layer selects for itself
    (the tree has to hold indexer leaves in ``dsa_shared``); ``stale``: a
    full layer behind the first attends under the FIRST one's selection and
    hands that on (the selection of the wrong full layer); and
    :func:`layer`'s keywords."""
    x = params["embedding"][tokens].astype(F32)
    S, kept, first = None, [], True
    for stack, l, full, is_dense in kinds_of(cfg):
        selects = (full and (first or not stale)) or shared_selects
        x, S = layer(cfg, x, S, params["layers"][stack], l, selects,
                     is_dense, **wrong)
        first = False
        kept.append(S)
    return _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]), kept


def _head(cfg, params):
    return (params["embedding"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])


def logits_one(cfg, params, tokens, **wrong):
    """tokens [T] int32 -> logits [T, vocab] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        return _mm(hidden_one(cfg, params, tokens, **wrong)[0],
                   _head(cfg, params))


def loss(cfg, params, tokens):
    """tokens [B, T + 1] -> mean next-token cross-entropy, one sequence at a
    time. (A sigmoid router has no router loss.)"""
    with jax.default_matmul_precision("highest"):
        def nll(row):
            logp = jax.nn.log_softmax(
                _mm(hidden_one(cfg, params, row[:-1])[0],
                    _head(cfg, params)), -1)
            return -jnp.take_along_axis(logp, row[1:, None], axis=-1).sum()

        B, T1 = tokens.shape
        return jax.lax.map(nll, tokens).sum() / (B * (T1 - 1))
