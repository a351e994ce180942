"""Plain AFMoE (``model_type`` ``afmoe``: Arcee's Trinity family) in
``jax.numpy``: the forward pass that ``correct`` is decided against.

Float32 at ``highest`` matmul precision, no kernel, no cache, no sort, no
batching: every expert runs over every row and its result is multiplied by
a weight that is zero where the row did not choose it (in blocks of
experts, so that it fits); a window is a mask on the dense ``[s, s]``
score matrix (in blocks of queries). It imports nothing of the program and
reads only the canonical weights of ``arch/afmoe.py``.

The layer equations. ``x`` is ``[tokens, hidden]``; every norm ``N`` is
RMSNorm with a learned weight, ``x / sqrt(mean(x^2) + eps) * g``. Lines
marked (a) are not evidenced by a key of the published ``config.json`` and
follow the family's published modelling code (``transformers``,
``models/afmoe``); each is listed under ``assumed`` in the configuration.

- Embedding: ``h = E[ids] * sqrt(hidden)`` (``mup_enabled``) (a).
- Attention, layer ``l``: ``a = N_in(h)``; ``q = a Wq`` as ``heads`` heads
  of ``head_dim``, ``k = a Wk`` and ``v = a Wv`` as ``kv_heads`` heads,
  ``g = a Wg`` (``heads * head_dim`` wide) (a); no biases. ``q = N_q(q)``,
  ``k = N_k(k)`` per head over ``head_dim`` (a). Where ``layer_types[l]``
  is ``sliding``: rotary positions on ``q`` and ``k`` (``rope_theta``,
  the whole head, rotate-half) and key ``j`` visible to query ``i`` iff
  ``i - window < j <= i``. Where it is ``full``: causal, NO rotary (a).
  ``o = softmax(q k^T / sqrt(head_dim)) v``, ``heads / kv_heads`` query
  heads to a KV head; ``attn = (o * sigmoid(g)) Wo``;
  ``h = h + N_post_attn(attn)`` (the second norm of the half: (a)).
- Feed-forward: ``m = N_pre_mlp(h)``. Dense layer (``l < dense_layers``):
  ``f = (silu(m W_gate) * (m W_up)) W_down``. Expert layer:
  ``s = sigmoid(m W_r)`` over all experts, float32 (a);
  ``sel = top_k(s + b)``, ``b`` the per-expert selection bias (zeros as
  initialised), used to select only (a);
  ``w = s[sel] / (sum(s[sel]) + 1e-20) * route_scale`` (``route_norm``);
  ``f = FFN_shared(m) + sum_{e in sel} w_e FFN_e(m)``, each ``FFN`` the
  gated form; ``n_group`` 1, so no group limit.
  ``h = h + N_post_mlp(f)`` (a).
- Head: ``logits = N_f(h) W_head^T``, untied.

Canonical weights (made by ``arch/afmoe.py``; matrices ``[in, out]``):
``embed``, ``head`` ``[V, h]``; ``n_f [h]``; per layer, stacked on a
leading axis of ``L``: ``n_in``, ``n_post_attn``, ``n_pre_mlp``,
``n_post_mlp`` ``[L, h]``, ``wq [L, h, heads dh]``, ``wk``, ``wv``
``[L, h, kv dh]``, ``wg [L, h, heads dh]``, ``wo [L, heads dh, h]``,
``n_q``, ``n_k`` ``[L, dh]``; dense layers, stacked on ``D``: ``d_in
[D, h, 2 ffn]`` (columns ``[gate | up]``), ``d_out [D, ffn, h]``; expert
layers, one entry per layer in a tuple: ``router [h, E]``, ``router_bias
[E]``, ``e_in [E, h, 2 f]``, ``e_out [E, f, h]``, ``s_in [h, 2 fs]``,
``s_out [fs, h]``. The large leaves arrive in bfloat16 (their float32
copies would not fit the chip beside the logits); each is cast up where it
is used, a layer or a block of experts at a time.

``logits`` returns the batch as a TUPLE of ``[s, V]`` arrays, one per
sequence: at 200,192 vocabulary rows a stacked ``[1, 4096, V]`` float32
array is 3.3 GB, and indexing its first row would copy all of it beside
8.5 GB of weights. ``quant`` is the *control*: a function applied to both
operands of every matrix product (``None`` is the reference itself;
:data:`fp8` puts it one precision below the bf16 the configuration
states). Per-tensor scaling takes as one tensor what one product reads:
an activation matrix, one expert's matrix, the head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32

#: experts multiplied at once, and queries of one block of scores
EXPERT_BLOCK = 8
QUERY_BLOCK = 512


def _scaled_cast(x, dtype, top):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = top / amax
    return (x * scale).astype(dtype).astype(F32) / scale


def fp8_e4m3(x):
    """Round to float8 e4m3 and back, scaled per tensor to the format's
    range (amax -> 448)."""
    return _scaled_cast(x, jnp.float8_e4m3fn, 448.0)


def fp8_e5m2(x):
    return _scaled_cast(x, jnp.float8_e5m2, 57344.0)


#: the control, in the form the other references give it: (rounding of a
#: product's operands, rounding of a gradient). Serving uses the first.
fp8 = (fp8_e4m3, fp8_e5m2)


def _q(x, quant):
    return x if quant is None else quant[0](x)


def _mm(a, b, quant):
    """``a [..., i] @ b [i, o]`` in float32; under the control both
    operands are rounded first."""
    return jnp.matmul(_q(a.astype(F32), quant), _q(b.astype(F32), quant),
                      precision=HI)


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * lax.rsqrt(var + eps) * g.astype(F32)


def _rope(x, theta):
    """Rotary positions ``0..s-1`` on ``x [heads, s, dh]``: the whole
    head, rotate-half (the pair of channel ``i`` is ``i + dh/2``)."""
    _, s, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + half * sin


def _gated(m, w_in, w_out, quant):
    gu = _mm(m, w_in, quant)
    f = w_out.shape[-2]
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_out, quant)


def _attention(h, lw, kind, *, heads, kv_heads, head_dim, eps, window,
               rope_theta, quant):
    s = h.shape[0]
    a = _rms(h, lw["n_in"], eps)

    def split(x, n):
        return x.reshape(s, n, head_dim).transpose(1, 0, 2)

    q = _rms(split(_mm(a, lw["wq"], quant), heads), lw["n_q"], eps)
    k = _rms(split(_mm(a, lw["wk"], quant), kv_heads), lw["n_k"], eps)
    v = split(_mm(a, lw["wv"], quant), kv_heads)
    gate = _mm(a, lw["wg"], quant)
    if kind == "sliding":
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    kq, vq = _q(k, quant), _q(v, quant)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)[None, :]

    def rows_from(i0):
        qb = lax.dynamic_slice_in_dim(q, i0, block, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", _q(qb, quant), kq,
                            precision=HI) / head_dim ** 0.5
        rows = i0 + jnp.arange(block)[:, None]
        seen = cols <= rows
        if kind == "sliding":
            seen = seen & (cols > rows - window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", _q(probs, quant), vq,
                          precision=HI)

    o = lax.map(rows_from, jnp.arange(0, s, block))      # [nb, H, block, d]
    o = o.transpose(0, 2, 1, 3).reshape(s, heads * head_dim)
    attn = _mm(o * jax.nn.sigmoid(gate), lw["wo"], quant)
    return h + _rms(attn, lw["n_post_attn"], eps)


def _experts(m, ew, *, top_k, route_scale, quant):
    """``FFN_shared(m) + sum_{e in sel} w_e FFN_e(m)``: every expert over
    every row, weighted by zero where the row did not choose it."""
    n_exp = ew["router"].shape[-1]
    scores = jax.nn.sigmoid(_mm(m, ew["router"], quant))
    _, sel = lax.top_k(scores + ew["router_bias"].astype(F32), top_k)
    w = jnp.take_along_axis(scores, sel, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * route_scale
    dense_w = jnp.sum(jax.nn.one_hot(sel, n_exp, dtype=F32) * w[..., None],
                      axis=1)                               # [s, E]
    block = EXPERT_BLOCK if n_exp % EXPERT_BLOCK == 0 else n_exp
    mq = _q(m, quant)

    def some(acc, e0):
        w_in = lax.dynamic_slice_in_dim(ew["e_in"], e0, block).astype(F32)
        w_out = lax.dynamic_slice_in_dim(ew["e_out"], e0, block).astype(F32)
        if quant is not None:
            w_in, w_out = jax.vmap(quant[0])(w_in), jax.vmap(quant[0])(w_out)
        f = w_out.shape[1]
        gu = jnp.einsum("sh,ehn->esn", mq, w_in, precision=HI)
        mid = jax.nn.silu(gu[..., :f]) * gu[..., f:]
        if quant is not None:
            mid = jax.vmap(quant[0])(mid)
        out = jnp.einsum("esf,efh->esh", mid, w_out, precision=HI)
        we = lax.dynamic_slice_in_dim(dense_w, e0, block, axis=1)
        return acc + jnp.einsum("esh,se->sh", out, we, precision=HI), None

    routed, _ = lax.scan(some, jnp.zeros_like(m),
                         jnp.arange(0, n_exp, block))
    return routed + _gated(m, ew["s_in"], ew["s_out"], quant)


def _head(x, head, quant):
    """``x [s, h] @ head[V, h]^T`` as one product: the compiler casts the
    bfloat16 rows up inside it, so no float32 copy of the head exists."""
    return jnp.matmul(_q(x, quant), _q(head.astype(F32), quant).T,
                      precision=HI)


def _one(w, ids, *, layer_types, dense_layers, emb_mult, top_k, route_scale,
         quant, **attn):
    h = w["embed"][ids].astype(F32) * emb_mult
    eps = attn["eps"]
    for l, kind in enumerate(layer_types):
        lw = {k: w[k][l] for k in ("n_in", "n_post_attn", "n_pre_mlp",
                                   "n_post_mlp", "wq", "wk", "wv", "wg",
                                   "wo", "n_q", "n_k")}
        h = _attention(h, lw, kind, quant=quant, **attn)
        m = _rms(h, lw["n_pre_mlp"], eps)
        if l < dense_layers:
            f = _gated(m, w["d_in"][l], w["d_out"][l], quant)
        else:
            ew = {k: w[k][l - dense_layers]
                  for k in ("router", "router_bias", "e_in", "e_out",
                            "s_in", "s_out")}
            f = _experts(m, ew, top_k=top_k, route_scale=route_scale,
                         quant=quant)
        h = h + _rms(f, lw["n_post_mlp"], eps)
    return _head(_rms(h, w["n_f"], eps), w["head"], quant)


def logits(w, tokens, *, quant=None, **shape):
    """``tokens [b, s]`` -> a tuple of ``b`` float32 ``[s, V]`` arrays
    (see the module docstring for why not one stacked array). ``shape``
    is what ``arch/afmoe.py``'s ``reference_args`` gives."""
    return tuple(_one(w, tokens[i], quant=quant, **shape)
                 for i in range(tokens.shape[0]))


def loss_sum(w, tokens, labels, *, quant=None, **shape):
    """Summed next-token cross entropy over ``tokens [b, s]``."""
    total = 0.0
    for i, lg in enumerate(logits(w, tokens, quant=quant, **shape)):
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, labels[i][:, None], -1)[:, 0]
        total = total + jnp.sum(lse - picked)
    return total


@functools.partial(jax.jit, static_argnames=(
    "quant", "rows", "layer_types", "dense_layers", "emb_mult", "top_k",
    "route_scale", "heads", "kv_heads", "head_dim", "eps",
    "window", "rope_theta"))
def loss_and_grads(w, tokens, labels, *, quant=None, rows=1, **shape):
    """Mean loss of the batch and its gradients (small sizes: the serving
    cell never calls it, nor ``adam`` and ``layer_norms`` below; they are
    what a training cell of this architecture would compare with)."""
    del rows
    count = tokens.size
    loss, grads = jax.value_and_grad(
        lambda w: loss_sum(w, tokens, labels, quant=quant, **shape))(w)
    return loss / count, jax.tree.map(lambda g: g / count, grads)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 1, 2))
def adam(w, m, v, grads, t, *, lr, b1, b2, eps):
    """One Adam step with bias correction and no weight decay, over any
    tree of leaves."""
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * g * g, v, grads)
    w = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
        w, m, v)
    return w, m, v


def layer_norms(tree: dict) -> dict:
    """Per-leaf L2 norms (float32 sums), one per layer for the leaves
    stacked on a layer axis or held one entry per layer."""
    single = ("embed", "head", "n_f")

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))

    out = {}
    for k, x in tree.items():
        if k in single:
            out[k] = norm(x)[None]
        else:
            out[k] = jnp.stack([norm(layer) for layer in x])
    return out
