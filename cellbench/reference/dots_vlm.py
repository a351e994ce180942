"""The language model of dots.vlm1 (``model_type`` ``dots_vlm``: the
DeepSeek-V3 block) in plain ``jax.numpy``: the forward pass that
``correct`` is decided against.

Float32 at ``highest`` matmul precision, EXPANDED attention only: every
head's keys and values are made from the latent rows and multiplied as in
any multi-head attention. No cache, no absorbed form, no kernel, no rows
sorted by expert: every held expert runs over every row and its result is
multiplied by a weight that is zero where the row did not choose it. It
imports nothing of the program and reads only the canonical weights of
``arch/dots_vlm.py``.

The layer equations. ``x`` is ``[tokens, hidden]``; every norm ``N`` is
RMSNorm with a learned weight, ``x / sqrt(mean(x^2) + eps) * g``; no bias
anywhere; no embedding multiplier. Lines marked (a) follow the family's
published modelling code (``transformers``, ``models/deepseek_v3``) where
the ``config.json`` has no key for them; each is listed under ``assumed``
in the configuration.

- Block: ``x = x + Attn(N_in(x))``; ``x = x + FFN(N_post(x))``; then
  ``logits = N_f(x) W_head^T``, untied.
- Attention, heads ``i``: ``cq = N_q(a W_DQ)``; ``[qC_i ; qR_i] = cq
  W_UQ`` (``nope + rope`` a head); ``[c ; kR] = a W_DKV``; ``c = N_kv(c)``;
  ``kR = RoPE(kR)``, one for all heads; ``[kC_i ; v_i] = c W_UKV`` (``nope
  + v`` a head); ``q_i = [qC_i ; RoPE(qR_i)]``, ``k_i = [kC_i ; kR]``;
  ``p = softmax_causal(q_i . k_j * scale)``, ``scale = (nope + rope)^-0.5
  * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; ``o_i = sum_j p_j
  v_j``; ``Attn = concat_i(o_i) W_O``.
- RoPE over the ``rope`` dims with YaRN: ``inv_freq`` blended between
  ``theta^(-2k/rope)`` and the same over ``factor`` by a linear ramp
  between the dims whose wavelength makes ``beta_fast`` and ``beta_slow``
  turns in ``original_max_position_embeddings``; cos and sin times
  ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``;
  rotate-half (the pair of channel ``i`` is ``i + rope/2``) (a).
- Feed-forward, dense layer (``l < dense_layers``): ``(silu(m W_gate) * (m
  W_up)) W_down``. Expert layer: ``s = sigmoid(m W_r)`` over ALL experts of
  the layer (the router's width), float32; ``s' = s + b`` (selects only;
  zeros as initialised); groups of consecutive experts, a group's score
  the sum of its two largest ``s'``; the ``topk_group`` best groups are
  kept; ``sel`` = top-k of ``s'`` over the kept groups; ``w = s[sel] /
  (sum s[sel] + 1e-20) * route_scale``; ``f = FFN_shared(m) + sum_{e in
  sel, e held} w_e FFN_e(m)``. Only experts ``[lo, hi)`` are HELD here
  (``expert_range``): an assignment to another adds nothing, as on the
  chip that holds this share of a layer.

Canonical weights (``arch/dots_vlm.py``; matrices ``[in, out]``):
``embed``, ``head`` ``[V, h]`` (the chip's slice of the vocabulary);
``n_f [h]``; stacked on a leading axis of ``L``: ``n_in``, ``n_post``
``[L, h]``, ``w_dq [L, h, q_rank]``, ``n_q [L, q_rank]``, ``w_uq [L,
q_rank, heads (nope + rope)]``, ``w_dkv [L, h, rank + rope]``, ``n_kv [L,
rank]``, ``w_ukv [L, rank, heads (nope + v)]``, ``wo [L, heads v, h]``;
dense layers, stacked on ``D``: ``d_in [D, h, 2 ffn]`` (columns ``[gate |
up]``), ``d_out [D, ffn, h]``; expert layers, one entry per layer in a
tuple: ``router [h, E]``, ``router_bias [E]`` (``E`` the router's width),
``e_in [held, h, 2 f]``, ``e_out [held, f, h]``, ``s_in [h, 2 fs]``,
``s_out [fs, h]``. The large leaves arrive in bfloat16 (float32 copies of
4.57 G parameters do not fit the chip); each is cast up where it is used.

What keeps 8,192 positions inside the chip's memory beside 9 GB of
weights: attention runs a block of heads at a time and, inside it, a block
of queries at a time; the experts run a block at a time. ``logits``
returns the batch as a TUPLE of ``[s, V]`` arrays. ``quant`` is the
*control* (``None`` is the reference itself; :data:`fp8` is one precision
below the bf16 the configuration states), applied to both operands of
every matrix product, scaled per tensor.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32

#: heads, queries and experts of one block
HEAD_BLOCK = 16
QUERY_BLOCK = 512
EXPERT_BLOCK = 2


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


#: the control: (rounding of a product's operands, rounding of a gradient)
fp8 = (fp8_e4m3, fp8_e5m2)


def _q(x, quant):
    return x if quant is None else quant[0](x)


def _mm(a, b, quant):
    return jnp.matmul(_q(a.astype(F32), quant), _q(b.astype(F32), quant),
                      precision=HI)


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * lax.rsqrt(var + eps) * g.astype(F32)


def _yarn(rope, theta, yarn):
    """(inv_freq ``[rope / 2]``, the factor on cos and sin) from ``yarn =
    (factor, original positions, beta_fast, beta_slow, mscale,
    mscale_all_dim)``, or the plain frequencies for ``None``."""
    base = theta ** (jnp.arange(0, rope, 2, dtype=F32) / rope)
    if yarn is None:
        return 1.0 / base, 1.0
    factor, orig, fast, slow, mscale, mscale_all = yarn

    def dim_of(turns):
        return rope * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(fast)), 0)
    high = min(math.ceil(dim_of(slow)), rope - 1)
    ramp = jnp.clip((jnp.arange(rope // 2, dtype=F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)

    def m(scale):
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0

    return ((1.0 / (factor * base)) * ramp + (1.0 / base) * (1.0 - ramp),
            m(mscale) / m(mscale_all))


def _rope(x, inv, factor):
    """Rotary positions ``0..s-1`` on ``x [..., s, rope]``, rotate-half."""
    s, d = x.shape[-2:]
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * factor
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * factor
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def _gated(m, w_in, w_out, quant):
    gu = _mm(m, w_in, quant)
    f = w_out.shape[-2]
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_out, quant)


def _attention(h, lw, *, heads, nope, rope, v_dim, rank, eps, rope_theta,
               yarn, quant):
    s = h.shape[0]
    a = _rms(h, lw["n_in"], eps)
    cq = _q(_rms(_mm(a, lw["w_dq"], quant), lw["n_q"], eps), quant)
    down = _mm(a, lw["w_dkv"], quant)
    c = _q(_rms(down[:, :rank], lw["n_kv"], eps), quant)
    inv, factor = _yarn(rope, rope_theta, yarn)
    kr = _rope(down[:, rank:], inv, factor)                   # [s, rope]
    scale = (nope + rope) ** -0.5
    if yarn is not None and yarn[5]:
        scale *= (0.1 * yarn[5] * math.log(yarn[0]) + 1.0) ** 2
    hb = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)[None, :]
    w_uq = lw["w_uq"].reshape(-1, heads, nope + rope)
    w_ukv = lw["w_ukv"].reshape(-1, heads, nope + v_dim)
    wo = lw["wo"].reshape(heads, v_dim, -1)

    def some_heads(acc, h0):
        def mine(w):
            w = lax.dynamic_slice_in_dim(w, h0, hb, axis=1).astype(F32)
            return _q(w, quant)

        q = jnp.einsum("sr,rhd->hsd", cq, mine(w_uq), precision=HI)
        kv = jnp.einsum("sr,rhd->hsd", c, mine(w_ukv), precision=HI)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], inv, factor)], -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(kr[None], (hb, s, rope))], -1)
        kq, vq = _q(k, quant), _q(kv[..., nope:], quant)

        def rows_from(i0):
            q_rows = lax.dynamic_slice_in_dim(q, i0, qb, axis=1)
            scores = jnp.einsum("hqd,hkd->hqk", _q(q_rows, quant), kq,
                                precision=HI) * scale
            seen = cols <= i0 + jnp.arange(qb)[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,hkd->hqd", _q(probs, quant), vq,
                              precision=HI)

        o = lax.map(rows_from, jnp.arange(0, s, qb))     # [nb, hb, qb, v]
        o = o.transpose(1, 0, 2, 3).reshape(hb, s, v_dim)
        w_o = _q(lax.dynamic_slice_in_dim(wo, h0, hb).astype(F32), quant)
        return acc + jnp.einsum("hsd,hdo->so", _q(o, quant), w_o,
                                precision=HI), None

    attn, _ = lax.scan(some_heads, jnp.zeros_like(h),
                       jnp.arange(0, heads, hb))
    return h + attn


def route(m, router, bias, *, top_k, n_group, topk_group, route_scale,
          quant=None):
    """``m [T, h]`` -> float32 ``[T, E]``: each row's weight on each
    expert of the layer, zero where the row did not choose it."""
    n_exp = router.shape[-1]
    scores = jax.nn.sigmoid(_mm(m, router, quant))
    biased = scores + bias.astype(F32)
    if n_group > 1:
        groups = biased.reshape(-1, n_group, n_exp // n_group)
        two = jnp.sum(lax.top_k(groups, 2)[0], -1)              # [T, G]
        # the kept groups: the topk_group best, the first of equals
        order = jnp.argsort(-two, axis=-1, stable=True)
        kept = jnp.argsort(order, axis=-1, stable=True) < topk_group
        biased = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(
            biased.shape)
    _, sel = lax.top_k(biased, top_k)
    w = jnp.take_along_axis(scores, sel, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * route_scale
    return jnp.sum(jax.nn.one_hot(sel, n_exp, dtype=F32) * w[..., None], 1)


def held_experts(m, ew, weights, quant=None):
    """``sum_e weights[:, e] * FFN_e(m)`` over the experts of ``ew``
    (``e_in [n, h, 2 f]``, ``e_out [n, f, h]``), ``weights [T, n]``:
    every expert over every row, a block of experts at a time."""
    n = ew["e_in"].shape[0]
    block = EXPERT_BLOCK if n % EXPERT_BLOCK == 0 else n
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
        we = lax.dynamic_slice_in_dim(weights, e0, block, axis=1)
        return acc + jnp.einsum("esh,se->sh", out, we, precision=HI), None

    routed, _ = lax.scan(some, jnp.zeros_like(m), jnp.arange(0, n, block))
    return routed


def _experts(m, ew, *, expert_range, quant, **routing):
    lo, hi = expert_range
    weights = route(m, ew["router"], ew["router_bias"], quant=quant,
                    **routing)
    return (held_experts(m, ew, weights[:, lo:hi], quant)
            + _gated(m, ew["s_in"], ew["s_out"], quant))


def _head(x, head, quant):
    return jnp.matmul(_q(x, quant), _q(head.astype(F32), quant).T,
                      precision=HI)


_ATTN_LEAVES = ("n_in", "w_dq", "n_q", "w_uq", "w_dkv", "n_kv", "w_ukv",
                "wo")


def _one(w, ids, *, dense_layers, top_k, n_group, topk_group, route_scale,
         expert_range, quant, **attn):
    h = w["embed"][ids].astype(F32)
    eps = attn["eps"]
    for l in range(w["n_in"].shape[0]):
        lw = {k: w[k][l] for k in _ATTN_LEAVES}
        h = _attention(h, lw, quant=quant, **attn)
        m = _rms(h, w["n_post"][l], eps)
        if l < dense_layers:
            h = h + _gated(m, w["d_in"][l], w["d_out"][l], quant)
        else:
            ew = {k: w[k][l - dense_layers]
                  for k in ("router", "router_bias", "e_in", "e_out",
                            "s_in", "s_out")}
            h = h + _experts(m, ew, top_k=top_k, n_group=n_group,
                             topk_group=topk_group, route_scale=route_scale,
                             expert_range=expert_range, quant=quant)
    return _head(_rms(h, w["n_f"], eps), w["head"], quant)


def logits(w, tokens, *, quant=None, **shape):
    """``tokens [b, s]`` -> a tuple of ``b`` float32 ``[s, V]`` arrays.
    ``shape`` is what ``arch/dots_vlm.py``'s ``reference_args`` gives."""
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
    "quant", "rows", "dense_layers", "top_k", "n_group", "topk_group",
    "route_scale", "expert_range", "heads", "nope", "rope", "v_dim", "rank",
    "eps", "rope_theta", "yarn"))
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
    """One Adam step with bias correction and no weight decay."""
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
