"""Plain GPT-2 in ``jax.numpy``: forward, loss, gradients, Adam.

The yardstick ``correct`` is decided against. It follows the published
GPT-2 equations (pre-LN blocks, learned positions, tanh-GELU, tied output
head, causal softmax attention at ``1/sqrt(d_head)``) in float32 at
``highest`` matmul precision, with no kernel, no cache and no batching
trick. It imports nothing of the program and reads only the canonical
weights of :mod:`cellbench.weights`.

``quant`` is the *control*: a function applied to both operands of every
matrix product. ``None`` is the reference itself; :func:`fp8` puts the
reference one precision below the bf16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _scaled_cast(x, dtype, top):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = top / amax
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def fp8_e4m3(x):
    """Round to float8 e4m3 and back, scaled per tensor to the format's
    range (the usual fp8 recipe: amax -> 448)."""
    return _scaled_cast(x, jnp.float8_e4m3fn, 448.0)


def fp8_e5m2(x):
    """The same for gradients, in e5m2 (amax -> 57344)."""
    return _scaled_cast(x, jnp.float8_e5m2, 57344.0)


#: the control: (rounding of a product's operands, rounding of the
#: gradient that flows back into a product)
fp8 = (fp8_e4m3, fp8_e5m2)


def _ste(x, quant):
    """Rounded going forward, untouched going back."""
    return x if quant is None else x + lax.stop_gradient(quant[0](x) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _qmm(a, b, quant):
    return jnp.matmul(quant[0](a), quant[0](b), precision=HI)


def _qmm_fwd(a, b, quant):
    aq, bq = quant[0](a), quant[0](b)
    return jnp.matmul(aq, bq, precision=HI), (aq, bq)


def _qmm_bwd(quant, res, g):
    aq, bq = res
    gq = quant[1](g)
    da = jnp.matmul(gq, bq.T, precision=HI)
    db = jnp.einsum("...i,...o->io", aq, gq, precision=HI)
    return da, db


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def _mm(a, b, quant):
    """``a [..., i] @ b [i, o]``; under the control both operands, and the
    gradient coming back, are rounded first."""
    if quant is None:
        return jnp.matmul(a, b, precision=HI)
    return _qmm(a, b, quant)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def _block(x, lw, heads, eps, quant):
    b, s, h = x.shape
    dh = h // heads
    a = _ln(x, lw["ln1_g"], lw["ln1_b"], eps)
    qkv = _mm(a, lw["w_qkv"], quant) + lw["b_qkv"]
    q, k, v = (t.reshape(b, s, heads, dh).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    q, k = _ste(q, quant), _ste(k, quant)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HI) / dh ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    probs, v = _ste(probs, quant), _ste(v, quant)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=HI)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    x = x + _mm(ctx, lw["w_o"], quant) + lw["b_o"]
    m = _ln(x, lw["ln2_g"], lw["ln2_b"], eps)
    m = _gelu(_mm(m, lw["w_fc"], quant) + lw["b_fc"])
    return x + _mm(m, lw["w_proj"], quant) + lw["b_proj"]


_LAYER_KEYS = ("ln1_g", "ln1_b", "w_qkv", "b_qkv", "w_o", "b_o", "ln2_g",
               "ln2_b", "w_fc", "b_fc", "w_proj", "b_proj")


def logits(w, tokens, *, heads, eps, quant=None):
    """``tokens [b, s]`` -> logits ``[b, s, V]`` (V = the rows of ``wte``)."""
    s = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:s]
    stacked = {k: w[k] for k in _LAYER_KEYS}

    @jax.checkpoint
    def body(x, lw):
        return _block(x, lw, heads, eps, quant), None

    x, _ = lax.scan(body, x, stacked)
    x = _ln(x, w["lnf_g"], w["lnf_b"], eps)
    return _mm(x, w["wte"].T, quant)


def loss_sum(w, tokens, labels, *, heads, eps, quant=None):
    """Summed next-token cross entropy over ``tokens [b, s]``."""
    lg = logits(w, tokens, heads=heads, eps=eps, quant=quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "quant", "rows"))
def loss_and_grads(w, tokens, labels, *, heads, eps, quant=None, rows=1):
    """Mean loss of the batch and its gradients, ``rows`` rows at a time
    so that float32 activations of the whole batch never coexist."""
    n = tokens.shape[0]
    tb = tokens.reshape(n // rows, rows, -1)
    lb = labels.reshape(n // rows, rows, -1)
    count = tokens.size
    vg = jax.value_and_grad(
        lambda w, t, l: loss_sum(w, t, l, heads=heads, eps=eps, quant=quant))

    def body(acc, xs):
        loss, g = vg(w, *xs)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
    (loss, grads), _ = lax.scan(body, zero, (tb, lb))
    return loss / count, jax.tree.map(lambda g: g / count, grads)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 1, 2))
def adam(w, m, v, grads, t, *, lr, b1, b2, eps):
    """One Adam step with bias correction and no weight decay."""
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def one(p, m, v, g):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        return p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps), m, v

    out = {k: one(w[k], m[k], v[k], grads[k]) for k in w}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def layer_norms(tree: dict) -> dict:
    """Per-leaf L2 norms, one per layer for the stacked leaves (float32
    sums): the measure the training comparison is taken in. The fused QKV
    projection counts as three leaves, its columns being ``[q | k | v]``."""
    out = {}

    def norm(x, stacked):
        sq = jnp.square(x.astype(jnp.float32))
        return jnp.sqrt(jnp.sum(sq.reshape(x.shape[0], -1), axis=1)
                        if stacked else jnp.sum(sq)[None])

    for k, x in tree.items():
        if k in ("w_qkv", "b_qkv"):
            for part, piece in zip("qkv", jnp.split(x, 3, axis=-1)):
                out[f"{k[0]}_{part}"] = norm(piece, True)
        else:
            out[k] = norm(x, k in _LAYER_KEYS)
    return out
