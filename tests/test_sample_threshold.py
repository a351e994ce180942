"""The sampler's per-row k-th largest is found by selection
(``engine._kth_largest``), not by sorting the vocabulary: the value, the
mask and every sampled token equal the sort-based form's, and the sort
cannot come back unnoticed."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving.engine import _kth_largest, _sample_tokens

N, V = 12, 1000


def _sort_kth(rows, k):
    order = jnp.sort(rows, axis=-1)
    return jnp.take_along_axis(order, (rows.shape[-1] - k)[:, None],
                               axis=-1)[:, 0]


def _sample_tokens_by_sort(logits, temps, topks, seeds, steps):
    """``_sample_tokens`` as it was before PR 27, kept as the oracle."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    v = logits.shape[-1]
    safe_t = jnp.where(temps > 0.0, temps, 1.0).astype(logits.dtype)
    scaled = logits / safe_t[:, None]
    order = jnp.sort(scaled, axis=-1)                      # ascending
    kth = jnp.take_along_axis(order, (v - topks)[:, None], axis=-1)
    masked = jnp.where(scaled < kth, -jnp.inf, scaled)

    def draw(seed, step, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(draw)(seeds, steps, masked).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def _rows(kind, seed=0):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((N, V)) * 4).astype(np.float32)
    if kind == "random":
        return x
    if kind == "wide":       # every exponent, both signs
        bits = rng.integers(0, 1 << 32, (N, V), dtype=np.uint64)
        x = bits.astype(np.uint32).view(np.float32)
        x = np.where(np.isnan(x), np.float32(1.5), x)
        # XLA's float compares read a denormal as zero, so the sort picks
        # any member of that tie group; the keys order them exactly
        return np.where(np.abs(x) < np.finfo(np.float32).tiny,
                        np.float32(0), x)
    if kind == "bf16_ties":  # 8 bits of mantissa: many equal values
        return np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                          .astype(jnp.float32))
    if kind == "infs":
        x[:, ::7] = -np.inf
        x[::2, 3::11] = np.inf
        x[5] = -np.inf
        return x
    if kind == "all_equal":
        return np.broadcast_to(
            rng.standard_normal((N, 1)).astype(np.float32), (N, V)).copy()
    if kind == "zeros":
        x = np.zeros((N, V), np.float32)
        x[:, ::2] = -0.0
        x[1::2, ::5] = 1.0
        x[::3, 1::5] = -1.0
        return x
    raise ValueError(kind)


def _ks(which, seed=0):
    if which == "per_row":
        return np.random.default_rng(seed).integers(
            1, V + 1, N).astype(np.int32)
    return np.full(N, {"V-1": V - 1, "V": V}.get(which, which), np.int32)


@pytest.mark.parametrize("k", [1, 2, 40, "V-1", "V", "per_row"])
@pytest.mark.parametrize("kind", ["random", "wide", "bf16_ties", "infs",
                                  "all_equal", "zeros"])
def test_kth_largest_equals_the_sorted_row(kind, k):
    rows, ks = jnp.asarray(_rows(kind)), jnp.asarray(_ks(k))
    got = np.asarray(jax.jit(_kth_largest)(rows, ks))
    want = np.asarray(_sort_kth(rows, ks))
    # equal as floats is equal bit for bit, the sign of a zero aside (the
    # sort leaves that to the input's order; the mask cannot see it)
    np.testing.assert_array_equal(got, want)
    nonzero = want != 0
    np.testing.assert_array_equal(got.view(np.uint32)[nonzero],
                                  want.view(np.uint32)[nonzero])
    np.testing.assert_array_equal(np.asarray(rows) < got[:, None],
                                  np.asarray(rows) < want[:, None])


def _traffic(seed):
    """Rows cycling greedy / T 0.7 top-k 40 / T 1.3 untruncated / T 1.0
    top-k 1 / T 0.5 top-k V-1, bf16-rounded logits (ties at the k-th)."""
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(_rows("bf16_ties", seed))
    temps = np.tile(np.float32([0.0, 0.7, 1.3, 1.0, 0.5]), 3)[:N]
    topks = np.tile(np.int32([V, 40, V, 1, V - 1]), 3)[:N]
    seeds = rng.integers(0, 2**31 - 1, N).astype(np.int32)
    return logits, jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(seeds)


@pytest.mark.parametrize("step", [0, 1, 517])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_sampled_tokens_equal_the_sort_based_form(seed, step):
    logits, temps, topks, seeds = _traffic(seed)
    steps = jnp.arange(N, dtype=jnp.int32) + step
    got = jax.jit(_sample_tokens)(logits, temps, topks, seeds, steps)
    want = _sample_tokens_by_sort(logits, temps, topks, seeds, steps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32
    greedy = np.asarray(temps) == 0
    np.testing.assert_array_equal(np.asarray(got)[greedy],
                                  np.argmax(np.asarray(logits), -1)[greedy])


@pytest.mark.parametrize("row", [0, 1, 2])     # greedy, top-k 40, untruncated
@pytest.mark.parametrize("where", ["one", "all", "negative"])
def test_a_nan_row_gets_a_token_in_range_and_disturbs_no_other(row, where):
    logits, temps, topks, seeds = _traffic(7)
    steps = jnp.arange(N, dtype=jnp.int32)
    clean = np.asarray(_sample_tokens(logits, temps, topks, seeds, steps))
    bad = np.asarray(logits).copy()
    if where == "one":
        bad[row, 123] = np.nan
    elif where == "all":
        bad[row] = np.nan
    else:
        bad[row, ::3] = -np.nan
    got = np.asarray(_sample_tokens(jnp.asarray(bad), temps, topks, seeds,
                                    steps))
    assert 0 <= got[row] < V
    others = np.arange(N) != row
    np.testing.assert_array_equal(got[others], clean[others])


@pytest.mark.parametrize("rows", [N, 1])     # the decode body, a prefill body
def test_the_compiled_sampler_holds_no_sort(rows):
    logits, temps, topks, seeds = (a[:rows] for a in _traffic(0))
    steps = jnp.arange(rows, dtype=jnp.int32)
    args = (logits, temps, topks, seeds, steps)
    sort_op = re.compile(r"stablehlo\.sort|\bsort\(")   # StableHLO, HLO
    lowered = jax.jit(_sample_tokens).lower(*args)
    assert not sort_op.search(lowered.as_text())
    assert not sort_op.search(lowered.compile().as_text())
    # the search is traced as a loop, not as unrolled passes: set-up
    # time is jax tracing this into the decode and every prefill program
    eqns = jax.make_jaxpr(_kth_largest)(logits, topks).jaxpr.eqns
    assert sum(e.primitive.name in ("while", "scan") for e in eqns) == 1
    assert len(eqns) < 24
    # and the oracle's texts do hold one, so the check can see it
    oracle = jax.jit(_sample_tokens_by_sort).lower(*args)
    assert sort_op.search(oracle.as_text())
    assert sort_op.search(oracle.compile().as_text())
