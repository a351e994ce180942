"""The drop-free routed expert layer (``transformer/moe.py``
``RoutedExperts``), its grouped products (``ops/grouped_matmul.py``), the
decode kernel's window, and the config fields for layers of more than one
kind: small sizes on the CPU, kernels interpreted. ``tests/test_afmoe.py``
holds the whole model against its plain reference."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.generation import _cached_forward, init_kv_caches
from apex_tpu.ops import decode_attention, grouped_matmul
from apex_tpu.transformer.moe import (RoutedExperts, RoutedMoEConfig,
                                      RoutingStats)
from cellbench.reference import afmoe as R
from test_afmoe import KEY, _model, weights  # noqa: F401


# -- the routed layer -----------------------------------------------------------

def _layer(k, experts=16, **over):
    return RoutedExperts(RoutedMoEConfig(
        hidden_size=32, ffn_hidden_size=16, num_experts=experts, top_k=k,
        num_shared_experts=1, route_scale=2.0, **over))


def _every_expert_masked(layer, params, x2d):
    """Every expert over every row, times a weight that is zero where the
    row did not choose it: the form the grouped products replace."""
    c = layer.config
    w, sel = layer.route(params, x2d)
    out = jnp.zeros_like(x2d)
    for e in range(c.num_experts):
        gu = x2d @ params["w_in"][e]
        f = c.ffn_hidden_size
        y = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ params["w_out"][e]
        out += jnp.sum(jnp.where(sel == e, w, 0.0), -1)[:, None] * y
    return out


@pytest.mark.parametrize("k", [1, 2, 8])
def test_routed_layer_matches_every_expert_masked(k):
    """Skewed routing (the selection bias favours three experts and bars
    one): nothing is dropped, and the sorted grouped products equal the
    every-expert form."""
    layer = _layer(k)
    params = layer.init(jax.random.PRNGKey(k))
    bias = jnp.zeros((16,)).at[jnp.array([1, 2, 3])].set(0.6).at[7].set(-9.)
    params["router"]["bias"] = bias
    params["router"]["weight"] = params["router"]["weight"] * 30
    x = jax.random.normal(jax.random.PRNGKey(9), (50, 1, 32))
    stats = RoutingStats(jnp.ones((1,), bool))
    got = layer.apply(params, x, stats)[:, 0]
    want = _every_expert_masked(layer, params, x[:, 0]) \
        + layer.shared(params, x[:, 0])
    np.testing.assert_allclose(got, want, atol=1e-6)
    rows, touched, busiest = (int(v) for v in stats.stacked()[0])
    _, sel = layer.route(params, x[:, 0])
    counts = np.bincount(np.asarray(sel).ravel(), minlength=16)
    assert counts[7] == 0 and rows == 50 * k      # none dropped, one unused
    assert touched == int((counts > 0).sum()) < 16
    assert busiest == counts.max() > 50 * k // 16     # skewed


def test_routing_stats_leave_idle_rows_out():
    """``x [s, b, h]`` with ``active [b]``: only the active batch rows'
    assignments are counted, in every sequence position; the result is
    the same with or without the collector."""
    layer = _layer(2)
    params = layer.init(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 6, 32))
    active = jnp.array([True, False, True, True, False, False])
    stats = RoutingStats(active)
    got = layer.apply(params, x, stats)
    np.testing.assert_array_equal(got, layer.apply(params, x))
    _, sel = layer.route(params, x.reshape(18, 32))
    counts = np.bincount(np.asarray(sel).reshape(3, 6, 2)[:, active].ravel(),
                         minlength=16)
    assert [int(v) for v in stats.stacked()[0]] == [
        3 * 3 * 2, (counts > 0).sum(), counts.max()]


@pytest.mark.parametrize("tile_rows", [4, 16])
def test_routed_layout_gives_each_tile_one_expert(tile_rows):
    """No expert is computed for a row that did not choose it: every
    assignment lands in a tile of its own expert, at a row of its own,
    and the tiles in use are exactly each group rounded up."""
    rng = np.random.default_rng(tile_rows)
    expert_of = rng.choice(6, 200, p=[.5, .3, .1, .06, .04, 0.]).astype(
        np.int32)
    expert_of[:9] = 6                         # nine rows held elsewhere
    dest, tile_expert, used = grouped_matmul.routed_layout(
        jnp.asarray(expert_of), 6, tile_rows)
    dest, tile_expert = np.asarray(dest), np.asarray(tile_expert)
    rows = grouped_matmul.padded_rows(200, 6, tile_rows)
    here = expert_of < 6
    assert (dest[~here] == rows).all()
    assert len(set(dest[here])) == here.sum()
    assert (tile_expert[dest[here] // tile_rows] == expert_of[here]).all()
    counts = np.bincount(expert_of[here], minlength=6)
    assert counts[5] == 0                     # an expert no row chose
    assert int(used) == sum(-(-c // tile_rows) for c in counts)
    assert dest[here].max() < int(used) * tile_rows <= rows


def test_grouped_products_kernel_matches_its_reference(pallas_kernels):
    """The two Pallas calls (interpreted) against the per-tile einsum."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    experts, h, f, tile = 5, 128, 128, 16
    expert_of = jax.random.randint(ks[0], (70,), 0, experts + 1)
    dest, tile_expert, used = grouped_matmul.routed_layout(
        expert_of, experts, tile)
    rows = grouped_matmul.padded_rows(70, experts, tile)
    x = jax.random.normal(ks[1], (rows, h))
    w_in = jax.random.normal(ks[2], (experts, h, 2 * f)) * 0.05
    w_out = jax.random.normal(ks[3], (experts, f, h)) * 0.05
    args = (x, w_in, w_out, tile_expert, used)
    got = grouped_matmul._pallas(*args, tile_rows=tile)
    want = grouped_matmul._reference(*args, tile)
    live = int(used) * tile
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5)


def test_the_shares_add_up():
    """The layer told it holds experts [0,32), [32,64), ... of 128, the
    shared expert counted once, sums to the uncut layer, which is the
    plain reference's."""
    whole = _layer(8, experts=128)
    params = whole.init(jax.random.PRNGKey(5))
    params["router"]["weight"] = params["router"]["weight"] * 30
    x = jax.random.normal(jax.random.PRNGKey(6), (40, 32))
    total = whole.shared(params, x)
    for lo in range(0, 128, 32):
        share = _layer(8, experts=128, expert_range=(lo, lo + 32))
        assert jax.eval_shape(share.init, KEY)["w_in"].shape[0] == 32
        part = dict(params, w_in=params["w_in"][lo:lo + 32],
                    w_out=params["w_out"][lo:lo + 32])
        stats = RoutingStats(jnp.ones((40,), bool))
        total = total + share.routed(part, x, stats)
        assert 0 < int(stats.stacked()[0, 0]) < 40 * 8    # its rows only
    uncut = whole.apply(params, x[:, None])[:, 0]
    np.testing.assert_allclose(total, uncut, atol=1e-6)
    ref = R._experts(x, {
        "router": params["router"]["weight"],
        "router_bias": params["router"]["bias"], "e_in": params["w_in"],
        "e_out": params["w_out"], "s_in": params["shared"]["w_in"],
        "s_out": params["shared"]["w_out"]},
        top_k=8, route_scale=2.0, quant=None)
    np.testing.assert_allclose(uncut, ref, atol=1e-6)


# -- the decode kernel's window -------------------------------------------------

@pytest.mark.parametrize("window", [None, 12, 16])
def test_decode_kernel_with_a_window_matches_its_reference(window,
                                                           pallas_kernels):
    """Contexts of 61, 6 and 34 over pages of 8: up to six pages lie
    wholly before the window and are neither fetched nor multiplied."""
    b, hl, kvh, dh, ps, pps = 3, 8, 2, 64, 8, 8    # pages of whole tiles
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    f = kvh * dh
    q = jax.random.normal(ks[0], (b, 1, hl, dh))
    k_new = jax.random.normal(ks[1], (b, 1, f))
    v_new = jax.random.normal(ks[2], (b, 1, f))
    k_pages = jax.random.normal(ks[3], (b * pps, ps, f))
    v_pages = jax.random.normal(ks[4], (b * pps, ps, f))
    table = jnp.arange(b * pps, dtype=jnp.int32).reshape(b, pps)
    pos = jnp.array([60, 5, 33], jnp.int32)
    args = (q, k_new, v_new, k_pages, v_pages, None, None, table, pos)
    got = decode_attention._pallas(*args, group=hl // kvh,
                                   sliding_window=window)
    want = decode_attention._reference(*args, hl // kvh, window)
    np.testing.assert_allclose(got[0], want[0], atol=2e-6)
    # the page walk names exactly the pages that hold a row the mask
    # lets through: none before the window, none past the last visible
    # row (without a window: every page up to the position's)
    first, stop = decode_attention.paged_page_range(
        np.asarray(pos), 1, ps, window)
    for r in range(b):
        seen = [s for s in range(pps * ps) if s <= int(pos[r])
                and (window is None or s > int(pos[r]) - window)]
        assert (int(first[r]), int(stop[r])) == (seen[0] // ps,
                                                  seen[-1] // ps + 1)


# -- the config ----------------------------------------------------------------

BASE = dict(num_layers=4, hidden_size=32, num_attention_heads=4)


@pytest.mark.parametrize("over,match", [
    (dict(attention_layer_types=("full",) * 3, sliding_window=8),
     "attention_layer_types has 3 entries for num_layers = 4"),
    (dict(attention_layer_types=("full", "local", "full", "full")),
     "attention_layer_types entries must be"),
    (dict(attention_layer_types=("sliding",) * 4),
     "sliding_window is not set"),
    (dict(num_routed_experts=4, routed_ffn_hidden_size=16,
          num_dense_layers=5), "num_dense_layers"),
    (dict(num_routed_experts=4), "needs routed_ffn_hidden_size"),
    (dict(num_routed_experts=4, num_moe_experts=4), "exclusive"),
])
def test_config_rejects_by_field_name(over, match):
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**BASE, **over)


def test_config_rejects_a_window_on_a_non_causal_model():
    from apex_tpu.transformer.enums import AttnMaskType

    with pytest.raises(ValueError, match="attention_layer_types requires "
                                         "causal"):
        TransformerConfig(**BASE, attn_mask_type=AttnMaskType.padding,
                          attention_layer_types=("full",) * 4)
    with pytest.raises(ValueError, match="sliding_window requires causal"):
        TransformerConfig(**BASE, attn_mask_type=AttnMaskType.padding,
                          sliding_window=8)


def test_scan_form_refuses_a_mixed_model(weights):
    _, tree = weights
    model = _model()
    stacked = init_kv_caches(model, 1, 16)          # the (k, v) scan form
    with pytest.raises(NotImplementedError, match="per-layer LIST"):
        _cached_forward(model, tree, stacked, jnp.zeros((1, 4), jnp.int32),
                        0)
    remat = GPTModel(dataclasses.replace(model.config, recompute=True))
    with pytest.raises(NotImplementedError, match="ROADMAP M1"):
        remat.apply(tree, jnp.zeros((1, 4), jnp.int32))
    from apex_tpu.lora.adapter import target_dims

    with pytest.raises(NotImplementedError, match="LoRA"):
        target_dims(model.config)
