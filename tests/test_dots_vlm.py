"""Latent (MLA) attention, the group-limited router and one chip's share of
a layer's experts, on the serving path, against their plain reference
(``cellbench/reference/dots_vlm.py``: float32, expanded attention only, no
cache, no kernel), at small sizes with seeded random weights.

The structure is the published one (``cellbench/configs/dots-vlm1-inst
.json``): low-rank q and kv projections with their norms, a rotary part
shared by all heads under YaRN, q/k head size 24 against v head size 16,
16 experts in 4 groups of which 2 are kept and 4 experts a token, a shared
expert, one dense layer before two routed ones, an untied head.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.generation import (_cached_forward, decode_step,
                                        flatten_decode_caches,
                                        init_kv_caches, init_paged_kv_caches)
from apex_tpu.ops import _support, decode_attention
from apex_tpu.ops.rope import YarnScaling
from cellbench.arch import dots_vlm as A
from cellbench.reference import dots_vlm as R

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "cellbench", "configs",
                       "dots-vlm1-inst.json")) as _f:
    CONFIG = json.load(_f)
CONFIG.update(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=16, router_width=16, expert_range=[0, 16], n_group=4,
    topk_group=2, num_experts_per_tok=4, vocab_size=128,
    num_hidden_layers=3, first_k_dense_replace=1,
    max_position_embeddings=256,
    rope_scaling=dict(CONFIG["rope_scaling"],
                      original_max_position_embeddings=16),
    serving={"max_slots": 4, "max_len": 64, "page_size": 8},
    # nearly independent experts (two correlate at 1/17): a row sent to
    # another expert moves the result as far as it can
    seeded_weights={"expert_spread": 4.0})
SZ = A.sizes(CONFIG)
KEY = jax.random.PRNGKey(34)


def _model(dtype=jnp.float32, **over):
    m = A.model_for(CONFIG)
    return GPTModel(dataclasses.replace(m.config, params_dtype=dtype,
                                        compute_dtype=dtype, **over))


@pytest.fixture(scope="module")
def weights():
    """(canonical bf16-rounded weights, the same in the program's tree)."""
    w = jax.jit(lambda k: A.canonical(k, SZ, round_to=jnp.bfloat16))(KEY)
    tree = jax.jit(lambda w: jax.tree.map(
        lambda x: x.astype(jnp.float32), A.program_tree(w, SZ)))(w)
    return w, tree


@jax.jit
def _reference(w, ids):
    return jnp.stack(R.logits(w, ids, **A.reference_args(SZ)))


@pytest.fixture
def kernel_mode(request, monkeypatch):
    """``reference``: the jnp paths; ``interpret``: the Pallas kernels
    through the interpreter."""
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS",
                       "interpret" if request.param == "interpret" else "off")
    _support.pallas_mode.cache_clear()
    yield request.param
    _support.pallas_mode.cache_clear()


def test_reference_imports_nothing_of_the_program():
    src = open(R.__file__).read()
    assert "apex_tpu" not in src
    assert "cellbench." not in src.replace("``cellbench", "")
    # expanded attention only: no latent-coordinate query, no cache
    assert "absorb" not in src.replace("no absorbed form", "")


# -- (a) the whole forward ------------------------------------------------------

def test_full_forward_matches_reference(weights):
    """float32 against float32: 2e-5 is the rounding of two differently
    ordered float32 sums at logits of size ~0.7."""
    w, tree = weights
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 128)
    got = jax.jit(_model().apply)(tree, ids).transpose(1, 0, 2)
    ref = _reference(w, ids)
    assert float(jnp.abs(ref).max()) > 0.3
    np.testing.assert_allclose(got, ref, atol=2e-5)


def test_full_forward_in_bf16_stays_near_reference(weights):
    w, tree = weights
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, 128)
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), tree)
    got = jax.jit(_model(jnp.bfloat16).apply)(bf16, ids).transpose(1, 0, 2)
    err = jnp.abs(got.astype(jnp.float32) - _reference(w, ids))  # noqa: APX006
    assert float(jnp.median(err)) < 5e-3


def test_fp8_control_moves_the_logits_far_more_than_bf16(weights):
    w, tree = weights
    ids = jax.random.randint(jax.random.PRNGKey(7), (1, 40), 0, 128)
    ref = _reference(w, ids)
    control = jnp.stack(jax.jit(lambda w, t: R.logits(
        w, t, quant=R.fp8, **A.reference_args(SZ)))(w, ids))
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), tree)
    got = jax.jit(_model(jnp.bfloat16).apply)(bf16, ids).transpose(1, 0, 2)
    got = got.astype(jnp.float32)  # noqa: APX006 (compared in float32)
    err_program = float(jnp.median(jnp.abs(got - ref)))
    err_control = float(jnp.median(jnp.abs(control - ref)))
    assert err_control > 4 * err_program


# -- (b) absorbed against expanded ----------------------------------------------

@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-4),
                                        (jnp.bfloat16, 4e-2)])
def test_absorbed_attention_matches_expanded(weights, dtype, rtol):
    """The same 24 tokens through one layer's attention: whole (expanded:
    K and V made from the rows) and as 16 tokens then a chunk of 8 over the
    cached rows (absorbed: the query taken into the latent's coordinates).
    Equal in exact arithmetic; float32 agrees to rounding, bf16 to a few
    of its own roundings (2^-8 each) of the largest output."""
    from apex_tpu.models.transformer import LatentAttention

    _, tree = weights
    attn = LatentAttention(_model(dtype).config)
    p = jax.tree.map(lambda x: x.astype(dtype),
                     tree["transformer"]["layers"][1]["self_attention"])
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 2, 64)).astype(dtype)
    whole = jax.jit(lambda x: attn.apply(p, x))(x)
    cache = tuple(jnp.zeros((2, 32, w), dtype) for w in (16, 128))
    _, cache = jax.jit(lambda x, c: attn.apply(
        p, x, kv_cache=c, cache_index=0))(x[:16], cache)
    chunk, _ = jax.jit(lambda x, c, i: attn.apply(
        p, x, kv_cache=c, cache_index=i))(x[16:], cache, jnp.int32(16))
    scale = float(jnp.abs(whole.astype(jnp.float32)).max())  # noqa: APX006
    assert scale > 0.02
    np.testing.assert_allclose(chunk.astype(jnp.float32),  # noqa: APX006
                               whole[16:].astype(jnp.float32),  # noqa: APX006
                               atol=rtol * scale)


@pytest.mark.parametrize("kernel_mode", ["reference", "interpret"],
                         indirect=True)
@pytest.mark.parametrize("start", [0, 5, 100])
def test_flash_chunk_with_its_own_value_size(kernel_mode, start):
    """``flash_chunk_fwd`` at a q/k head size of 24 against a v head size
    of 16, four query heads on one shared K/V head, queries at a traced
    offset into a longer cache: the plain softmax over the visible
    keys."""
    from apex_tpu.ops.attention import flash_chunk_fwd

    key = jax.random.PRNGKey(start)
    q = jax.random.normal(key, (1, 4, 48, 24))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 160, 24))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 1, 160, 16))
    o, _ = jax.jit(lambda q, k, v, s: flash_chunk_fwd(
        q, k, v, q_start=s, k_start=0, causal=True, softmax_scale=0.2,
        block_q=16, block_k=32))(q, k, v,
                                                       jnp.int32(start))
    assert o.shape == (1, 4, 48, 16)
    scores = jnp.einsum("bhqd,bkd->bhqk", q, k[:, 0]) * 0.2
    seen = jnp.arange(160)[None, :] <= start + jnp.arange(48)[:, None]
    want = jnp.einsum("bhqk,bkd->bhqd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), -1), v[:, 0])
    np.testing.assert_allclose(o, want, atol=2e-6)


def test_prefill_then_flat_decode(weights):
    """Prefill 12 tokens (expanded), then decode 20 more through the flat
    latent cache (absorbed, one token over the cached rows): every
    position's logits are the reference's full forward."""
    w, tree = weights
    model = _model()
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 128)
    ref = _reference(w, ids)
    caches = init_kv_caches(model, 2, 32, stacked=False)
    assert caches[0][0].shape == (2, 32, 16)
    assert caches[0][1].shape == (2, 32, 128)
    logits, caches = jax.jit(lambda c, t: _cached_forward(
        model, tree, c, t, 0))(caches, ids[:, :12])
    np.testing.assert_allclose(logits.transpose(1, 0, 2), ref[:, :12],
                               atol=3e-5)
    caches = flatten_decode_caches(caches, SZ["L"])
    step = jax.jit(lambda c, t, i: decode_step(model, tree, c, t, i))
    for i in range(12, 32):
        logits, caches = step(caches, ids[:, i], i)
        np.testing.assert_allclose(logits, ref[:, i], atol=3e-5)


# -- (c) through the paged latent cache -----------------------------------------

@pytest.mark.parametrize("kernel_mode", ["reference", "interpret"],
                         indirect=True)
def test_paged_decode_matches_reference(weights, kernel_mode):
    """Two slots decode 30 tokens through the paged latent pools (pages of
    8), one with the jnp path and one with the Pallas kernel interpreted:
    logits are the reference's full forward."""
    w, tree = weights
    model = _model()
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 30), 0, 128)
    ref = _reference(w, ids)
    ps, pps = 8, 4
    caches = init_paged_kv_caches(model, 2 * pps, ps, jnp.float32)
    assert caches[0][0].shape == (8, 8, 16)
    assert caches[0][1].shape == (8, 8, 128)
    table = jnp.arange(2 * pps, dtype=jnp.int32).reshape(2, pps)
    step = jax.jit(lambda c, t, p: decode_step(
        model, tree, c, t, p, paged_state=table))
    for i in range(30):
        pos = jnp.full((2,), i, jnp.int32)
        logits, caches = step(caches, ids[:, i], pos)
        np.testing.assert_allclose(logits, ref[:, i], atol=4e-5)


def _gap(w, prompt, tokens):
    """The widest gap by which a served token's logit lies below the
    reference's best, over one request."""
    ids = np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
    rows = _reference(w, jnp.asarray(ids[None]))[0][len(prompt) - 1:]
    return float(jnp.max(jnp.max(rows, -1) - jnp.take_along_axis(
        rows, jnp.asarray(tokens)[:, None], -1)[:, 0]))


@pytest.mark.parametrize("budget", [None, 16])
def test_engine_serves_the_reference_logits_in_float32(weights, budget):
    """Through ``InferenceEngine``: whole-bucket prefill (expanded) or
    16-token chunks (absorbed over cached rows) into the latent pages, the
    paged decode step with one step in flight, a prefix-cache hit whose
    suffix attends over another request's interned rows. In float32 every
    served greedy token is the reference's own first choice (its logit
    within 1e-5 of the best). The engine holds experts 0..15 of 16: every
    assignment lands here."""
    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.serving import (EngineConfig, InferenceEngine, Request,
                                  SamplingParams)

    w, tree = weights
    reg = MetricsRegistry()
    eng = InferenceEngine(_model(), tree, EngineConfig(
        max_slots=4, max_len=64, page_size=8, prefill_token_budget=budget),
        metrics=reg)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 128, 19).tolist()
    prompts = [rng.integers(0, 128, n).tolist() for n in (5, 37, 12)]
    prompts += [shared + rng.integers(0, 128, 6).tolist()]
    reqs = [Request(prompt=p, max_new_tokens=m, sampling=SamplingParams())
            for p, m in zip(prompts, (20, 25, 12, 10))]
    results = eng.serve(reqs)
    # the same prefix again: two whole pages of it are interned by now
    late = Request(prompt=shared + rng.integers(0, 128, 9).tolist(),
                   max_new_tokens=8, sampling=SamplingParams())
    results += eng.serve([late])
    for rq, rs in zip(reqs + [late], results):
        assert rs.finish_reason == "length"
        assert _gap(w, rq.prompt, rs.tokens) < 1e-5
    counters = reg.counters()
    assert counters["prefix_hits"] >= 1
    assert counters["decode_steps_overlapped"] > 0
    assert eng.decode_compiles == 1 and eng.decode_retraces == 0
    if budget:
        assert results[1].prefill_chunks >= 3
    live = reg.histogram("decode_batch_size")
    assert counters["moe_rows_routed"] == live.sum * 4 * 2
    assert "moe_rows_elsewhere" not in counters
    assert reg.gauges()["kv_latent_bytes_in_use"] == (
        reg.gauges()["kv_pages_in_use"] * 3 * 8 * (16 + 128) * 4)


@pytest.fixture(scope="module")
def supervised(weights):
    """(supervisor over the interleaved tree, as the cell builds it, its
    registry)."""
    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.serving import EngineConfig, EngineSupervisor

    reg = MetricsRegistry()
    return EngineSupervisor(_model(), weights[1], EngineConfig(
        max_slots=4, max_len=64, page_size=8, prefill_token_budget=16),
        metrics=reg), reg


def test_engine_holds_the_dense_layers_gate_and_up_apart(weights,
                                                         supervised):
    """The supervisor takes the interleaved tree of ``program_tree`` and
    keeps the dense layer's ``[2*ffn, h]`` weight as ``[2, ffn, h]``, the
    caller's tree left as it was; its engines re-lay nothing more; the
    gauge says what is held so; the forward on it is ``model.apply`` on
    what was given."""
    _, tree = weights
    sup, reg = supervised

    def dense(params):
        return params["transformer"]["layers"][0]["mlp"]["dense_h_to_4h"][
            "weight"]

    given, kept, held = dense(tree), dense(sup._params), dense(
        sup.engine._params)
    assert given.shape == (2 * 96, 64) and kept.shape == (2, 96, 64)
    assert held is kept
    np.testing.assert_array_equal(held[0], given[0::2])
    np.testing.assert_array_equal(held[1], given[1::2])
    assert reg.gauges()["decode_weights_relaid_bytes"] == 2 * 96 * 64 * 4
    assert dense(sup._build_engine()._params) is kept
    assert reg.gauges()["decode_weights_relaid_bytes"] == 2 * 96 * 64 * 4
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 24), 0, 128)
    model = _model()
    want = jax.jit(model.apply)(tree, ids)
    caches = init_kv_caches(model, 2, 32, stacked=False)
    got, _ = jax.jit(lambda p, c, t: _cached_forward(model, p, c, t, 0))(
        sup.engine._params, caches, ids)
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("program", ["decode", "prefill", "suffix"])
def test_serving_programs_make_no_lane_dim_of_two(supervised, program):
    """None of the three serving programs (the suffix program is the
    chunk program) reshapes the dense layer's product to ``[..., ffn,
    2]``."""
    from serving_reference import lane_pair_reshapes, serving_program_text

    text = serving_program_text(supervised[0].engine, program)
    assert "stablehlo.dot_general" in text
    assert lane_pair_reshapes(text, 96) == []


def test_engine_with_a_share_counts_rows_elsewhere(weights):
    """An engine that holds experts 4..8 of 16: what it routes here and
    what it would hand to the other holders add up to every live row's
    four assignments a routed layer call."""
    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.serving import (EngineConfig, InferenceEngine, Request,
                                  SamplingParams)

    _, tree = weights
    part = dict(tree)
    part["transformer"] = dict(tree["transformer"])
    part["transformer"]["layers"] = [
        p if "router" not in p["mlp"] else dict(p, mlp=dict(
            p["mlp"], w_in=p["mlp"]["w_in"][4:8],
            w_out=p["mlp"]["w_out"][4:8]))
        for p in tree["transformer"]["layers"]]
    reg = MetricsRegistry()
    eng = InferenceEngine(_model(routed_expert_range=(4, 8)), part,
                          EngineConfig(max_slots=4, max_len=64, page_size=8),
                          metrics=reg)
    rng = np.random.default_rng(2)
    eng.serve([Request(prompt=rng.integers(0, 128, n).tolist(),
                       max_new_tokens=12, sampling=SamplingParams())
               for n in (6, 11, 20)])
    c = reg.counters()
    live = reg.histogram("decode_batch_size").sum
    assert c["moe_rows_routed"] + c["moe_rows_elsewhere"] == live * 4 * 2
    assert 0 < c["moe_rows_routed"] < c["moe_rows_elsewhere"]


# -- (d) the grouped router -----------------------------------------------------

def _layer(**over):
    from apex_tpu.transformer.moe import RoutedExperts, RoutedMoEConfig

    return RoutedExperts(RoutedMoEConfig(**{**dict(
        hidden_size=64, ffn_hidden_size=32, num_experts=16, top_k=4,
        route_scale=2.5, num_shared_experts=1, num_groups=4,
        topk_groups=2), **over}))


def test_grouped_router_matches_reference(weights):
    """256 rows: chosen experts and weights are the reference's, and for
    some rows the plain top-4 over all 16 would have chosen otherwise."""
    w, tree = weights
    mlp = tree["transformer"]["layers"][1]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(6), (256, 64))
    weights_, experts = jax.jit(_layer().route)(mlp, x)
    dense = jnp.zeros((256, 16)).at[
        jnp.arange(256)[:, None], experts].set(weights_)
    ref = jax.jit(lambda m: R.route(
        m, w["router"][0], w["router_bias"][0], top_k=4, n_group=4,
        topk_group=2, route_scale=2.5))(x)
    np.testing.assert_allclose(dense, ref, atol=1e-6)
    assert np.all(np.sum(np.asarray(ref) > 0, -1) == 4)
    _, plain = jax.jit(_layer(num_groups=1, topk_groups=1).route)(mlp, x)
    differs = np.any(np.sort(np.asarray(plain), -1)
                     != np.sort(np.asarray(experts), -1), axis=-1)
    assert 0 < differs.sum() < 256


def test_grouped_router_keeps_the_groups_with_the_best_two():
    """Hand-made scores: group 0 holds the single best expert and nothing
    else, groups 1 and 2 hold two good ones each. The two kept groups are
    1 and 2 (by the sum of their two best) and expert 0, the ungrouped
    top-4's first choice, is not chosen; the bias moves the selection and
    not the weights."""
    logits = np.full((1, 16), -4.0, np.float32)
    logits[0, 0] = 3.0
    logits[0, [4, 5]] = 1.0, 0.9
    logits[0, [8, 9]] = 0.8, 0.7
    params = {"router": {"weight": jnp.asarray(logits.repeat(64, 0)) / 64,
                         "bias": jnp.zeros((16,))}}
    x = jnp.ones((1, 64))
    wts, experts = _layer().route(params, x)
    assert sorted(np.asarray(experts)[0].tolist()) == [4, 5, 8, 9]
    s = jax.nn.sigmoid(jnp.asarray(logits[0, [4, 5, 8, 9]]))
    np.testing.assert_allclose(np.sort(np.asarray(wts)[0]),
                               np.sort(np.asarray(s / s.sum() * 2.5)),
                               rtol=1e-5)
    _, plain = _layer(num_groups=1, topk_groups=1).route(params, x)
    assert 0 in np.asarray(plain)[0]
    # a bias on group 3's experts brings the group in: selection only
    params["router"]["bias"] = jnp.zeros((16,)).at[12:14].set(5.0)
    wts, experts = _layer().route(params, x)
    assert {12, 13} <= set(np.asarray(experts)[0].tolist())
    assert float(wts.max()) <= 2.5


def test_group_settings_are_checked():
    with pytest.raises(ValueError, match="groups"):
        _layer(num_groups=3)
    with pytest.raises(ValueError, match="groups"):
        _layer(num_groups=8, topk_groups=1)      # 2 experts < top_k 4


# -- (e) the shares add up ------------------------------------------------------

def test_shares_add_up_to_the_uncut_layer(weights):
    """The routed parts that the four holders of 4 experts each compute,
    plus the shared expert once, are the whole layer: in the program and
    in the reference, and the two agree."""
    w, tree = weights
    mlp = tree["transformer"]["layers"][2]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(8), (48, 64))
    whole = _layer()
    full = jax.jit(lambda p, x: whole.apply(p, x[:, None])[:, 0])(mlp, x)
    total = whole.shared(mlp, x)
    ref_total = R._gated(x, w["s_in"][1], w["s_out"][1], None)
    ref_weights = R.route(x, w["router"][1], w["router_bias"][1], top_k=4,
                          n_group=4, topk_group=2, route_scale=2.5)
    for lo in range(0, 16, 4):
        held = _layer(expert_range=(lo, lo + 4))
        part = dict(mlp, w_in=mlp["w_in"][lo:lo + 4],
                    w_out=mlp["w_out"][lo:lo + 4])
        mine = jax.jit(held.routed)(part, x)
        ref_mine = R.held_experts(
            x, {"e_in": w["e_in"][1][lo:lo + 4],
                "e_out": w["e_out"][1][lo:lo + 4]},
            ref_weights[:, lo:lo + 4])
        assert float(jnp.abs(ref_mine).max()) > 1e-3
        np.testing.assert_allclose(mine, ref_mine, atol=2e-6)
        total, ref_total = total + mine, ref_total + ref_mine
    np.testing.assert_allclose(total, full, atol=2e-6)
    np.testing.assert_allclose(ref_total, full, atol=2e-6)
    uncut = R._experts(
        x, {k: w[k][1] for k in ("router", "router_bias", "e_in", "e_out",
                                 "s_in", "s_out")},
        expert_range=(0, 16), quant=None, top_k=4, n_group=4, topk_group=2,
        route_scale=2.5)
    np.testing.assert_allclose(ref_total, uncut, atol=2e-6)


# -- (f) the latent decode kernel -----------------------------------------------

def _kernel_case(b=5, heads=4, rank=128, rope=8, ps=8, pps=6, seed=0):
    rng = np.random.default_rng(seed)
    n_pages = b * pps
    lanes = decode_attention.latent_rope_lanes(rope)

    def f(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    table = rng.permutation(n_pages).reshape(b, pps).astype(np.int32)
    # ragged: a slot on its first row, one mid-page, one that fills its
    # pages to the last row, and an idle one (no page mapped)
    positions = np.asarray([0, 13, pps * ps - 1, 0, 29][:b], np.int32)
    table[3] = n_pages
    for r in range(b):
        table[r, positions[r] // ps + 1:] = n_pages
    kr_pool = f(n_pages, ps, lanes).at[:, :, rope:].set(0.0)
    return dict(
        q_latent=f(b, heads, rank), q_rope=f(b, heads, rope),
        c_new=f(b, rank), kr_new=f(b, rope), c_pages=f(n_pages, ps, rank),
        kr_pages=kr_pool, page_table=jnp.asarray(table),
        positions=jnp.asarray(positions))


@pytest.mark.parametrize("buffer_pages", [1, 4, 64])
def test_latent_kernel_interpreted_matches_reference(monkeypatch,
                                                     buffer_pages):
    """Ragged page ranges, an idle slot and a partial last round (a round
    of 4 pages over ranges of 1, 2, 6 and 4 pages; a round a page; the
    whole table a round)."""
    case = _kernel_case()
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "off")
    _support.pallas_mode.cache_clear()
    want = decode_attention.fused_latent_decode_attention(
        **case, softmax_scale=0.2)
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "interpret")
    monkeypatch.setattr(decode_attention, "_BUFFER_BYTES",
                        buffer_pages * 8 * 128 * 4)
    _support.pallas_mode.cache_clear()
    decode_attention._latent_pallas.clear_cache()
    try:
        got = decode_attention.fused_latent_decode_attention(
            **case, softmax_scale=0.2)
    finally:
        _support.pallas_mode.cache_clear()
        decode_attention._latent_pallas.clear_cache()
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)
    assert float(jnp.abs(got[0][3]).max()) == 0.0       # the idle slot
    assert float(jnp.abs(got[0][0]).max()) > 0.0


def test_latent_kernel_slot_reads_only_its_own_pages(monkeypatch):
    """NaN rows in one slot's pages, and in pages no slot maps: every other
    slot's result is finite and unchanged."""
    case = _kernel_case()
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "interpret")
    monkeypatch.setattr(decode_attention, "_BUFFER_BYTES", 4 * 8 * 128 * 4)
    _support.pallas_mode.cache_clear()
    decode_attention._latent_pallas.clear_cache()
    try:
        clean = decode_attention.fused_latent_decode_attention(
            **case, softmax_scale=0.2)[0]
        table = np.asarray(case["page_table"])
        mapped = set(table[table < 30].tolist())
        bad = sorted(set(range(30)) - mapped) + [int(table[1, 0])]
        poisoned = dict(case, c_pages=case["c_pages"].at[
            jnp.asarray(bad)].set(jnp.nan))
        got = decode_attention.fused_latent_decode_attention(
            **poisoned, softmax_scale=0.2)[0]
    finally:
        _support.pallas_mode.cache_clear()
        decode_attention._latent_pallas.clear_cache()
    for slot in (0, 2, 3, 4):
        np.testing.assert_array_equal(got[slot], clean[slot])
    assert not bool(jnp.all(jnp.isfinite(got[1])))


def test_latent_pools_are_checked():
    case = _kernel_case()
    with pytest.raises(ValueError, match="latent pools"):
        decode_attention.fused_latent_decode_attention(
            **dict(case, kr_pages=case["kr_pages"][:, :, :8]),
            softmax_scale=0.2)


# -- (g) what refuses the latent kind, by name ----------------------------------

@pytest.mark.parametrize("what,config,kw", [
    ("kv_dtype='int8'", dict(kv_dtype="int8"), {}),
    ("speculation", dict(speculation=2), {}),
    ("LoRA adapters", {}, dict(adapters=object())),
])
def test_engine_refuses_by_name(weights, what, config, kw):
    from apex_tpu.serving import EngineConfig, InferenceEngine

    _, tree = weights
    with pytest.raises(ValueError, match=f"{what} is not supported with "
                                         f"latent attention"):
        InferenceEngine(_model(), tree, EngineConfig(
            max_slots=2, max_len=32, page_size=8, **config), **kw)


def test_sharded_engine_refuses_by_name(weights):
    from apex_tpu.serving import EngineConfig
    from apex_tpu.serving.fleet.sharded import ShardedEngine
    from apex_tpu.transformer import parallel_state

    _, tree = weights
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2, devices=jax.devices()[:2])
    try:
        with pytest.raises(ValueError, match="ShardedEngine.*latent"):
            ShardedEngine(_model(), tree, EngineConfig(
                max_slots=2, max_len=32, page_size=8), mesh=mesh)
    finally:
        parallel_state.destroy_model_parallel()


def test_pools_and_windows_refuse_by_name(weights):
    _, tree = weights
    model = _model()
    with pytest.raises(ValueError, match="int8.*latent"):
        init_paged_kv_caches(model, 8, 8, quantized=True)
    with pytest.raises(ValueError, match="stacked=False"):
        init_kv_caches(model, 1, 16)
    caches = init_paged_kv_caches(model, 8, 8, jnp.float32)
    table = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    with pytest.raises(ValueError, match="speculation window"):
        _cached_forward(model, tree, caches, jnp.zeros((2, 3), jnp.int32),
                        jnp.zeros((2,), jnp.int32), paged_state=table)


@pytest.mark.parametrize("over,match", [
    (dict(q_lora_rank=None), "q_lora_rank"),
    (dict(qk_rope_head_dim=7), "even"),
    (dict(position_embedding_type="learned"), "rotary key"),
    (dict(sliding_window=8), "sliding_window"),
    (dict(num_query_groups=2), "num_query_groups"),
    (dict(qk_layernorm=True), "qk_layernorm"),
    (dict(kv_lora_rank=None), "rope_yarn"),
])
def test_config_refuses_by_name(over, match):
    base = dataclasses.asdict(_model().config)
    base["rope_yarn"] = YarnScaling(**base["rope_yarn"])
    with pytest.raises(ValueError, match=match):
        TransformerConfig(**{**base, **over})


def test_prefix_salt_names_the_latent_rows():
    from apex_tpu.serving.prefix import prefix_salt

    assert prefix_salt(_model().config).endswith(":mla16+8")


def test_yarn_frequencies():
    """Published settings (factor 40 over 4,096, beta 32 / 1, 64 rotary
    dims): the fast dims keep theta^(-2k/64), the slow ones are divided
    by the factor, the ramp lies between; the temperature is 1.3689."""
    from apex_tpu.ops.rope import yarn_inv_freq, yarn_mscale

    yarn = YarnScaling(factor=40, original_max_position_embeddings=4096,
                       mscale=1.0, mscale_all_dim=1.0)
    inv, scale = yarn_inv_freq(64, 10000.0, yarn)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    assert scale == 1.0
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[-8:], plain[-8:] / 40, rtol=1e-6)
    ratio = plain / inv
    assert np.all(np.diff(ratio) >= -1e-4) and 1.5 < ratio[16] < 39
    assert abs(yarn_mscale(40, 1.0) - 1.3689) < 1e-4
    ref_inv, ref_scale = R._yarn(64, 10000.0, (40.0, 4096, 32.0, 1.0, 1.0,
                                               1.0))
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-5)
    assert ref_scale == 1.0
