"""A model whose layers differ, on the serving path, against its plain
reference (``cellbench/reference/afmoe.py``: float32, no kernel, no cache,
no sort), at small sizes with seeded random weights.

Window and full attention in one layer list, a dense layer before the
routed ones, gated QK-normed attention with a head size that is not
hidden / heads, four norms a block, an untied head, and the drop-free
routed expert layer (``transformer/moe.py`` ``RoutedExperts``) with its
grouped products (``ops/grouped_matmul.py``).
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.generation import (_cached_forward, decode_step,
                                        flatten_decode_caches,
                                        init_kv_caches, init_paged_kv_caches)
from cellbench.arch import afmoe as A
from cellbench.reference import afmoe as R

CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 3, "num_shared_experts": 1,
    "vocab_size": 128, "sliding_window": 8, "num_hidden_layers": 4,
    "num_dense_layers": 1, "max_position_embeddings": 64,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "mup_enabled": True, "initializer_range": 0.02,
    "serving": {"max_slots": 4, "max_len": 64, "page_size": 8},
    # nearly independent experts (two correlate at 1/17): a row sent to
    # another expert moves the result as far as it can
    "seeded_weights": {"expert_spread": 4.0},
}
SZ = A.sizes(CONFIG)
KEY = jax.random.PRNGKey(30)


def _model(dtype=jnp.float32):
    m = A.model_for(CONFIG)
    return GPTModel(dataclasses.replace(m.config, params_dtype=dtype,
                                        compute_dtype=dtype))


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


def test_reference_imports_nothing_of_the_program():
    import cellbench.reference.afmoe as ref

    src = open(ref.__file__).read()
    assert "apex_tpu" not in src.replace("``apex_tpu", "")
    assert "cellbench." not in src.replace("``cellbench", "").replace(
        "arch/afmoe.py", "")


def test_full_forward_matches_reference(weights):
    w, tree = weights
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 128)
    got = jax.jit(_model().apply)(tree, ids).transpose(1, 0, 2)
    ref = _reference(w, ids)
    assert float(jnp.abs(ref).max()) > 0.3
    np.testing.assert_allclose(got, ref, atol=2e-5)


def test_full_forward_in_bf16_stays_near_reference(weights):
    w, tree = weights
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, 128)
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), tree)
    got = jax.jit(_model(jnp.bfloat16).apply)(bf16, ids).transpose(1, 0, 2)
    ref = _reference(w, ids)
    # rounding, or rarely another expert where two scores nearly tie
    # (the bf16 program's output is compared in the reference's float32)
    err = jnp.abs(got.astype(jnp.float32) - ref)  # noqa: APX006
    assert float(jnp.median(err)) < 5e-3


def test_prefill_then_flat_decode_across_the_window(weights):
    """Prefill 12 tokens (past the window of 8), then decode 20 more
    through the flat cache: every position's logits are the reference's
    full forward."""
    w, tree = weights
    model = _model()
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 128)
    ref = _reference(w, ids)
    caches = init_kv_caches(model, 2, 32, stacked=False)
    logits, caches = jax.jit(lambda c, t: _cached_forward(
        model, tree, c, t, 0))(caches, ids[:, :12])
    np.testing.assert_allclose(logits.transpose(1, 0, 2), ref[:, :12],
                               atol=2e-5)
    caches = flatten_decode_caches(caches, SZ["L"])
    step = jax.jit(lambda c, t, i: decode_step(model, tree, c, t, i))
    for i in range(12, 32):
        logits, caches = step(caches, ids[:, i], i)
        np.testing.assert_allclose(logits, ref[:, i], atol=2e-5)


@pytest.mark.parametrize("kernel", ["reference", "interpret"])
def test_paged_decode_across_the_window(weights, kernel, monkeypatch):
    """Two slots at different positions decode 30 tokens through the
    paged pool (pages of 4: several lie wholly before the window by the
    end), one with the jnp path and one with the Pallas kernel
    interpreted: logits are the reference's full forward."""
    from apex_tpu.ops import _support

    if kernel == "interpret":
        monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "interpret")
    _support.pallas_mode.cache_clear()
    try:
        w, tree = weights
        model = _model()
        ids = jax.random.randint(jax.random.PRNGKey(4), (2, 30), 0, 128)
        ref = _reference(w, ids)
        ps, pps = 4, 8
        caches = init_paged_kv_caches(model, 2 * pps, ps, jnp.float32)
        table = jnp.arange(2 * pps, dtype=jnp.int32).reshape(2, pps)
        step = jax.jit(lambda c, t, p: decode_step(
            model, tree, c, t, p, paged_state=table))
        for i in range(30):
            pos = jnp.full((2,), i, jnp.int32)
            logits, caches = step(caches, ids[:, i], pos)
            np.testing.assert_allclose(logits, ref[:, i], atol=3e-5)
    finally:
        _support.pallas_mode.cache_clear()


def test_engine_serves_the_mixed_model_token_exact_in_float32(weights):
    """Through ``InferenceEngine``: bucketed prefill into pages, paged
    decode, the routed layer's counters read back with the tick. In
    float32 every served greedy token is the reference's own first
    choice (gap 0 up to a tie in the last bits)."""
    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.serving import (EngineConfig, InferenceEngine, Request,
                                  SamplingParams)

    w, tree = weights
    reg = MetricsRegistry()
    eng = InferenceEngine(_model(), tree, EngineConfig(
        max_slots=4, max_len=64, page_size=8), metrics=reg)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, 128, n).tolist(),
                    max_new_tokens=m, sampling=SamplingParams())
            for n, m in [(5, 30), (20, 40), (12, 20), (30, 30)]]
    seen = []
    results = eng.serve(reqs, on_tick=lambda e, i: seen.append(
        reg.gauges().get("kv_pages_out_of_window", 0.0)))
    for rq, rs in zip(reqs, results):
        ids = np.asarray(list(rq.prompt) + list(rs.tokens[:-1]), np.int32)
        lg = _reference(w, jnp.asarray(ids[None]))[0]
        rows = lg[len(rq.prompt) - 1:]
        gap = jnp.max(rows, -1) - jnp.take_along_axis(
            rows, jnp.asarray(rs.tokens)[:, None], -1)[:, 0]
        assert float(gap.max()) < 1e-5
    counters = reg.counters()
    steps = counters["decode_steps"]
    # every decode step routes its LIVE slots x 3 experts in each of 3
    # layers: an idle slot's row is left out
    live = reg.histogram("decode_batch_size")
    assert live.min < 4 and counters["moe_rows_routed"] == live.sum * 3 * 3
    touched = reg.histogram("moe_experts_touched")
    assert touched.count == steps * 3
    assert 1 <= touched.min and touched.max <= 8
    assert reg.histogram("moe_max_expert_rows").max <= 4
    # contexts pass the window of 8 by several pages of 8: 3 of the 4
    # layers are window layers
    assert max(seen) > 0 and max(seen) % 0.75 == 0
    # one step in flight: the carried vector holds the routing counts
    # behind the tokens and the program was compiled for it once; no
    # request ends on EOS, so no row was thrown away
    assert eng._carry.shape == (4 + 3 * 3,)
    assert eng.decode_compiles == 1 and eng.decode_retraces == 0
    assert counters["decode_steps_overlapped"] >= steps - 4
    assert counters["decode_rows_dropped"] == 0


@pytest.fixture(scope="module")
def served(weights):
    """(engine over the interleaved tree, its registry)."""
    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.serving import EngineConfig, InferenceEngine

    reg = MetricsRegistry()
    return InferenceEngine(_model(), weights[1], EngineConfig(
        max_slots=4, max_len=64, page_size=8), metrics=reg), reg


def test_engine_holds_the_dense_layers_gate_and_up_apart(weights, served):
    """The engine takes the interleaved tree the adapter builds and holds
    the one dense layer's ``[2*ffn, h]`` weight as ``[2, ffn, h]``; the
    gauge says how much was re-laid; the forward on what it holds is
    ``model.apply`` on what it was given."""
    _, tree = weights
    eng, reg = served
    given = tree["transformer"]["layers"][0]["mlp"]["dense_h_to_4h"][
        "weight"]
    held = eng._params["transformer"]["layers"][0]["mlp"]["dense_h_to_4h"][
        "weight"]
    assert given.shape == (2 * 96, 64) and held.shape == (2, 96, 64)
    np.testing.assert_array_equal(held[0], given[0::2])
    np.testing.assert_array_equal(held[1], given[1::2])
    assert reg.gauges()["decode_weights_relaid_bytes"] == 2 * 96 * 64 * 4
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 24), 0, 128)
    model = _model()
    want = jax.jit(model.apply)(tree, ids)
    caches = init_kv_caches(model, 2, 32, stacked=False)
    got, _ = jax.jit(lambda p, c, t: _cached_forward(model, p, c, t, 0))(
        eng._params, caches, ids)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("program", ["decode", "prefill", "suffix"])
def test_serving_programs_make_no_lane_dim_of_two(served, program):
    """No serving program of the gated model reshapes the dense layer's
    product to ``[..., ffn, 2]``."""
    from serving_reference import lane_pair_reshapes, serving_program_text

    text = serving_program_text(served[0], program)
    assert "stablehlo.dot_general" in text
    assert lane_pair_reshapes(text, 96) == []


def test_training_forward_keeps_its_lane_dim_of_two():
    """The training forward takes the interleaved weight and slices the
    pair as it did (its digests stand: ``PARENT_PROGRAMS`` below)."""
    from serving_reference import lane_pair_reshapes

    model = _model()
    text = jax.jit(model.apply).lower(
        jax.eval_shape(model.init, KEY),
        jnp.zeros((1, 16), jnp.int32)).as_text()
    assert len(lane_pair_reshapes(text, 96)) == 1


def test_routed_streams_one_step_ahead_are_the_lock_step_engines(weights):
    """The routed model with one decode step in flight (the fed token
    sliced from in front of the routing counts of the step before)
    against the same engine under ``speculation=2``, whose tick reads
    every step it dispatches: sampled and greedy streams, one ending on
    an EOS that the plain engine learns a step late."""
    from apex_tpu.observability import MetricsRegistry
    from apex_tpu.serving import (EngineConfig, InferenceEngine, Request,
                                  SamplingParams)

    _, tree = weights
    rng = np.random.default_rng(1)
    specs = [(rng.integers(0, 128, n).tolist(), m, s) for n, m, s in [
        (6, 12, SamplingParams(temperature=0.9, top_k=20, seed=3)),
        (14, 9, SamplingParams()),
        (9, 10, SamplingParams(temperature=1.2, seed=8))]]

    def serve(eos=None, **config):
        reg = MetricsRegistry()
        with InferenceEngine(_model(), tree, EngineConfig(
                max_slots=4, max_len=64, page_size=8, **config),
                metrics=reg) as eng:
            out = eng.serve([Request(prompt=p, max_new_tokens=m, sampling=s,
                                     eos_token=eos if i == 0 else None)
                             for i, (p, m, s) in enumerate(specs)])
            assert eng.decode_retraces == 0
        return [r.tokens for r in out], reg.counters()

    free, _ = serve(speculation=2)
    n = next(i for i in range(1, 11) if free[0][i] not in free[0][:i])
    want, lock_step = serve(free[0][n], speculation=2)
    assert want[0] == free[0][:n + 1] and want[1:] == free[1:]
    got, counters = serve(free[0][n])
    assert got == want
    assert lock_step["decode_steps_overlapped"] == 0
    assert counters["decode_steps_overlapped"] > 0
    assert counters["decode_rows_dropped"] == 1
    assert counters["moe_rows_routed"] > 0


def test_fp8_control_moves_the_logits_far_more_than_bf16(weights):
    w, tree = weights
    ids = jax.random.randint(jax.random.PRNGKey(7), (1, 32), 0, 128)
    ref = _reference(w, ids)
    control = jnp.stack(jax.jit(lambda w, t: R.logits(
        w, t, quant=R.fp8, **A.reference_args(SZ)))(w, ids))
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), tree)
    got = jax.jit(_model(jnp.bfloat16).apply)(bf16, ids).transpose(1, 0, 2)
    got = got.astype(jnp.float32)  # noqa: APX006 (compared in float32)
    err_program = float(jnp.median(jnp.abs(got - ref)))
    err_control = float(jnp.median(jnp.abs(control - ref)))
    assert err_control > 4 * err_program


# -- every model that was there, under the new defaults --------------------------

def _lowered_programs():
    from apex_tpu.models.bert import BertModel
    from apex_tpu.models.encoder_decoder import EncoderDecoderModel
    from apex_tpu.models.generation import generate
    from apex_tpu.transformer.enums import AttnMaskType
    from serving_reference import serving_programs

    base = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
                vocab_size=64, max_position_embeddings=32,
                hidden_dropout=0.0, attention_dropout=0.0)
    cases = {
        "gpt2-style": dict(),
        "rope-rms-swiglu-gqa-window": dict(
            position_embedding_type="rope", normalization="rmsnorm",
            activation="swiglu", num_query_groups=2, sliding_window=8,
            ffn_hidden_size=48),
        "switch-moe-top2": dict(num_moe_experts=4, moe_top_k=2),
        "bf16-recompute": dict(compute_dtype=jnp.bfloat16, recompute=True,
                               scan_unroll=2),
    }
    key = jax.random.PRNGKey(0)
    tok = jnp.zeros((2, 16), jnp.int32)
    out = {}
    for name, over in cases.items():
        m = GPTModel(TransformerConfig(**{**base, **over}))
        p = jax.eval_shape(m.init, key)
        out[name + "/init"] = (m.init, key)
        out[name + "/loss"] = (lambda p, t, m=m: m.apply(p, t, t), p, tok)
        out[name + "/generate"] = (
            lambda p, t, m=m: generate(m, p, t[:, :4], 6), p, tok)
    b = BertModel(TransformerConfig(
        **{**base, "attn_mask_type": AttnMaskType.padding}))
    out["bert/forward"] = (lambda p, t: b.apply(p, t),
                           jax.eval_shape(b.init, key), tok)
    e = EncoderDecoderModel(TransformerConfig(**base))
    out["encoder-decoder/loss"] = (lambda p, t: e.apply(p, t, t, t),
                                   jax.eval_shape(e.init, key), tok)
    # the serving engine's own step programs (what the gpt2m.serve-* cells
    # run), at a tiny GPT-2
    from apex_tpu.serving import EngineConfig, InferenceEngine

    m = GPTModel(TransformerConfig(**{**base, "compute_dtype": jnp.bfloat16}))
    params = m.init(key)
    eng = InferenceEngine(m, params, EngineConfig(
        max_slots=4, max_len=32, page_size=8))
    # decode, and the prefill programs, one bucket each, with the
    # arguments InferenceEngine._prefill_into builds for them
    names = {"decode": "paged-decode", "prefill": "paged-prefill",
             "suffix": "suffix-prefill"}
    for name, program in serving_programs(eng).items():
        out["engine/" + names[name]] = program
    spec = InferenceEngine(m, params, EngineConfig(
        max_slots=4, max_len=32, page_size=8, speculation=3))
    out["engine/spec-decode"] = (spec._decode_fn._fn, *spec._decode_args())
    # the same three of the mixed, routed model above (what
    # trinitym.serve-decode-4k runs): window and full layers, the
    # ungrouped router, every expert held
    m = _model(jnp.bfloat16)
    eng = InferenceEngine(m, m.init(key), EngineConfig(
        max_slots=4, max_len=32, page_size=8))
    for name, program in serving_programs(eng).items():
        out["routed/" + names[name]] = program
    return out


#: sha256 (first 16 hex digits) of each program's lowered StableHLO text AT
#: THE PARENT OF PR 30 (commit 92d9db2), read there with this very
#: function: the same text lowers to the same program, so the outputs of
#: every model that existed are unchanged bit for bit under the new
#: config fields' defaults. A PR that changes one of these programs on
#: purpose reads the digests anew and says so: PR 33 gave the plain
#: decode program the carried token vector and the mask that chooses
#: between it and the host's tokens (``engine/paged-decode``, read anew
#: there; the prefill programs and the spec-decode body kept theirs).
#: PR 36 re-lays a gated dense layer's gate/up weight halves apart where
#: the serving side takes the weights, so the four programs that serve a
#: gated dense layer read a ``[2, ffn, h]`` weight and slice the product
#: at ``ffn`` (``rope-rms-swiglu-gqa-window/generate`` and the three
#: ``routed/*``, read anew there); every other digest, the gated model's
#: ``init`` and ``loss`` among them, stood: training and the models that
#: are not gated lower to the text they did. Carrying a prefill's first
#: token to the decode step on the device gave the two prefill programs
#: the carried token vector and the slot's index as their last arguments
#: and the vector with the first token put in as their last result
#: (``engine/paged-prefill``, ``engine/suffix-prefill`` and the two
#: ``routed/*`` prefills, read anew then: the text before it with two
#: arguments, one result and one scatter more); decode and spec-decode
#: kept theirs.
PARENT_PROGRAMS = {
    "gpt2-style/init": "54950bef44af6471",
    "gpt2-style/loss": "eff5da79fceee869",
    "gpt2-style/generate": "1e9382acb4c9d6d3",
    "rope-rms-swiglu-gqa-window/init": "4be10ee2967089e2",
    "rope-rms-swiglu-gqa-window/loss": "015f5b5ee09f107a",
    "rope-rms-swiglu-gqa-window/generate": "14b5ba9961434cde",
    "switch-moe-top2/init": "bc22a398d187ac0e",
    "switch-moe-top2/loss": "41e4e2b0e51b05d4",
    "switch-moe-top2/generate": "ff72039acdf97118",
    "bf16-recompute/init": "54950bef44af6471",
    "bf16-recompute/loss": "46e6b2ed813977ba",
    "bf16-recompute/generate": "1c6985bfac1e684d",
    "bert/forward": "35e3f4c7245fd3e6",
    "encoder-decoder/loss": "f2746609c8484d9e",
    "engine/paged-decode": "59e792bd043da028",
    "engine/paged-prefill": "450d89270bab5e2b",
    "engine/suffix-prefill": "a880e4ad656e0bea",
    "engine/spec-decode": "5d15c62e192aebbf",
    # read at the parent of PR 34 (commit 8cb1cae), which gave the router
    # its groups, the engine its latent kind and the chunk program's body
    # a second cache form: the routed model's programs were the parent's
    # until PR 36 (above), whose own these three are
    "routed/paged-decode": "1602bcec0c68f676",
    "routed/paged-prefill": "b5667977bec981fa",
    "routed/suffix-prefill": "fad958ffe6875b4e",
}


def program_digests() -> dict:
    """sha256 (16 hex digits) of every program's lowered text."""
    out = {}
    for name, (fn, *args) in _lowered_programs().items():
        lowered = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(*args)
        out[name] = hashlib.sha256(
            lowered.as_text().encode()).hexdigest()[:16]
    return out


@pytest.fixture(scope="module")
def digests_here():
    """Read in a fresh interpreter: the lowered text also carries the
    process's jax settings (matmul precision, a mesh left set), which
    earlier test modules of a worker may have changed."""
    import json
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    env = {k: v for k, v in os.environ.items()
           if k not in ("APEX_TPU_FORCE_PALLAS", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.dirname(here), here, env.get("PYTHONPATH", "")]))
    done = subprocess.run(
        [sys.executable, "-c", "import json, test_afmoe as t; "
         "print('DIGESTS', json.dumps(t.program_digests()))"],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = next(l for l in done.stdout.splitlines()
                if l.startswith("DIGESTS "))
    return json.loads(line[len("DIGESTS "):])


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_existing_models_lower_to_the_parents_program(name, digests_here):
    assert digests_here[name] == PARENT_PROGRAMS[name]
