"""RoPE wired through the model stack (``position_embedding_type="rope"``).

The reference ships fused RoPE kernels (``csrc/megatron/fused_rotary_
positional_embedding``) but its standalone GPT uses learned positions; here
rotary is a first-class config option. Anchors:

- no position-embedding table is allocated;
- relative-position property: shifting an entire causal sequence window
  changes nothing about next-token logits when positions are rotary and the
  content is shift-invariant (checked via decode offsets);
- cached decode logits match the full forward (rope offset = cache_index,
  rotate-then-cache);
- training decreases loss; TP=2 reproduces single-rank numerics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.generation import (
    decode_step,
    init_kv_caches,
    is_gated_mlp_weight,
    preslice_layer_params,
    split_gated_mlp_params,
)


def _cfg(**kw):
    d = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
             position_embedding_type="rope", vocab_size=64,
             max_position_embeddings=32, hidden_dropout=0.0,
             attention_dropout=0.0)
    d.update(kw)
    return TransformerConfig(**d)


def test_no_position_table():
    model = GPTModel(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    assert "position_embeddings" not in params["embedding"]
    assert "position_embeddings" not in model.spec()["embedding"]


def test_rope_freqs_layout():
    from apex_tpu.models.transformer import rope_freqs

    f = rope_freqs(0, 8, 16, 10000.0)
    assert f.shape == (8, 1, 1, 16)
    np.testing.assert_allclose(np.asarray(f[0, 0, 0]), 0.0)   # pos 0 -> no rot
    # Megatron concat(f, f) convention
    np.testing.assert_allclose(np.asarray(f[3, 0, 0, :8]),
                               np.asarray(f[3, 0, 0, 8:]))


def test_rope_changes_the_function():
    """rope vs none with identical params must differ on varied tokens —
    i.e. the rotation is actually applied."""
    rope = GPTModel(_cfg())
    none = GPTModel(_cfg(position_embedding_type="none"))
    params = rope.init(jax.random.PRNGKey(0))   # same tree shape for both
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 64)
    out_rope = rope.apply(params, toks)
    out_none = none.apply(params, toks)
    assert not np.allclose(np.asarray(out_rope, np.float32),
                           np.asarray(out_none, np.float32), atol=1e-4)


def test_relative_position_property():
    """A uniform token sequence yields position-independent outputs under
    rope (identical values at every slot make attention output independent
    of the rotated scores) — the relative-position contract; learned
    positions break it."""
    model = GPTModel(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.full((1, 8), 5, jnp.int32)
    logits = model.apply(params, toks)
    np.testing.assert_allclose(np.asarray(logits[0, 0], np.float32),
                               np.asarray(logits[7, 0], np.float32),
                               atol=1e-4)


@pytest.mark.slow
def test_cached_decode_matches_full_forward():
    model = GPTModel(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
    full = model.apply(params, tokens)
    caches = init_kv_caches(model, 2, 16)
    for i in range(10):
        logits, caches = decode_step(model, params, caches, tokens[:, i], i)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[i]).astype(np.float32),
            rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # decode parity sweep: slow tier (ROADMAP)


def test_rope_with_gqa_decode():
    model = GPTModel(_cfg(num_attention_heads=8, num_query_groups=2))
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 64)
    full = model.apply(params, tokens)
    caches = init_kv_caches(model, 2, 8)
    for i in range(6):
        logits, caches = decode_step(model, params, caches, tokens[:, i], i)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[i]).astype(np.float32),
            rtol=2e-4, atol=2e-4)


def test_partial_rotary():
    model = GPTModel(_cfg(rotary_percent=0.5))
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 64)
    logits = model.apply(params, toks)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_training_decreases_loss():
    model = GPTModel(_cfg())
    params = model.init(jax.random.PRNGKey(0))
    from apex_tpu.optimizers import FusedAdam

    opt = FusedAdam(lr=2e-3)
    st = opt.init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    labs = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 64)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(lambda p: model.apply(p, toks, labs))(p)
        p, s = opt.step(g, p, s)
        return p, s, l

    losses = []
    for _ in range(5):
        params, st, l = step(params, st)
        losses.append(float(l))
    assert losses[-1] < losses[0]


def _tp_parity_train(tp, cfg_kwargs, sp=False, steps=3):
    """Train the same seeded GPT under (tp, sp); return the loss trace."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=tp)
    model = GPTModel(_cfg(sequence_parallel=sp, **cfg_kwargs))
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-3)
    ost = opt.init(params)
    step = make_train_step(
        lambda p, b, r: model.apply(p, b["tokens"], b["labels"], rng=r),
        opt, mesh, model.spec(),
        {"tokens": P("data"), "labels": P("data")},
        params_template=params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    labs = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 64)
    losses = []
    for _ in range(steps):
        params, ost, loss = step(params, ost,
                                 {"tokens": toks, "labels": labs},
                                 jax.random.PRNGKey(3))
        losses.append(float(loss))
    parallel_state.destroy_model_parallel()
    return losses


def _losses_after_training(model, steps=4, lr=2e-3):
    from apex_tpu.optimizers import FusedAdam

    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=lr)
    st = opt.init(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    labs = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(lambda p: model.apply(p, toks, labs))(p)
        return opt.step(g, p, s) + (l,)

    losses = []
    for _ in range(steps):
        params, st, l = step(params, st)
        losses.append(float(l))
    return losses, params


@pytest.mark.slow
def test_tp2_matches_unsharded():
    np.testing.assert_allclose(_tp_parity_train(1, {}),
                               _tp_parity_train(2, {}),
                               atol=2e-5, rtol=2e-5)


def test_invalid_position_type_rejected():
    with pytest.raises(ValueError, match="position_embedding_type"):
        _cfg(position_embedding_type="rotary")


def test_invalid_rotary_percent_rejected():
    with pytest.raises(ValueError, match="rotary_percent"):
        _cfg(rotary_percent=1.5)
    with pytest.raises(ValueError, match="rotary_percent"):
        _cfg(rotary_percent=0.0)
    with pytest.raises(ValueError):
        _cfg(rotary_percent=0.01).rotary_dim   # rounds below 2 channels


def test_pipelined_param_tree_matches_gpt():
    """PipelinedGPT under rope must not allocate the dead position table
    (same embedding tree as GPTModel for the same config)."""
    from apex_tpu.models import PipelinedGPT

    cfg = _cfg(num_layers=2)
    pp = PipelinedGPT(cfg, pipeline_size=1, num_microbatches=1)
    params = pp.init(jax.random.PRNGKey(0))
    assert "position_embeddings" not in params["embedding"]
    assert "position_embeddings" not in pp.spec()["embedding"]


class TestActivations:
    """MLP activation config incl. gated variants (swiglu/geglu — exceeds
    the gelu-only reference ParallelMLP). Gated runs one fused 2*ffn
    column projection with gate/up unit-interleaved."""

    @pytest.mark.parametrize("act", [
        "gelu", "swiglu",
        pytest.param("relu", marks=pytest.mark.slow),
        pytest.param("geglu", marks=pytest.mark.slow)])
    def test_trains(self, act):
        model = GPTModel(_cfg(activation=act,
                              position_embedding_type="learned"))
        losses, params = _losses_after_training(model)
        if act in ("swiglu", "geglu"):
            w = params["transformer"]["layers"]["mlp"]["dense_h_to_4h"][
                "weight"]
            assert w.shape[-2] == 2 * 4 * 64   # fused [2*ffn, h], per layer
        assert losses[-1] < losses[0]

    @pytest.mark.slow
    def test_swiglu_tp2_matches_unsharded(self):
        np.testing.assert_allclose(
            _tp_parity_train(1, {"activation": "swiglu"}),
            _tp_parity_train(2, {"activation": "swiglu"}),
            atol=2e-5, rtol=2e-5)

    def test_invalid_activation_rejected(self):
        with pytest.raises(ValueError, match="activation"):
            _cfg(activation="swish")


class TestGatedWeightHalvesApart:
    """What the serving side does to a gated ``ParallelMLP`` at intake
    (``split_gated_mlp_params``): the interleaved ``[2*ffn, h]`` weight
    re-laid once to ``[2, ffn, h]``, and ``ParallelMLP.apply`` computing
    the same numbers from either form."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("act", ["swiglu", "geglu"])
    def test_apply_matches_the_interleaved_form(self, act, dtype):
        from apex_tpu.models.transformer import ParallelMLP

        dt = jnp.dtype(dtype)
        c = _cfg(activation=act, ffn_hidden_size=96)
        mlp = ParallelMLP(c)
        params = jax.tree.map(lambda x: x.astype(dt),
                              mlp.init(jax.random.PRNGKey(0)))
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 3, 64)).astype(dt)
        apart, nbytes = split_gated_mlp_params({"mlp": params}, c)
        w = apart["mlp"]["dense_h_to_4h"]["weight"]
        assert w.shape == (2, 96, 64) and nbytes == w.size * dt.itemsize
        inter = params["dense_h_to_4h"]["weight"]
        np.testing.assert_array_equal(np.asarray(w[0], np.float32),
                                      np.asarray(inter[0::2], np.float32))
        np.testing.assert_array_equal(np.asarray(w[1], np.float32),
                                      np.asarray(inter[1::2], np.float32))
        want = np.asarray(mlp.apply(params, x), np.float32)
        got = np.asarray(mlp.apply(apart["mlp"], x), np.float32)
        if dt == jnp.float32:
            # each output column is the same dot over h
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("act", ["swiglu", "geglu"])
    def test_lora_delta_lands_on_the_same_columns(self, act):
        """The LoRA delta's out columns are interleaved, as ``B``'s are;
        under the re-laid weight each still meets its own column."""
        from apex_tpu.models.transformer import ParallelMLP

        c = _cfg(activation=act, ffn_hidden_size=96)
        mlp = ParallelMLP(c)
        params = mlp.init(jax.random.PRNGKey(0))
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        x = jax.random.normal(ks[0], (5, 3, 64))
        lora = {"A": jax.random.normal(ks[1], (3, 64, 4)) * 0.3,
                "B": jax.random.normal(ks[2], (3, 4, 2 * 96)) * 0.3}
        apart, _ = split_gated_mlp_params(params, c)
        want = mlp.apply(params, x, lora=lora)
        assert float(jnp.abs(want - mlp.apply(params, x)).max()) > 1e-3
        np.testing.assert_array_equal(
            np.asarray(mlp.apply(apart, x, lora=lora)), np.asarray(want))

    def test_stacked_and_listed_layers_are_both_relaid(self):
        model = GPTModel(_cfg(activation="swiglu"))
        params = model.init(jax.random.PRNGKey(0))
        stacked, n_stacked = split_gated_mlp_params(params, model.config)
        w = stacked["transformer"]["layers"]["mlp"]["dense_h_to_4h"][
            "weight"]
        assert w.shape == (2, 2, 4 * 64, 64)          # [L, 2, ffn, h]
        listed, n_listed = split_gated_mlp_params(
            preslice_layer_params(params, 2), model.config)
        for i, layer in enumerate(listed["transformer"]["layers"]):
            np.testing.assert_array_equal(
                np.asarray(layer["mlp"]["dense_h_to_4h"]["weight"]),
                np.asarray(w[i]))
        assert n_stacked == n_listed == w.size * 4

    def test_identity_on_a_relaid_tree_and_every_other_leaf(self):
        model = GPTModel(_cfg(activation="swiglu"))
        params = model.init(jax.random.PRNGKey(0))
        once, n_once = split_gated_mlp_params(params, model.config)
        twice, n_twice = split_gated_mlp_params(once, model.config)
        assert n_once == n_twice > 0
        before = jax.tree_util.tree_flatten_with_path(params)[0]
        for (path, x), y, z in zip(before, jax.tree.leaves(once),
                                   jax.tree.leaves(twice)):
            assert z is y                     # nothing is re-laid twice
            if not is_gated_mlp_weight(path):
                assert y is x, path           # nor anything else touched
        assert sum(is_gated_mlp_weight(p) for p, _ in before) == 1

    @pytest.mark.parametrize("act", ["gelu", "relu"])
    def test_identity_on_a_model_that_is_not_gated(self, act):
        model = GPTModel(_cfg(activation=act))
        params = model.init(jax.random.PRNGKey(0))
        same, nbytes = split_gated_mlp_params(params, model.config)
        assert same is params and nbytes == 0

    def test_generate_matches_the_interleaved_decode(self):
        """``generate()`` re-lays the weight at intake; its greedy stream
        is the one ``decode_step`` gives on the interleaved params."""
        from apex_tpu.models.generation import generate

        model = GPTModel(_cfg(
            activation="swiglu", untie_embeddings_and_output_weights=True,
            init_method_std=0.3))
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 64)
        out = np.asarray(generate(model, params, prompt, 6, max_len=16))
        caches = init_kv_caches(model, 2, 16)
        tok, want = prompt[:, 0], []
        for i in range(10):
            logits, caches = decode_step(model, params, caches, tok, i)
            tok = (prompt[:, i + 1] if i + 1 < 5
                   else jnp.argmax(logits, -1).astype(prompt.dtype))
            if i + 1 >= 5:
                want.append(np.asarray(tok))
        np.testing.assert_array_equal(out[:, 5:], np.stack(want, 1))
        assert len(set(out[0, 5:].tolist())) > 1   # a stream that varies


class TestNormalization:
    """normalization="rmsnorm" (LLaMA-class, bias-free RMS statistics via
    the fused RMSNorm kernel) vs the reference's LayerNorm default."""

    def test_rmsnorm_params_have_no_bias(self):
        model = GPTModel(_cfg(normalization="rmsnorm"))
        params = model.init(jax.random.PRNGKey(0))
        ln = params["transformer"]["layers"]["input_layernorm"]
        assert "bias" not in ln and "weight" in ln
        fln = params["transformer"]["final_layernorm"]
        assert "bias" not in fln

    def test_rmsnorm_trains_llama_trio(self):
        model = GPTModel(_cfg(normalization="rmsnorm", activation="swiglu"))
        losses, _ = _losses_after_training(model)
        assert losses[-1] < losses[0]

    def test_rmsnorm_matches_manual(self):
        from apex_tpu.models.transformer import _ln, _ln_params

        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 32))
        p = _ln_params(32, jnp.float32, "rmsnorm")
        y = _ln(p, x, 1e-5, norm="rmsnorm")
        ref = x / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True)
                          + 1e-5)
        np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    def test_rmsnorm_tp2_sp_matches_unsharded(self):
        ref = _tp_parity_train(1, {"normalization": "rmsnorm"})
        np.testing.assert_allclose(
            ref, _tp_parity_train(2, {"normalization": "rmsnorm"}),
            atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(
            ref, _tp_parity_train(2, {"normalization": "rmsnorm"}, sp=True),
            atol=2e-5, rtol=2e-5)

    def test_invalid_normalization_rejected(self):
        with pytest.raises(ValueError, match="normalization"):
            _cfg(normalization="batchnorm")


def test_gelu_init_stream_is_plain_two_way_split():
    """Default-gelu params come from the historical 2-way key split
    (seed-stable init for old checkpoints)."""
    from apex_tpu.models.transformer import ParallelMLP

    mlp = ParallelMLP(_cfg(position_embedding_type="learned"))
    p = mlp.init(jax.random.PRNGKey(7))
    k1, _ = jax.random.split(jax.random.PRNGKey(7))
    ref = mlp.dense_h_to_4h.init(k1)
    np.testing.assert_array_equal(np.asarray(p["dense_h_to_4h"]["weight"]),
                                  np.asarray(ref["weight"]))


@pytest.mark.slow  # composition parity sweep: slow tier (ROADMAP)


def test_moe_with_gated_activation():
    """activation threads through MoEConfig: swiglu experts get the
    unit-interleaved 2*ffn w_in and the model trains."""
    model = GPTModel(_cfg(activation="swiglu", num_moe_experts=4,
                          position_embedding_type="learned",
                          moe_expert_axis=None))
    losses, params = _losses_after_training(model)
    w_in = params["transformer"]["layers"]["mlp"]["w_in"]
    assert w_in.shape[-1] == 2 * 4 * 64      # [L, E, h, 2*ffn]
    assert losses[-1] < losses[0]


def test_gated_projection_is_bias_free():
    """LLaMA convention: the fused gate/up projection carries no bias
    (dense and MoE paths share it via utils/activations.py)."""
    from apex_tpu.transformer.moe import MoEConfig, SwitchMLP

    model = GPTModel(_cfg(activation="swiglu",
                          position_embedding_type="learned"))
    params = model.init(jax.random.PRNGKey(0))
    assert "bias" not in params["transformer"]["layers"]["mlp"][
        "dense_h_to_4h"]
    moe = SwitchMLP(MoEConfig(hidden_size=32, ffn_hidden_size=64,
                              num_experts=2, activation="swiglu",
                              expert_axis=None))
    mp = moe.init(jax.random.PRNGKey(0))
    assert "b_in" not in mp and "b_in" not in moe.spec()


class TestSlidingWindowModel:
    @pytest.mark.slow
    def test_decode_matches_full_forward(self):
        """Cached decode must reproduce the full windowed forward (window
        folded into the cache mask at real cache offsets)."""
        model = GPTModel(_cfg(sliding_window=4,
                              position_embedding_type="learned"))
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
        full = model.apply(params, tokens)
        caches = init_kv_caches(model, 2, 16)
        for i in range(10):
            logits, caches = decode_step(model, params, caches,
                                         tokens[:, i], i)
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(full[i]).astype(np.float32),
                rtol=2e-4, atol=2e-4)

    def test_window_changes_function(self):
        full = GPTModel(_cfg(position_embedding_type="learned"))
        win = GPTModel(_cfg(sliding_window=2,
                            position_embedding_type="learned"))
        params = full.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 64)
        assert not np.allclose(
            np.asarray(full.apply(params, toks), np.float32),
            np.asarray(win.apply(params, toks), np.float32), atol=1e-4)

    def test_validation(self):
        with pytest.raises(ValueError, match="sliding_window"):
            _cfg(sliding_window=0)
        # sliding_window under context parallelism is supported: the ring
        # masks with global positions (exact across chunk boundaries)
        _cfg(sliding_window=4, context_parallel_method="ring")


@pytest.mark.slow
def test_sliding_window_with_dropout_trains_windowed():
    """Regression for the dropped-mask bug: with attention dropout active
    (unfused softmax path) the window must still bind — rows beyond the
    window get zero probability, so changing far-past tokens cannot change
    the loss."""
    model = GPTModel(_cfg(sliding_window=2, attention_dropout=0.3,
                          position_embedding_type="learned"))
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, 64)
    # same rng -> same dropout; mutate a token far outside every window of
    # the last position's loss contribution... simplest check: full-vs-window
    # divergence on the dropout path
    full = GPTModel(_cfg(attention_dropout=0.3,
                         position_embedding_type="learned"))
    r = jax.random.PRNGKey(7)
    lw = model.apply(params, toks, toks, rng=r, deterministic=False)
    lf = full.apply(params, toks, toks, rng=r, deterministic=False)
    assert float(lw) != float(lf)
