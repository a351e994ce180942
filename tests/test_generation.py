"""KV-cache generation tests.

Correctness anchor: incrementally-decoded logits must match the full
(non-cached) forward pass position by position — the property that makes a
KV cache a cache and not an approximation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.generation import decode_step, generate, init_kv_caches
from apex_tpu.utils.sharding import shard_map


def _model(**kw):
    d = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
             vocab_size=64, max_position_embeddings=32,
             hidden_dropout=0.0, attention_dropout=0.0)
    d.update(kw)
    return GPTModel(TransformerConfig(**d))


class TestDecodeStep:
    @pytest.mark.slow
    def test_cached_logits_match_full_forward(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
        # full forward logits [s, b, V]
        full = model.apply(params, tokens)
        caches = init_kv_caches(model, 2, 16)
        for i in range(10):
            logits, caches = decode_step(model, params, caches,
                                         tokens[:, i], i)
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(full[i]).astype(np.float32),
                rtol=2e-4, atol=2e-4)

    @pytest.mark.slow
    def test_moe_cached_logits_match_full_forward(self):
        # TRAINING-DEFAULT capacity factor (1.25): the cache path routes
        # drop-free (round 5), and the matching baseline is the drop-free
        # serving forward — parity is unconditional in the factor, where
        # round 4 needed capacity_factor = num_experts to avoid drops
        model = _model(num_moe_experts=4, moe_top_k=2)
        assert model.config.moe_capacity_factor == 1.25  # the default
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        full = model.apply(params, tokens, moe_drop_free=True)
        caches = init_kv_caches(model, 2, 12)
        for i in range(8):
            logits, caches = decode_step(model, params, caches,
                                         tokens[:, i], i)
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(full[i]).astype(np.float32),
                rtol=2e-4, atol=2e-4)
        # the prefill (cached, batched) agrees with the decode steps too
        from apex_tpu.models.generation import _cached_forward
        caches2 = init_kv_caches(model, 2, 12)
        pre, _ = _cached_forward(model, params, caches2, tokens, 0)
        np.testing.assert_allclose(np.asarray(pre),
                                   np.asarray(full).astype(np.float32),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.slow
    def test_moe_generate_runs(self):
        model = _model(num_moe_experts=4, moe_capacity_factor=4.0)
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0, 64)
        out = generate(model, params, prompt, max_new_tokens=4)
        assert out.shape == (2, 7)

    def test_cache_smaller_than_positions_guard(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 64)
        with pytest.raises(ValueError):
            generate(model, params, prompt, max_new_tokens=8, max_len=6)


class TestGenerate:
    @pytest.mark.slow
    def test_greedy_matches_stepwise_argmax(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 64)
        out = generate(model, params, prompt, max_new_tokens=5)
        assert out.shape == (2, 9)
        np.testing.assert_array_equal(np.asarray(out[:, :4]),
                                      np.asarray(prompt))
        # reference: recompute greedily with full forwards
        cur = prompt
        for _ in range(5):
            logits = model.apply(params, cur)       # [s, b, V]
            nxt = jnp.argmax(logits[-1], axis=-1).astype(prompt.dtype)
            cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(cur))

    def test_generate_jits(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 3), 0, 64)
        f = jax.jit(lambda p, t: generate(model, p, t, max_new_tokens=4))
        out = f(params, prompt)
        assert out.shape == (1, 7)

    @pytest.mark.slow
    def test_sampling_reproducible_and_varied(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0, 64)
        r = jax.random.PRNGKey(7)
        o1 = generate(model, params, prompt, max_new_tokens=6,
                      temperature=1.0, rng=r)
        o2 = generate(model, params, prompt, max_new_tokens=6,
                      temperature=1.0, rng=r)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        o3 = generate(model, params, prompt, max_new_tokens=6,
                      temperature=1.0, rng=jax.random.PRNGKey(8))
        assert not np.array_equal(np.asarray(o1), np.asarray(o3))

    @pytest.mark.slow
    def test_top_k_restricts_support(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 3), 0, 64)
        # top_k=1 sampling == greedy
        o_top1 = generate(model, params, prompt, max_new_tokens=5,
                          temperature=1.0, top_k=1,
                          rng=jax.random.PRNGKey(2))
        o_greedy = generate(model, params, prompt, max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(o_top1),
                                      np.asarray(o_greedy))

    def test_sampling_requires_rng(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        prompt = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError):
            generate(model, params, prompt, max_new_tokens=2,
                     temperature=0.7)

    def test_eos_freezes_rows(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 3), 0, 64)
        greedy = generate(model, params, prompt, max_new_tokens=8)
        first = int(greedy[0, 3])      # force the first generated token
        out = generate(model, params, prompt, max_new_tokens=8,
                       eos_token=first)
        # once eos is emitted every later token is eos
        gen = np.asarray(out[0, 3:])
        hit = np.where(gen == first)[0]
        assert hit.size > 0
        assert (gen[hit[0]:] == first).all()


class TestGuards:
    def test_max_new_tokens_zero_rejected(self):
        """max_new_tokens=0 would make total == prompt_len, so the
        first-token write (out.at[:, prompt_len]) silently clamps onto
        the last prompt slot — must raise instead."""
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        prompt = jnp.zeros((1, 3), jnp.int32)
        with pytest.raises(ValueError, match="max_new_tokens"):
            generate(model, params, prompt, max_new_tokens=0)

    def test_top_k_below_one_rejected(self):
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        prompt = jnp.zeros((1, 3), jnp.int32)
        with pytest.raises(ValueError, match="top_k"):
            generate(model, params, prompt, max_new_tokens=2,
                     temperature=1.0, top_k=0, rng=jax.random.PRNGKey(0))

    def test_position_overflow_rejected(self):
        model = _model()   # max_position_embeddings=32
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 30), 0, 64)
        with pytest.raises(ValueError, match="max_position_embeddings"):
            generate(model, params, prompt, max_new_tokens=10)

    @pytest.mark.slow
    def test_tp_generation_matches_single_rank(self):
        """Greedy generation under TP == unsharded (full-vocab argmax after
        the vocab all-gather)."""
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer import parallel_state

        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 64)
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        ref = generate(model, params, prompt, max_new_tokens=5)

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2)
        out = shard_map(
            lambda p, t: generate(model, p, t, max_new_tokens=5),
            mesh=mesh, in_specs=(model.spec(), P()), out_specs=P(),
            check_vma=False)(params, prompt)
        parallel_state.destroy_model_parallel()
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


class TestCacheForms:
    @pytest.mark.slow
    def test_stacked_and_list_caches_agree(self):
        """The scan-form (stacked [L,...]) and the fast decode form
        (per-layer list, PERF.md round 4) must produce identical logits
        through prefill AND stepwise decode."""
        from apex_tpu.models.generation import _cached_forward

        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
        stacked = init_kv_caches(model, 2, 16)
        listed = init_kv_caches(model, 2, 16, stacked=False)
        assert isinstance(listed, list) and len(listed) == 2
        # prefill over 6 tokens, then 4 incremental steps, on both forms
        l_s, stacked = _cached_forward(model, params, stacked,
                                       tokens[:, :6], 0)
        l_l, listed = _cached_forward(model, params, listed,
                                      tokens[:, :6], 0)
        np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_l),
                                   rtol=1e-5, atol=1e-5)
        for i in range(6, 10):
            l_s, stacked = decode_step(model, params, stacked,
                                       tokens[:, i], i)
            l_l, listed = decode_step(model, params, listed,
                                      tokens[:, i], i)
            np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_l),
                                       rtol=1e-5, atol=1e-5)
        # cache contents agree leaf-for-leaf
        for l, (k_l, v_l) in enumerate(listed):
            np.testing.assert_allclose(np.asarray(stacked[0][l]),
                                       np.asarray(k_l), atol=1e-6)
            np.testing.assert_allclose(np.asarray(stacked[1][l]),
                                       np.asarray(v_l), atol=1e-6)

    @pytest.mark.slow
    def test_flat_caches_agree(self):
        """The FLAT [b, S, h*d] decode form (PERF.md round 5) must match
        the 4D list form through prefill and stepwise decode."""
        from apex_tpu.models.generation import _cached_forward

        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
        listed = init_kv_caches(model, 2, 16, stacked=False)
        flat = init_kv_caches(model, 2, 16, stacked=False, flat=True)
        assert flat[0][0].ndim == 3
        l_l, listed = _cached_forward(model, params, listed,
                                      tokens[:, :6], 0)
        l_f, flat = _cached_forward(model, params, flat, tokens[:, :6], 0)
        np.testing.assert_allclose(np.asarray(l_l), np.asarray(l_f),
                                   rtol=1e-5, atol=1e-5)
        for i in range(6, 10):
            l_l, listed = decode_step(model, params, listed,
                                      tokens[:, i], i)
            l_f, flat = decode_step(model, params, flat, tokens[:, i], i)
            np.testing.assert_allclose(np.asarray(l_l), np.asarray(l_f),
                                       rtol=1e-5, atol=1e-5)

    @pytest.mark.slow
    def test_flat_cache_kv_lengths_masks_padding(self):
        """kv_lengths must mask pad slots on the FLAT path exactly as on
        the 4D path (the flat branch initially dropped it — r5 review)."""
        model = _model()
        params = model.init(jax.random.PRNGKey(0))
        c = model.config
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        kvl = jnp.array([5, 8], jnp.int32)
        emb = model.embedding.apply(
            params["embedding"]["word_embeddings"], tokens)
        hidden = emb.transpose(1, 0, 2)[:1]      # decode one position
        outs = {}
        flat_cache0 = None
        layer0 = jax.tree.map(lambda x: x[0],
                              params["transformer"]["layers"])
        from apex_tpu.models.generation import _cached_forward
        for name, flat in (("4d", False), ("flat", True)):
            caches = init_kv_caches(model, 2, 8, stacked=False, flat=flat)
            # prefill the cache with 8 tokens' K/V, then attend one query
            # with kv_lengths = [5, 8]: row 0 must ignore slots 5..7
            _, caches = _cached_forward(model, params, caches, tokens, 0)
            if flat:
                flat_cache0 = caches[0]
            out, _ = model.transformer.layer.attention.apply(
                layer0["self_attention"], hidden, kv_cache=caches[0],
                cache_index=7, kv_lengths=kvl)
            outs[name] = np.asarray(out)
        np.testing.assert_allclose(outs["4d"], outs["flat"],
                                   rtol=1e-5, atol=1e-5)
        # and kv_lengths actually changes the result (masking is live)
        out_nolen, _ = model.transformer.layer.attention.apply(
            layer0["self_attention"], hidden, kv_cache=flat_cache0,
            cache_index=7, kv_lengths=None)
        assert not np.allclose(outs["flat"], np.asarray(out_nolen),
                               atol=1e-6)
