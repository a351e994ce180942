"""Cross-attention / encoder-decoder layer tests.

Reference: ``standalone_transformer_lm.py`` ``ParallelAttention`` cross_attn
branch and decoder ``ParallelTransformerLayer`` (inter_attention ~:1090-1115);
the reference exercises them through ``ModelType.encoder_and_decoder``
pipeline tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.transformer import (
    ParallelAttention,
    ParallelTransformer,
    ParallelTransformerLayer,
    TransformerConfig,
)
from apex_tpu.transformer.enums import AttnMaskType, AttnType, LayerType
from apex_tpu.utils.sharding import shard_map


def _cfg(**kw):
    d = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
             hidden_dropout=0.0, attention_dropout=0.0,
             attn_mask_type=AttnMaskType.causal)
    d.update(kw)
    return TransformerConfig(**d)


class TestCrossAttention:
    def test_shapes(self):
        attn = ParallelAttention(_cfg(), attn_type=AttnType.cross_attn)
        params = attn.init(jax.random.PRNGKey(0))
        assert set(params) == {"query", "key_value", "dense"}
        dec = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 32))
        enc = jax.random.normal(jax.random.PRNGKey(2), (9, 2, 32))
        out = attn.apply(params, dec, encoder_output=enc)
        assert out.shape == (6, 2, 32)

    def test_requires_encoder_output(self):
        attn = ParallelAttention(_cfg(), attn_type=AttnType.cross_attn)
        params = attn.init(jax.random.PRNGKey(0))
        dec = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 32))
        with pytest.raises(ValueError):
            attn.apply(params, dec)

    def test_not_causal_across_encoder(self):
        """Cross-attention must see the WHOLE encoder sequence: changing a
        late encoder position must affect an early decoder position (a
        causal mask would forbid that)."""
        attn = ParallelAttention(_cfg(), attn_type=AttnType.cross_attn)
        params = attn.init(jax.random.PRNGKey(0))
        dec = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 32))
        enc = jax.random.normal(jax.random.PRNGKey(2), (8, 1, 32))
        out1 = attn.apply(params, dec, encoder_output=enc)
        enc2 = enc.at[-1].add(1.0)
        out2 = attn.apply(params, dec, encoder_output=enc2)
        delta = np.abs(np.asarray(out1 - out2))[0]   # first decoder pos
        assert delta.max() > 1e-6

    def test_encoder_padding_mask(self):
        """Masked encoder positions must not influence the output."""
        attn = ParallelAttention(_cfg(), attn_type=AttnType.cross_attn)
        params = attn.init(jax.random.PRNGKey(0))
        dec = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 32))
        enc = jax.random.normal(jax.random.PRNGKey(2), (8, 1, 32))
        # True = masked out; mask the last 3 encoder positions
        mask = jnp.zeros((1, 1, 4, 8), bool).at[..., 5:].set(True)
        out1 = attn.apply(params, dec, encoder_output=enc,
                          attention_mask=mask)
        enc2 = enc.at[6].add(10.0)
        out2 = attn.apply(params, dec, encoder_output=enc2,
                          attention_mask=mask)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=1e-6)


class TestDecoderLayer:
    def test_decoder_layer_params_and_apply(self):
        layer = ParallelTransformerLayer(_cfg(), LayerType.decoder)
        params = layer.init(jax.random.PRNGKey(0))
        assert "inter_attention" in params
        assert "post_inter_attention_layernorm" in params
        dec = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 32))
        enc = jax.random.normal(jax.random.PRNGKey(2), (9, 2, 32))
        out = layer.apply(params, dec, encoder_output=enc)
        assert out.shape == (6, 2, 32)
        assert np.isfinite(np.asarray(out)).all()

    def test_encoder_layer_unchanged(self):
        layer = ParallelTransformerLayer(_cfg())
        params = layer.init(jax.random.PRNGKey(0))
        assert "inter_attention" not in params

    @pytest.mark.slow
    def test_decoder_stack_grads(self):
        model = ParallelTransformer(_cfg(), LayerType.decoder)
        params = model.init(jax.random.PRNGKey(0))
        dec = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 32))
        enc = jax.random.normal(jax.random.PRNGKey(2), (9, 2, 32))

        def loss(p, enc):
            out = model.apply(p, dec, encoder_output=enc)
            return jnp.mean(out ** 2)

        g_params = jax.grad(loss)(params, enc)
        g_enc = jax.grad(loss, argnums=1)(params, enc)
        total = sum(float(jnp.sum(jnp.abs(l)))
                    for l in jax.tree.leaves(g_params))
        assert np.isfinite(total) and total > 0
        # encoder gradient flows through cross-attention
        assert float(jnp.sum(jnp.abs(g_enc))) > 0

    def test_decoder_with_recompute(self):
        model = ParallelTransformer(_cfg(recompute=True), LayerType.decoder)
        params = model.init(jax.random.PRNGKey(0))
        dec = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 32))
        enc = jax.random.normal(jax.random.PRNGKey(2), (9, 2, 32))
        out = jax.jit(lambda p, d, e: model.apply(p, d, encoder_output=e))(
            params, dec, enc)
        assert np.isfinite(np.asarray(out)).all()


class TestEncoderDecoderModel:
    def _model(self, **kw):
        from apex_tpu.models import EncoderDecoderModel

        cfg = _cfg(vocab_size=64, max_position_embeddings=32, **kw)
        return EncoderDecoderModel(cfg)

    @pytest.mark.slow  # compile-bound mode sweep: slow tier (ROADMAP)

    def test_loss_and_logits_modes(self):
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        enc = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
        dec = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 64)
        loss = model.apply(params, enc, dec, labels)
        assert loss.shape == () and np.isfinite(float(loss))
        logits = model.apply(params, enc, dec)
        assert logits.shape == (8, 2, 64)

    @pytest.mark.slow
    def test_trains(self):
        from apex_tpu.optimizers import FusedAdam

        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-2)
        opt_state = opt.init(params)
        enc = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0, 64)
        dec = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0, 64)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(
                lambda p: model.apply(p, enc, dec, labels))(params)
            params, opt_state = opt.step(grads, params, opt_state)
            return params, opt_state, loss

        losses = []
        for _ in range(6):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    @pytest.mark.slow
    def test_encoder_padding_mask_blocks_pads(self):
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        enc = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, 64)
        dec = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, 64)
        pad = jnp.zeros((1, 12), bool).at[:, 8:].set(True)
        out1 = model.apply(params, enc, dec, enc_padding_mask=pad)
        enc2 = enc.at[0, 10].set(int(enc[0, 10]) ^ 1)
        out2 = model.apply(params, enc2, dec, enc_padding_mask=pad)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=1e-5)

    def test_asymmetric_depths(self):
        from apex_tpu.models import EncoderDecoderModel

        model = EncoderDecoderModel(
            _cfg(vocab_size=64, max_position_embeddings=32),
            num_encoder_layers=1)
        params = model.init(jax.random.PRNGKey(0))
        n_enc = params["encoder"]["layers"]["input_layernorm"]["weight"].shape[0]
        assert n_enc == 1     # stacked leading dim = encoder depth
        enc = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 64)
        dec = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, 64)
        logits = model.apply(params, enc, dec)
        assert logits.shape == (6, 2, 64)

    @pytest.mark.parametrize("sp", [False, True])
    @pytest.mark.slow
    def test_tensor_parallel_matches_single_rank(self, sp):
        """TP(+SP) sharded run == unsharded reference — exercises the
        encoder-output gather before cross-attention under a bound axis."""
        from jax.sharding import PartitionSpec as P

        from apex_tpu.models import EncoderDecoderModel
        from apex_tpu.transformer import parallel_state

        enc_t = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        dec_t = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 64)

        def run(tp, sp):
            parallel_state.destroy_model_parallel()
            mesh = parallel_state.initialize_model_parallel(
                tensor_model_parallel_size=tp)
            model = EncoderDecoderModel(_cfg(
                vocab_size=64, max_position_embeddings=32,
                sequence_parallel=sp))
            params = model.init(jax.random.PRNGKey(0))

            def loss_fn(p):
                return model.apply(p, enc_t, dec_t, labels)

            out = shard_map(
                jax.value_and_grad(loss_fn), mesh=mesh,
                in_specs=(model.spec(),),
                out_specs=(P(), model.spec()), check_vma=False)(params)
            parallel_state.destroy_model_parallel()
            return out

        ref_loss, ref_grads = run(1, False)
        tp_loss, tp_grads = run(2, sp)
        np.testing.assert_allclose(float(ref_loss), float(tp_loss),
                                   atol=2e-5, rtol=2e-5)
        for a, b_ in zip(jax.tree.leaves(ref_grads),
                         jax.tree.leaves(tp_grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-5, rtol=5e-5)

    def test_enc_lengths_matches_padding_mask(self):
        """Varlen flash path (enc_lengths) == boolean-mask fallback."""
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        enc = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
        dec = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 64)
        lengths = jnp.array([9, 12])
        pad = jnp.arange(12)[None, :] >= lengths[:, None]
        l_len = model.apply(params, enc, dec, labels, enc_lengths=lengths)
        l_mask = model.apply(params, enc, dec, labels, enc_padding_mask=pad)
        np.testing.assert_allclose(float(l_len), float(l_mask), rtol=1e-5)

    def test_both_mask_kinds_rejected(self):
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        enc = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
        dec = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 64)
        with pytest.raises(ValueError):
            model.apply(params, enc, dec,
                        enc_padding_mask=jnp.zeros((2, 12), bool),
                        enc_lengths=jnp.array([12, 12]))
